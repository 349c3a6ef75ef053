"""Run one cell of the benchmark once, on the chips of this machine.

    python3 perfbench/run.py --workload m462-plan --seed 7 --seconds 51 \
        --trace 0

Prints the checks against the reference as its last lines on standard
error, and one JSON result line as the last line of standard output.
Exits non-zero, with no result, when JAX finds no TPU or fewer chips than
the cell asks for.  `--trace 1` reports the per-layer metrics instead of
the end-to-end ones, from the program's spans and a profiler trace of the
window.  `--control 1` puts the reference, one precision below the
configuration's, in the program's place in the comparison: it must come
out not correct.
"""
import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import os                                                    # noqa: E402
import sys                                                   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from perfbench.harness import bench
    try:
        bench.run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START,
                       control="lower" if args.control else None)
    except bench.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
