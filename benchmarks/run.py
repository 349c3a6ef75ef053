"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run           # reduced scale
    PYTHONPATH=src python -m benchmarks.run --full    # paper scale
    PYTHONPATH=src python -m benchmarks.run --only fig6,roofline
    PYTHONPATH=src python -m benchmarks.run --trace   # + span summaries

Prints ``name,us_per_call,derived`` CSV (also written to
experiments/bench/results.csv) and, per suite, a machine-readable
``BENCH_<suite>.json`` -- written both under experiments/bench/ and at the
repo root, where the cross-PR perf-trajectory tooling reads it (the
smoke-sized des/ga/tab1 files are committed with each PR; CI runs the same
smoke command and uploads the results as artifacts).

With ``--trace`` (or ``$REPRO_BENCH_TRACE=1``) the repro.obs tracer runs
for the whole suite and every row carries a ``spans`` dict -- the per-row
delta of the span summary (count / total seconds per span name), i.e. the
jit-vs-simulate-vs-solver decomposition of that row's wall clock.  The
regression gate carries these fields but does not gate on them; the CI
smoke runs WITHOUT --trace so the wall-clock gate measures the default
(disabled, near-zero-cost) configuration.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

sys.path.insert(0, "src")
sys.path.insert(0, ".")

SUITES = ("tab1", "fig6", "fig7", "fig8", "fig9", "fig10", "fig11",
          "fleet", "kernels", "des", "ga", "robust", "chaos", "steering",
          "planes", "roofline")


def _span_delta(before: dict, after: dict) -> dict:
    """Per-row span summary: what the tracer accumulated since the last
    yielded row, as {span name: {count, total_s}} (max_s is a running
    maximum, not a delta, so it is dropped here)."""
    out = {}
    for name, row in after.items():
        prev = before.get(name, {"count": 0, "total_s": 0.0})
        count = row["count"] - prev["count"]
        if count > 0:
            out[name] = {"count": int(count),
                         "total_s": round(row["total_s"] - prev["total_s"],
                                          6)}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale microbatches and solver budgets")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(SUITES))
    ap.add_argument("--trace", action="store_true",
                    default=os.environ.get("REPRO_BENCH_TRACE", "0")
                    not in ("0", ""),
                    help="enable repro.obs tracing; attach per-row span "
                         "summaries (jit vs simulate vs solver time) to "
                         "the BENCH_*.json payloads")
    args = ap.parse_args()
    picked = [s.strip() for s in args.only.split(",") if s.strip()] or \
        list(SUITES)

    from benchmarks import (chaos_bench, des_bench, fig6_bandwidth,
                            fig7_rates, fig8_seqlen, fig9_ports,
                            fig10_realloc, fig11_exectime, fleet_bench,
                            ga_bench, kernels_bench, planes_bench,
                            robust_bench, roofline, steering_bench,
                            tab1_workloads)
    from benchmarks.common import OUT_DIR, save_json
    from repro.compile_cache import enable_compile_cache
    from repro.obs import TRACER

    enable_compile_cache()

    if args.trace:
        TRACER.enable()

    modules = {"tab1": tab1_workloads, "fig6": fig6_bandwidth,
               "fig7": fig7_rates, "fig8": fig8_seqlen,
               "fig9": fig9_ports, "fig10": fig10_realloc,
               "fig11": fig11_exectime, "fleet": fleet_bench,
               "kernels": kernels_bench, "des": des_bench,
               "ga": ga_bench, "robust": robust_bench,
               "chaos": chaos_bench, "steering": steering_bench,
               "planes": planes_bench, "roofline": roofline}

    print("name,us_per_call,derived")
    lines = ["name,us_per_call,derived"]
    t_start = time.time()
    failures = []
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.makedirs(OUT_DIR, exist_ok=True)
    for s in picked:
        mod = modules[s]
        t0 = time.time()
        rows = []
        row_spans = []
        error = None
        TRACER.clear()
        prev_summary: dict = {}
        try:
            for row in mod.run(full=args.full):
                rows.append(row)
                lines.append(row.emit())
                if args.trace:
                    cur = TRACER.summary()
                    row_spans.append(_span_delta(prev_summary, cur))
                    prev_summary = cur
        except Exception as exc:   # noqa: BLE001
            failures.append(s)
            error = f"{type(exc).__name__}: {exc}"
            print(f"{s}/ERROR,0,{type(exc).__name__}:{exc}", flush=True)
            traceback.print_exc(file=sys.stderr)
        dt = time.time() - t0
        print(f"# {s} done in {dt:.1f}s", flush=True)
        payload = {
            "suite": s, "full": args.full, "seconds": dt, "error": error,
            "rows": [{"name": r.name, "us_per_call": r.us_per_call,
                      "derived": r.derived} for r in rows]}
        if args.trace:
            for rdict, spans in zip(payload["rows"], row_spans):
                if spans:
                    rdict["spans"] = spans
            payload["spans"] = TRACER.summary()
        save_json(f"BENCH_{s}", payload)
        # mirror to the repo root: the growth loop's perf trajectory reads
        # BENCH_*.json from there, not from experiments/bench/
        save_json(f"BENCH_{s}", payload, out_dir=repo_root)
    with open(os.path.join(OUT_DIR, "results.csv"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"# total {time.time()-t_start:.1f}s -> {OUT_DIR}/results.csv",
          flush=True)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
