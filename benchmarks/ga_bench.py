"""GA engine throughput: the population-array-resident DELTA-Fast hot loop
at a fixed seed and generation budget, plus `trim_ports` on its cost-gated
`auto` path and its forced batched path (the two must agree).
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmarks.common import Row, bench_dag, save_json
from repro.core.des import DESProblem, simulate
from repro.core.ga import GAOptions, TopologySpace, delta_fast, trim_ports

WORKLOAD = "megatron-177b"      # medium: 24 pods, 5 active pairs


def _opts(gens: int) -> dict:
    return dict(seed=0, max_generations=gens, patience=10**9,
                time_limit=1e9)


def run(full: bool = False) -> list[Row]:
    rows: list[Row] = []
    smoke = os.environ.get("REPRO_BENCH_SMOKE") == "1"
    mb = 16 if full else 8
    gens = 30 if full else (6 if smoke else 12)
    dag = bench_dag(WORKLOAD, full=False, mb=mb)

    t0 = time.time()
    new = delta_fast(dag, GAOptions(**_opts(gens)))
    t_new = time.time() - t0
    rows.append(Row(f"ga/vectorized/{WORKLOAD}/mb{mb}", t_new * 1e6,
                    f"seconds={t_new:.2f};gens={new.generations};"
                    f"evals={new.evaluations};makespan={new.makespan:.6f}"))

    # trim_ports: the cost-gated auto path vs the forced batched path.
    # Trim a port-saturated feasible topology (X̄ pushed through Alg. 6
    # repair) so the sweep has real work to do.
    problem = DESProblem(dag)
    space = TopologySpace(dag)
    g_fat, _ = space.repair(space.xbar.copy(), np.random.default_rng(0))
    x_fat = space.to_matrix(g_fat)
    t0 = time.time()
    xt_new = trim_ports(dag, x_fat)            # auto backend (cost-gated)
    t_tnew = time.time() - t0
    t0 = time.time()
    xt_jax = trim_ports(dag, x_fat, backend="jax")   # forced batched path
    t_tjax = time.time() - t0
    ms_new = simulate(problem, xt_new).makespan
    ms_jax = simulate(problem, xt_jax).makespan
    rows.append(Row(
        f"ga/trim_ports/{WORKLOAD}/mb{mb}", t_tnew * 1e6,
        f"seconds={t_tnew:.2f};jax_seconds={t_tjax:.2f};"
        f"ports={int(xt_new.sum())};jax_ports={int(xt_jax.sum())};"
        f"rel_makespan={(ms_jax - ms_new) / max(ms_new, 1e-12):+.2e}"))

    save_json("ga_bench", {
        "workload": WORKLOAD, "mb": mb, "generations": gens,
        "vectorized_seconds": t_new, "vectorized_makespan": new.makespan,
        "trim_auto_seconds": t_tnew, "trim_jax_seconds": t_tjax})
    return rows
