"""Shared helpers for the benchmark modules.

Every benchmark emits rows `name,us_per_call,derived`; `us_per_call` is the
wall time of the measured operation in microseconds and `derived` the
figure's metric (NCT, port ratio, solve time, ...).

Default scale: the paper's workloads with reduced microbatch counts so the
whole `python -m benchmarks.run` completes in minutes on CPU; pass --full
for paper-scale (# of MBS = 8 x PP, 600 s solver budgets).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

from repro.configs import PAPER_WORKLOADS, make_job
from repro.core.api import optimize
from repro.core.ga import GAOptions
from repro.core.milp import MILPOptions
from repro.core.schedule import build_comm_dag

OUT_DIR = os.environ.get("REPRO_BENCH_OUT", "experiments/bench")

WORKLOADS = ("gpt-7b", "megatron-177b", "mixtral-8x22b", "megatron-462b",
             "deepseek-671b")
# MILP variants run on the tractable subset by default.  mixtral-8x22b used
# to be here, but that was an artifact of the bug this repo fixed: its DAG
# silently dropped the expert-parallel all-to-all and carried only 16 DP
# tasks.  The corrected MoE DAG (912 tasks at reduced scale) needs
# Gurobi-class budgets, so only gpt-7b stays HiGHS-tractable by default;
# delta-fast covers the MoE workloads.
MILP_WORKLOADS = ("gpt-7b",)


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float
    derived: str

    def emit(self) -> str:
        line = f"{self.name},{self.us_per_call:.1f},{self.derived}"
        print(line, flush=True)
        return line


def save_json(name: str, payload, out_dir: str = OUT_DIR) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(payload, f, indent=1, default=float)


def bench_dag(workload: str, bandwidth: float = 400.0, seq_len: int = 4096,
              full: bool = False, mb: int | None = None,
              reverse: bool = False):
    arch = PAPER_WORKLOADS[workload]
    if mb is None:
        # reduced default: pp microbatches keeps the MILP variants tractable
        # under HiGHS (paper scale via --full: 8 x pp and Gurobi-level time)
        mb = arch.plan.num_microbatches if full else \
            max(arch.plan.pp, 4 if workload == "gpt-7b" else 8)
    job = make_job(arch, seq_len=seq_len, microbatches=mb)
    return build_comm_dag(job, inter_pod_gbps=bandwidth,
                          reverse_stages=reverse)


def ga_opts(full: bool) -> GAOptions:
    return GAOptions(seed=0, time_limit=120.0 if full else 25.0,
                     patience=60 if full else 25)


def milp_opts(full: bool, **kw) -> MILPOptions:
    return MILPOptions(time_limit=600.0 if full else 120.0,
                       mip_rel_gap=1e-4 if full else 2e-3, **kw)


def run_method(dag, method: str, full: bool, port_min: bool = False):
    t0 = time.time()
    res = optimize(dag, method, port_min=port_min,
                   ga_options=ga_opts(full),
                   milp_options=milp_opts(full, port_min=port_min))
    return res, time.time() - t0


def nct_str(res) -> str:
    return f"nct={res.nct:.4f};ports={res.total_ports}" if res.feasible \
        else "infeasible"
