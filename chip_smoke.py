"""Chip smoke test: the DELTA planner's main path on one TPU chip.

    python chip_smoke.py

One process, in order:

  1. exits non-zero unless JAX's first device is a TPU (no CPU fallback);
  2. turns on the persistent compile cache (`repro.compile_cache`);
  3. builds megatron-462b at its Table I parallelism (tp=8, pp=16, dp=8,
     128 microbatches, seq 4096, 400 Gb/s per GPU: 800 inter-pod tasks on
     32 pods) and plans it through `plan(PlanRequest(method="delta-fast"))`
     with a GA budget set in generations, so the run is deterministic;
     every fitness batch must run on the jitted Pallas DES;
  4. checks the jitted DES against the exact numpy DES on the plan and on
     the three traffic-matrix baselines (1e-3 relative, same feasibility);
  5. admits a two-tenant gpt-7b fleet whose surplus pass runs the
     `fill_matvec` kernel, then checks port-ledger conservation.

Any failed check raises.  The last line of stdout is one JSON object with
the device as JAX reports it.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                 # noqa: E402
import numpy as np                                         # noqa: E402

from repro.compile_cache import enable_compile_cache       # noqa: E402
from repro.configs import PAPER_WORKLOADS, make_job        # noqa: E402
from repro.core.api import PlanRequest, plan               # noqa: E402
from repro.core.baselines import BASELINES                 # noqa: E402
from repro.core.des import DESProblem, simulate            # noqa: E402
from repro.core.des_jax import (DESOptions, JaxDES,        # noqa: E402
                                des_cache_stats)
from repro.core.ga import GAOptions                        # noqa: E402
from repro.core.schedule import build_comm_dag             # noqa: E402
from repro.fleet import FleetPlanner, FleetSpec, JobArrival  # noqa: E402
from repro.obs import TRACER                               # noqa: E402

WORKLOAD = "megatron-462b"
EXPECT_TASKS, EXPECT_PODS = 800, 32
GENERATIONS = 4
FLEET_GENERATIONS = 3
MAKESPAN_RTOL = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu():
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform!r}")
    return dev, len(devices)


def check_des_path() -> None:
    """The engine the GA gets by default must be the compiled kernel."""
    r = DESOptions().resolve()
    print(f"des path: backend={r.backend} interpret={r.interpret}",
          flush=True)
    check(r.backend == "pallas" and not r.interpret,
          f"DES resolved to backend={r.backend} interpret={r.interpret}")
    # `repro.kernels.ops` (the fleet's fill_matvec) picks compiled Pallas
    # by the same rule: JAX's default backend
    check(jax.default_backend() == "tpu",
          f"JAX's default backend is {jax.default_backend()!r}")


def spans(name: str, since: int = 0, **attrs) -> list:
    return [r for r in TRACER.records[since:] if r.name == name
            and all(r.attrs.get(k) == v for k, v in attrs.items())]


def gens_budget(g: int) -> GAOptions:
    """A GA stopped by generation count alone: no wall-clock limit and no
    early stop, so the run is the same on every machine."""
    return GAOptions(seed=0, max_generations=g, time_limit=1e9, patience=g)


def build_dag(arch_name: str):
    arch = PAPER_WORKLOADS[arch_name]
    t0 = time.perf_counter()
    job = make_job(arch, seq_len=4096,
                   microbatches=arch.plan.num_microbatches)
    dag = build_comm_dag(job, inter_pod_gbps=400.0)
    s = dag.summary()
    print(f"dag: {arch_name} tp={job.tp} pp={job.pp} dp={job.dp} "
          f"mb={job.num_microbatches} tasks={s['num_tasks']} "
          f"deps={s['num_deps']} pods={s['num_pods']} "
          f"active_pairs={len(dag.undirected_pairs())} "
          f"build_s={time.perf_counter() - t0:.3f}", flush=True)
    return dag


def plan_phase(dag, generations: int):
    """delta-fast through plan(); fitness must run on the jitted DES."""
    since = len(TRACER.records)
    misses0 = des_cache_stats()["misses"]
    t0 = time.perf_counter()
    res = plan(PlanRequest(dag=dag, method="delta-fast",
                           ga_options=gens_budget(generations)))
    wall = time.perf_counter() - t0
    batches = spans("des.simulate", since, entry="batch_genomes")
    jit_s = sum(r.dur for r in spans("des.jit", since))
    gens = spans("ga.generation", since)
    misses = des_cache_stats()["misses"] - misses0
    print(f"plan: method={res.method} nct={res.nct:.6f} "
          f"makespan_s={res.makespan:.6f} ports={res.total_ports} "
          f"generations={res.details['generations']} "
          f"evaluations={res.details['evaluations']}", flush=True)
    print(f"plan timing: wall_s={wall:.3f} compile_setup_s={jit_s:.3f} "
          f"steady_generations_s={sum(r.dur for r in gens):.3f} "
          f"per_generation_s="
          f"{[round(r.dur, 3) for r in gens]} "
          f"batch_genomes_calls={len(batches)} des_compile_misses={misses}",
          flush=True)
    check(len(batches) > 0, "no fitness batch ran on the jitted DES")
    check(misses >= 1, "the plan compiled no DES bucket")
    check(res.details["generations"] == generations,
          f"GA ran {res.details['generations']} of {generations} "
          f"generations")
    check(res.feasible and np.isfinite(res.makespan), "plan is infeasible")
    x = np.asarray(res.x)
    used = x.sum(axis=1)
    limits = np.asarray(dag.cluster.port_limits)
    check(bool((x == x.T).all()) and bool((x >= 0).all()),
          "plan is not a symmetric non-negative circuit matrix")
    check(bool((used <= limits).all()),
          f"plan breaks a pod's port budget: {used.tolist()} > "
          f"{limits.tolist()}")
    return res


def check_against_numpy(dag, plan_x) -> None:
    """Jitted DES vs the exact numpy DES on the plan and the baselines."""
    problem = DESProblem(dag)
    jd = JaxDES(problem)
    topologies = {"delta-fast": np.asarray(plan_x)}
    topologies.update({m: np.asarray(BASELINES[m](dag))
                       for m in ("prop-alloc", "sqrt-alloc", "iter-halve")})
    for name, x in topologies.items():
        ms_j, feas_j, _, _ = jd.simulate(x)
        ref = simulate(problem, x)
        check(feas_j == ref.feasible,
              f"{name}: jax feasible={feas_j}, numpy {ref.feasible}")
        rel = abs(ms_j - ref.makespan) / ref.makespan if ref.feasible \
            else 0.0
        print(f"verify: {name:10s} ports={int(x.sum())} "
              f"numpy_makespan_s={ref.makespan:.6f} "
              f"jax_makespan_s={ms_j:.6f} rel_diff={rel:.3e}", flush=True)
        check(rel <= MAKESPAN_RTOL,
              f"{name}: jax vs numpy makespan differ by {rel:.3e}")


def fleet_phase(generations: int) -> None:
    """Two gpt-7b tenants: a port-minimised donor, then a reversed-stage
    co-tenant that the surplus pass boosts (`waterfill_grants`)."""
    since = len(TRACER.records)
    arch = PAPER_WORKLOADS["gpt-7b"]
    job = make_job(arch, microbatches=8)
    placement = job.placement()
    fleet = FleetSpec(num_pods=placement.num_pods,
                      ports_per_pod=2 * max(placement.port_limits()),
                      nic_gbps=100.0)
    planner = FleetPlanner(fleet, ga_options=gens_budget(generations),
                           seed=0)
    t0 = time.perf_counter()
    donor = planner.handle(JobArrival("model", job, port_min=True))
    cot = planner.handle(JobArrival("model_t", job, reverse_stages=True))
    wall = time.perf_counter() - t0
    surplus = spans("fleet.surplus_pass", since)
    print(f"fleet: donor nct={donor['nct']:.6f} ports={donor['ports']} "
          f"donated={donor['donated_ports']}; co-tenant "
          f"nct={cot['nct']:.6f} ports={cot['ports']} "
          f"surplus_passes={len(surplus)} "
          f"realloc_batches={planner.realloc_batches} "
          f"wall_s={wall:.3f}", flush=True)
    for o in cot["realloc"]:
        print(f"fleet realloc: {o['tenant']} granted={o['granted']} "
              f"nct {o['nct_before']:.6f} -> {o['nct_after']:.6f}",
              flush=True)
    check(len(surplus) >= 1, "the surplus pass never ran")
    planner.ledger.check()
    print("fleet: ledger conservation OK", flush=True)


def main() -> None:
    dev, count = require_tpu()
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    check_des_path()
    TRACER.enable()
    t0 = time.perf_counter()
    dag = build_dag(WORKLOAD)
    s = dag.summary()
    check(s["num_tasks"] == EXPECT_TASKS and s["num_pods"] == EXPECT_PODS,
          f"{WORKLOAD}: {s['num_tasks']} tasks on {s['num_pods']} pods, "
          f"expected {EXPECT_TASKS} on {EXPECT_PODS}")
    res = plan_phase(dag, GENERATIONS)
    check_against_numpy(dag, res.x)
    fleet_phase(FLEET_GENERATIONS)
    print(f"total_s={time.perf_counter() - t0:.3f} "
          f"des_cache={des_cache_stats()}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))


if __name__ == "__main__":
    main()
