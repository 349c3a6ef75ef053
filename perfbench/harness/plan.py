"""Traffic kind "plan": a closed loop of single-job plan requests.

Each request builds the job's DAG (`build_comm_dag`) and plans it through
`plan(PlanRequest(dag=..., method=...))` with a GA budget set in
generations, so a plan depends on its seed alone.  Request i gets the GA
seed `request_seed(--seed, i)`; every request plans the configuration's
own job.  A request starts only while it can be expected to end inside
the window, judged by the one before it; the first always starts.

Set-up builds the DAG once and plans it with no generations, which
compiles and runs the one fitness batch shape the window uses.  After the
window every plan, and a sample of the device simulator's answers drawn
from the seed, is compared with the reference, and every request's DAG
with the one the configuration file states.
"""
from __future__ import annotations

import sys
import time
import traceback

import numpy as np

from perfbench.harness.common import (LOWER_PRECISION, check_rng,
                                      dag_fingerprint, dag_mismatches,
                                      make_job, plan_faults, request_seed)
from perfbench.harness.reference import (RefProblem, Simulator,
                                         relative_gap)
from perfbench.harness.tap import DeviceTap

# Limits of the numbers compared, each set between the largest reading of
# sound runs and the smallest of the control (PERF.md, "Correctness").
# des_gap: device DES makespan against the float64 reference, relative.
DES_GAP_LIMIT = 1e-4
# oracle_gap: the plan's reported makespan and NCT against the reference.
ORACLE_GAP_LIMIT = 1e-10


class Loop:
    def __init__(self, cell, seed: int):
        self.cell = cell
        self.seed = int(seed)
        t = cell.traffic
        self.method = t["method"]
        self.ga = dict(t["ga"])
        self.sample_size = int(t["check"]["device_sample"])
        self.gbps = float(cell.config["cluster"]["inter_pod_gbps"])
        self.requests: list[dict] = []
        self.attempted = 0
        self.failed = 0
        self.window_s = 0.0
        # the reference's NCT of the first request's plan
        self.first_nct = float("nan")
        self._kernel_shape = None

    # -------------------------------------------------------------- plan
    def _options(self, seed: int, generations: int | None = None):
        from repro.core.ga import GAOptions
        ga = dict(self.ga)
        if generations is not None:
            ga.update(max_generations=generations, patience=generations)
        # no wall-clock limit: the budget is the generation count
        return GAOptions(seed=seed, time_limit=1e9, **ga)

    def _request(self, index: int, generations: int | None = None) -> dict:
        from repro.core.api import PlanRequest, plan
        from repro.core.schedule import build_comm_dag
        from repro.obs import span
        seed = request_seed(self.seed, index)
        t0 = time.perf_counter()
        with span("bench.dag_build"):
            dag = build_comm_dag(self.job, inter_pod_gbps=self.gbps)
        t1 = time.perf_counter()
        with span("bench.plan"):
            res = plan(PlanRequest(dag=dag, method=self.method,
                                   ga_options=self._options(seed,
                                                            generations)))
        t2 = time.perf_counter()
        return {"index": index, "seed": seed, "t0": t0,
                "t_plan": t1, "t1": t2, "dag_s": t1 - t0, "plan_s": t2 - t1,
                "wall": t2 - t0, "dag": dag, "result": res}

    # ------------------------------------------------------------ phases
    def setup(self) -> None:
        self.job = make_job(self.cell.config)
        self.tap = DeviceTap().__enter__()
        self._request(0, generations=0)

    def window(self, seconds: float) -> None:
        self.tap.recording = True
        start = time.perf_counter()
        last = 0.0
        index = 0
        while index == 0 or time.perf_counter() - start + last <= seconds:
            self.attempted += 1
            t = time.perf_counter()
            try:
                self.requests.append(self._request(index))
            except Exception:
                self.failed += 1
                traceback.print_exc(file=sys.stderr)
            last = time.perf_counter() - t
            index += 1
        self.window_s = time.perf_counter() - start
        self.tap.recording = False

    def free(self) -> None:
        self.tap.__exit__(None, None, None)

    def end_to_end(self) -> dict:
        done = self.requests
        return {"plan_s": sum(r["wall"] for r in done) / len(done),
                "nct": self.first_nct}

    # ------------------------------------------------------------ checks
    def check(self, checks, control: str | None = None) -> None:
        """Every plan against the reference, and a sample of the device
        DES's answers.  With `control`, the reference one precision below
        the configuration's takes the program's place."""
        prec = self.cell.config["precision"]
        sims: dict = {}

        def sim_of(dag):
            if id(dag) not in sims:
                sims[id(dag)] = Simulator(RefProblem.from_dag(dag))
            return sims[id(dag)]

        invalid, oracle_gap = 0, 0.0
        for r in self.requests:
            sim = sim_of(r["dag"])
            prob = sim.prob
            res = r["result"]
            if self._kernel_shape is None:
                self._kernel_shape = (*sim.W.shape, int(self.ga["pop_size"]))
            faults = plan_faults(res.x, prob.port_limits, prob.pairs())
            faults += [f"DAG differs on {k}" for k in dag_mismatches(
                dag_fingerprint(r["dag"]), self.cell.config["dag"])]
            gens = res.details.get("generations")
            if gens != self.ga["max_generations"]:
                faults.append(f"GA ran {gens} generations")
            if faults:
                print(f"request {r['index']}: {faults}", file=sys.stderr)
                invalid += 1
            if np.shape(res.x) != (prob.num_pods,) * 2:
                continue
            ref = sim.nct(res.x)
            if r["index"] == 0:
                self.first_nct = ref["nct"]
            got = {"makespan": res.makespan, "nct": res.nct}
            if control:
                got = sim.nct(res.x, precision=LOWER_PRECISION[
                    prec["host_oracle"]])
            oracle_gap = max(oracle_gap,
                             relative_gap(got["makespan"], ref["makespan"]),
                             relative_gap(got["nct"], ref["nct"]))
        checks.add("failed_requests", self.failed, 0)
        checks.add("invalid_plans", invalid, 0)
        checks.add("oracle_gap", oracle_gap, ORACLE_GAP_LIMIT)
        rows = self.tap.sample(check_rng(self.seed), self.sample_size)
        checks.add("des_gap", device_gap(rows + self._plan_rows(), sim_of,
                                         control, prec), DES_GAP_LIMIT)

    def kernel_shape(self) -> tuple[int, int, int] | None:
        """(constraints, tasks, simulations) of one fill_round call of a
        fitness batch, at the first request's real DAG sizes."""
        return self._kernel_shape

    def _plan_rows(self) -> list:
        """The device's answer for each returned plan, where the device
        evaluated that plan's genome."""
        rows = []
        for r in self.requests:
            x = np.asarray(r["result"].x)
            for call in self.tap.calls:
                if call.dag is not r["dag"]:
                    continue
                hit = np.nonzero((call.genomes == x[call.edge_u,
                                                    call.edge_v]).all(1))[0]
                if len(hit):
                    rows.append((call, int(hit[0])))
                    break
        return rows


def device_gap(rows: list, sim_of, control, prec: dict) -> float:
    """Largest relative makespan gap between the device's answers `rows`
    ((call, row) pairs of a `DeviceTap`) and the float64 reference; a
    feasibility disagreement, or no answer at all, is an infinite gap.
    With `control`, the reference one precision below the device's takes
    the device's place."""
    if not rows:
        return float("inf")
    gap = 0.0
    for call, row in rows:
        sim = sim_of(call.dag)
        x = call.topology(row)
        ref = sim.run(x)
        if control:
            low = sim.run(x, precision=LOWER_PRECISION[prec["device_des"]])
            ms, feas = low["makespan"], low["feasible"]
        else:
            ms, feas = call.makespans[row], call.feasible[row]
        if bool(feas) != ref["feasible"]:
            return float("inf")
        if ref["feasible"]:
            gap = max(gap, relative_gap(float(ms), ref["makespan"]))
    return gap
