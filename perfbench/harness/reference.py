"""Plain reference for the planner's answers: an exact event-driven
simulation of one reduced inter-pod communication DAG over a fixed
topology.

It is written from the model's definition (paper Sec. IV-B: weighted
max-min fair sharing of each pod pair's circuits, Eq. 9, and of each GPU's
NIC, Eq. 10) and imports nothing of the program, so no edit to the
program's simulators can reach it.  It reads the DAG as plain numbers
(`RefProblem.from_dag`): task endpoints, flow counts, volumes and GPUs, and
the rigid-delay dependencies.

`precision` rounds every state quantity (levels, rates, remaining volume,
clock) to a narrower float after each step.  "float64" is the reference;
"float32" and "bfloat16" are the controls that stand in for the program at
the precision below the one it runs in.
"""
from __future__ import annotations

from dataclasses import dataclass

import ml_dtypes
import numpy as np

INF = float("inf")
# a start whose ready time lies within this of the clock happens now
START_SLACK_S = 1e-15
# a task whose remaining volume is below this share of its volume is done
DONE_SHARE = 1e-9

_DTYPES = {"float64": np.float64, "float32": np.float32,
           "bfloat16": ml_dtypes.bfloat16}


def rounder(precision: str):
    """Function that rounds an array to `precision` and back to float64."""
    if precision not in _DTYPES:
        raise ValueError(f"unknown precision {precision!r}")
    dt = _DTYPES[precision]
    if dt is np.float64:
        return lambda a: a
    return lambda a: np.asarray(a, dtype=np.float64).astype(dt).astype(
        np.float64)


def resolution(precision: str) -> float:
    """Relative tolerance of the event tests at `precision`: the exact
    definition's where that is coarser than rounding, else 16 units in the
    last place, so that a narrower simulation coalesces the events its
    rounding smears instead of stalling on them."""
    eps = float(ml_dtypes.finfo(_DTYPES[precision]).eps)
    return max(DONE_SHARE, 16 * eps)


@dataclass
class RefProblem:
    """One DAG as plain arrays; task 0 is the virtual source at t = 0."""

    num_pods: int
    bandwidth: float                 # bytes/s of one circuit and one NIC
    port_limits: np.ndarray          # (P,) ports each pod may wire
    src: np.ndarray                  # (n,) source pod (-1 for task 0)
    dst: np.ndarray                  # (n,) destination pod
    flows: np.ndarray                # (n,) concurrent GPU-pair flows
    volume: np.ndarray               # (n,) bytes
    src_gpus: list                   # n tuples of GPU ids
    dst_gpus: list
    dep_pre: np.ndarray              # (d,)
    dep_succ: np.ndarray
    dep_delta: np.ndarray            # seconds after pre completes

    @classmethod
    def from_dag(cls, dag) -> "RefProblem":
        tasks = dag.tasks
        deps = dag.deps
        return cls(
            num_pods=int(dag.cluster.num_pods),
            bandwidth=float(dag.cluster.nic_bandwidth),
            port_limits=np.asarray(dag.cluster.port_limits, dtype=np.int64),
            src=np.array([t.src_pod for t in tasks], dtype=np.int64),
            dst=np.array([t.dst_pod for t in tasks], dtype=np.int64),
            flows=np.array([t.flows for t in tasks], dtype=np.float64),
            volume=np.array([t.volume for t in tasks], dtype=np.float64),
            src_gpus=[tuple(t.src_gpus) for t in tasks],
            dst_gpus=[tuple(t.dst_gpus) for t in tasks],
            dep_pre=np.array([d.pre for d in deps], dtype=np.int64),
            dep_succ=np.array([d.succ for d in deps], dtype=np.int64),
            dep_delta=np.array([d.delta for d in deps], dtype=np.float64))

    @property
    def n(self) -> int:
        return len(self.volume)

    def pairs(self) -> list[tuple[int, int]]:
        """Undirected pod pairs that carry traffic."""
        return sorted({(int(min(a, b)), int(max(a, b)))
                       for a, b in zip(self.src[1:], self.dst[1:])})


class Simulator:
    """Precomputed constraint matrix and dependency lists of a problem."""

    def __init__(self, prob: RefProblem):
        self.prob = prob
        n = prob.n
        real = np.arange(1, n)
        # one link constraint per ordered pod pair with traffic (Eq. 9):
        # sum over its tasks of flows * per-flow level <= circuits * B
        links = sorted({(int(prob.src[m]), int(prob.dst[m])) for m in real})
        rows = []
        for a, b in links:
            row = np.zeros(n)
            on = real[(prob.src[real] == a) & (prob.dst[real] == b)]
            row[on] = prob.flows[on]
            rows.append(row)
        self.link_src = np.array([a for a, _ in links], dtype=np.int64)
        self.link_dst = np.array([b for _, b in links], dtype=np.int64)
        # one NIC constraint per GPU and direction (Eq. 10): sum of the
        # per-flow levels of its tasks <= B.  GPUs with the same task set
        # give the same row; one copy of each row is enough.
        seen = set()
        for side in (prob.src_gpus, prob.dst_gpus):
            members: dict[int, list[int]] = {}
            for m in real:
                for g in side[m]:
                    members.setdefault(int(g), []).append(int(m))
            for tids in members.values():
                key = tuple(sorted(tids))
                if key in seen:
                    continue
                seen.add(key)
                row = np.zeros(n)
                row[list(key)] = 1.0
                rows.append(row)
        self.W = np.array(rows).reshape(-1, n)
        self.num_links = len(links)
        self.Wbool = self.W > 0
        # dependencies by successor and by predecessor
        self.preds = [[] for _ in range(n)]
        self.succs = [[] for _ in range(n)]
        for p, s, d in zip(prob.dep_pre, prob.dep_succ, prob.dep_delta):
            self.preds[int(s)].append((int(p), float(d)))
            self.succs[int(p)].append(int(s))
        self.indegree = np.array([len(p) for p in self.preds])

    # ----------------------------------------------------------- sharing
    def caps(self, x: np.ndarray, ideal: bool) -> np.ndarray:
        B = self.prob.bandwidth
        c = np.full(len(self.W), B)
        c[:self.num_links] = INF if ideal else \
            np.asarray(x, dtype=np.float64)[self.link_src, self.link_dst] * B
        return c

    def rates(self, active: np.ndarray, caps: np.ndarray, q) -> np.ndarray:
        """Task rates (flows * per-flow level) under weighted max-min
        fairness, by progressive filling: raise every unfrozen level
        together until a constraint is full, freeze its tasks, repeat."""
        level = np.zeros(self.prob.n)
        unfrozen = active.copy()
        for _ in range(len(self.W) + 1):
            if not unfrozen.any():
                break
            used = q(self.W @ (level * active))
            share = self.W @ unfrozen.astype(np.float64)
            with np.errstate(divide="ignore", invalid="ignore"):
                room = np.where(share > 0, q((caps - used) / share), INF)
            step = room.min()
            if not np.isfinite(step):
                break
            step = max(step, 0.0)
            level = np.where(unfrozen, q(level + step), level)
            full = np.isfinite(room) & (room <= step * (1 + 1e-9) + 1e-18)
            if not full.any():
                break
            unfrozen &= ~self.Wbool[full].any(axis=0)
        return q(self.prob.flows * level * active)

    # ------------------------------------------------------- event loop
    def run(self, x: np.ndarray, ideal: bool = False,
            precision: str = "float64") -> dict:
        """Makespan, feasibility and task finish times for topology `x`
        (circuits per ordered pod pair, already scaled by any capacity
        mask)."""
        q = rounder(precision)
        tol = resolution(precision)
        prob = self.prob
        n = prob.n
        caps = self.caps(x, ideal)
        rem = prob.volume.copy()
        finish = np.full(n, INF)
        ready = np.full(n, INF)
        missing = self.indegree.copy()
        started = np.zeros(n, dtype=bool)
        done = np.zeros(n, dtype=bool)

        def complete(ms, t):
            for m in ms:
                done[m] = True
                finish[m] = t
            for m in ms:
                for s in self.succs[m]:
                    missing[s] -= 1
                    if missing[s] == 0 and not started[s]:
                        ready[s] = q(max(finish[p] + d
                                         for p, d in self.preds[s]))

        t = 0.0
        started[0] = True
        complete([0], 0.0)
        ready[(self.indegree == 0) & ~started] = 0.0
        feasible = True
        for _ in range(4 * n + 8):
            new = ~started & (missing == 0) & (
                ready <= t + max(START_SLACK_S, t * tol / 16))
            if new.any():
                started |= new
                complete([int(m) for m in np.nonzero(new & (rem <= 0))[0]],
                         t)
            if done.all():
                break
            active = started & ~done
            t_done = INF
            rates = np.zeros(n)
            left = np.full(n, INF)
            if active.any():
                rates = self.rates(active, caps, q)
                if (rates[active] <= 0).any():
                    feasible = False
                    break
                left[active] = q(rem[active] / rates[active])
                t_done = q(t + left.min())
            waiting = ~started & (missing == 0)
            t_ready = ready[waiting].min() if waiting.any() else INF
            t_next = min(t_done, t_ready)
            if not np.isfinite(t_next):
                feasible = False
                break
            rem = np.where(active, np.maximum(q(rem - rates * (t_next - t)),
                                              0.0), rem)
            # done: the volume is spent, or what is left of it takes less
            # time than the clock can resolve
            finished = active & (
                (rem <= tol * np.maximum(prob.volume, 1.0))
                | (left - (t_next - t) <= tol * t_next))
            t = t_next
            rem[finished] = 0.0
            complete([int(m) for m in np.nonzero(finished)[0]], t)
        else:
            feasible = False
        makespan = float(finish[np.isfinite(finish)].max()) if feasible \
            else INF
        return {"makespan": makespan, "feasible": feasible,
                "finish": finish}

    def comm_time(self, finish: np.ndarray) -> float:
        """Makespan less the rigid delays on the critical path: walk back
        from the last task through the predecessor that bound each
        start."""
        cur = int(np.argmax(np.where(np.isfinite(finish), finish, -INF)))
        makespan = finish[cur]
        delays = 0.0
        for _ in range(self.prob.n + 1):
            if cur == 0 or not self.preds[cur]:
                break
            p, d = max(self.preds[cur], key=lambda pd: finish[pd[0]] + pd[1])
            delays += d
            cur = p
        return float(makespan - delays)

    def nct(self, x: np.ndarray, precision: str = "float64") -> dict:
        """Makespan and NCT (critical-path communication time over that of
        the non-blocking ideal network) of topology `x`."""
        res = self.run(x, precision=precision)
        ideal = self.run(np.zeros((self.prob.num_pods,) * 2), ideal=True,
                         precision=precision)
        if not res["feasible"] or not ideal["feasible"]:
            return {"makespan": INF, "nct": INF, "feasible": False}
        return {"makespan": res["makespan"], "feasible": True,
                "nct": self.comm_time(res["finish"])
                / self.comm_time(ideal["finish"])}


def relative_gap(value: float, reference: float) -> float:
    """|value - reference| / |reference|; 0 when both are the same
    infinity, infinity when only one is finite."""
    if not np.isfinite(reference) or not np.isfinite(value):
        return 0.0 if value == reference else INF
    return abs(value - reference) / max(abs(reference), 1e-300)
