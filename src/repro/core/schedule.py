"""1F1B schedule -> full computation-communication DAG -> reduced inter-pod
communication DAG (paper Sec. III-A, Fig. 3).

The full DAG contains three node kinds:
  * compute nodes  F(r, b, s) / B(r, b, s) with fixed durations,
  * intra-pod communication nodes (fixed durations, electrical network),
  * inter-pod communication nodes (durations decided by the topology).

Dependency categories (paper Fig. 3a):
  (1) data dependencies  (activation / gradient / encoder-output arrival,
      plus the expert-parallel all-to-all of MoE stages: dispatch + combine
      per MoE layer, aggregated per (replica, microbatch, stage, direction)
      and wired between the F/B compute nodes so it contends with the PP
      transfer on the same boundary),
  (2) scheduling dependencies (1F1B op order per stage GPU),
  (3) gradient dependencies (DP sync waits for the last microbatch backward).

EP placement assumption: EP groups stride across DP replicas within a
stage (Placement.ep_groups), so the all-to-all is inter-pod even when a
replica's whole pipeline fits in one pod.  Each burst of one replica's
stage shard fans out to every other member of its group, one directed
task per peer carrying that peer's share (JobSpec.ep_a2a_peer_volume).
Under the single-replica projection (reduce_replicas=True) replica 0's
fan-out 0 -> k, k = 1 .. span-1, stands for the group: it is port-exact
at pod 0, whose egress it is, and lifts to the whole group when
x[0,k] = x[0,span-k], because the ingress k -> 0 is the rotational image
of 0 -> span-k.  The full-replica builder puts the all-to-all on every
ordered pair of each group.  Jobs with ep == 1 build DAGs bit-identical
to the pre-MoE builder.

Graph reduction replaces chains of intra-pod nodes between inter-pod tasks by
rigid-delay edges delta (Eq. 2).  Because completion-to-start edges over a
stage's op chain are quadratic in microbatch count, we prune every candidate
edge that is *dominated* by a two-edge path (o -> m -> n) with
delta1 + tau_min(m) + delta2 >= delta, where tau_min(m) = V_m / (F_m * B) is
m's minimum physical duration (valid in every feasible schedule because
Eq. 10 caps r_m <= F_m * B).  Domination is transitive, so one-hop checking
is sound; for homogeneous pipelines this brings |D| back to O(|M|).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cluster import ClusterSpec, Placement
from repro.core.dag import VIRTUAL, CommDAG, CommTask, Dep, make_virtual
from repro.core.traffic import JobSpec
from repro.kernels import ops
from repro.kernels.maxplus import TILE
from repro.obs.tracing import span


# --------------------------------------------------------------------- 1F1B
def order_1f1b(stage: int, num_stages: int, num_microbatches: int
               ) -> list[tuple[str, int]]:
    """Execution order of ('F'|'B', microbatch) ops on one stage GPU."""
    mb = num_microbatches
    warmup = min(num_stages - stage - 1, mb)
    order: list[tuple[str, int]] = [("F", b) for b in range(1, warmup + 1)]
    for i in range(1, mb - warmup + 1):
        order.append(("F", warmup + i))
        order.append(("B", i))
    for b in range(mb - warmup + 1, mb + 1):
        order.append(("B", b))
    return order


# ----------------------------------------------------------------- full DAG
@dataclass
class _Node:
    kind: str                 # comp | intra | inter
    duration: float = 0.0     # comp / intra only
    task: CommTask | None = None  # inter only (tid assigned later)


@dataclass
class FullDAG:
    """Intermediate complete computation-communication DAG."""
    nodes: list[_Node] = field(default_factory=list)
    edges: list[tuple[int, int]] = field(default_factory=list)

    def add(self, node: _Node) -> int:
        self.nodes.append(node)
        return len(self.nodes) - 1

    def link(self, u: int | None, v: int | None) -> None:
        if u is not None and v is not None:
            self.edges.append((u, v))

    def stats(self) -> dict:
        kinds = collections.Counter(n.kind for n in self.nodes)
        return {"nodes": len(self.nodes), "edges": len(self.edges),
                **dict(kinds)}


def build_full_dag(job: JobSpec, cluster: ClusterSpec,
                   placement: Placement | None = None,
                   reduce_replicas: bool = True) -> FullDAG:
    """Build the complete computation-communication DAG of one iteration."""
    placement = placement or job.placement()
    S, MB = job.pp, job.num_microbatches
    replicas = [0] if (reduce_replicas or job.dp == 1) else list(range(job.dp))
    g = FullDAG()

    def comm_node(src_pod: int, dst_pod: int, volume: float, flows: int,
                  src_gpus, dst_gpus, kind: str, tag: tuple) -> int:
        if src_pod == dst_pod:
            dur = volume / (flows * cluster.intra_pod_bandwidth)
            return g.add(_Node("intra", duration=dur))
        task = CommTask(tid=-1, src_pod=src_pod, dst_pod=dst_pod, flows=flows,
                        volume=volume, src_gpus=tuple(src_gpus),
                        dst_gpus=tuple(dst_gpus), kind=kind, tag=tag)
        return g.add(_Node("inter", task=task))

    # compute nodes per (replica, microbatch, stage)
    fwd: dict[tuple[int, int, int], int] = {}
    bwd: dict[tuple[int, int, int], int] = {}
    for r, s in itertools.product(replicas, range(S)):
        for b in range(1, MB + 1):
            fwd[(r, b, s)] = g.add(_Node("comp", duration=job.fwd_duration(s)))
            bwd[(r, b, s)] = g.add(_Node("comp", duration=job.bwd_duration(s)))

    # (2) scheduling dependencies: 1F1B op order per stage
    for r, s in itertools.product(replicas, range(S)):
        order = order_1f1b(s, S, MB)
        nodes = [fwd[(r, b, s)] if k == "F" else bwd[(r, b, s)]
                 for k, b in order]
        for u, v in zip(nodes, nodes[1:]):
            g.link(u, v)

    # (1) data dependencies via PP / xattn communications
    pp_fwd: dict[tuple[int, int, int], int] = {}
    pp_bwd: dict[tuple[int, int, int], int] = {}
    for r in replicas:
        for s in range(S - 1):
            pod_s, pod_n = placement.pod_of(r, s), placement.pod_of(r, s + 1)
            for b in range(1, MB + 1):
                cf = comm_node(pod_s, pod_n, job.pp_volume(), job.tp,
                               placement.gpu_ids(r, s),
                               placement.gpu_ids(r, s + 1),
                               "pp_fwd", (r, b, s))
                pp_fwd[(r, b, s)] = cf
                g.link(fwd[(r, b, s)], cf)
                g.link(cf, fwd[(r, b, s + 1)])
                cb = comm_node(pod_n, pod_s, job.pp_volume(), job.tp,
                               placement.gpu_ids(r, s + 1),
                               placement.gpu_ids(r, s),
                               "pp_bwd", (r, b, s + 1))
                pp_bwd[(r, b, s + 1)] = cb
                g.link(bwd[(r, b, s + 1)], cb)
                g.link(cb, bwd[(r, b, s)])
        # last stage: backward directly follows its own forward (loss);
        # covered by the scheduling chain, add the data edge for clarity.
        for b in range(1, MB + 1):
            g.link(fwd[(r, b, S - 1)], bwd[(r, b, S - 1)])

    # encoder-decoder cross-attention broadcast (whisper-style pipelines)
    if job.enc_stages and job.enc_stages < S:
        e_last = job.enc_stages - 1
        for r in replicas:
            for s_dec in range(job.enc_stages, S):
                pod_e = placement.pod_of(r, e_last)
                pod_d = placement.pod_of(r, s_dec)
                for b in range(1, MB + 1):
                    cx = comm_node(pod_e, pod_d, job.xattn_volume(), job.tp,
                                   placement.gpu_ids(r, e_last),
                                   placement.gpu_ids(r, s_dec),
                                   "xattn", (r, b, s_dec))
                    g.link(fwd[(r, b, e_last)], cx)
                    g.link(cx, fwd[(r, b, s_dec)])

    # (1c) expert-parallel all-to-all (MoE dispatch + combine per stage).
    # EP groups stride across DP replicas within a stage, so the all-to-all
    # crosses pods even when a replica's whole pipeline fits in one pod.
    # Each task carries one member's egress to one peer for one
    # (microbatch, MoE stage, direction); the projection keeps replica 0's
    # fan-out (module docstring).  The fwd a2a is wired F(s) -> a2a ->
    # F(s+1) (B(s) at the last stage) and the bwd a2a B(s) -> a2a ->
    # B(s-1): with atomic compute nodes the intra-layer dispatch/combine
    # collapses onto the stage boundary, where it contends with the PP
    # transfer -- the concurrent-demand burst the traffic-matrix view
    # obscures.
    ep_span = placement.ep_span
    if ep_span >= 2 and any(job.moe_stage_layers):
        with span("dag.ep_a2a") as sp:
            if reduce_replicas:
                # replica 0's fan-out, gated by replica 0 alone (ep_span
                # >= 2 implies dp >= ep_span, so the peers' pods exist)
                ep_groups = [([(0, k) for k in range(1, ep_span)], [0])]
            else:
                # every ordered pair of each group; collective gating:
                # every member's compute node bounds every task
                ep_groups = [([(i, j) for i in grp for j in grp if i != j],
                              list(grp)) for grp in placement.ep_groups()]
            ep_tasks, ep_bytes = 0, 0.0
            for pairs, gates in ep_groups:
                for s in range(S):
                    vol = job.ep_a2a_peer_volume(s)
                    if vol <= 0.0:
                        continue
                    for b in range(1, MB + 1):
                        for r_src, r_dst in pairs:
                            pod_s = placement.pod_of(r_src, s)
                            pod_d = placement.pod_of(r_dst, s)
                            src = placement.gpu_ids(r_src, s)
                            dst = placement.gpu_ids(r_dst, s)
                            tag = (r_src, r_dst, b, s)
                            ca = comm_node(pod_s, pod_d, vol, job.tp, src,
                                           dst, "ep_a2a_fwd", tag)
                            cb = comm_node(pod_s, pod_d, vol, job.tp, src,
                                           dst, "ep_a2a_bwd", tag)
                            # replicas never share a pod: both inter-pod
                            ep_tasks += 2
                            ep_bytes += 2 * vol
                            for r in gates:
                                g.link(fwd[(r, b, s)], ca)
                                g.link(ca, fwd[(r, b, s + 1)] if s < S - 1
                                       else bwd[(r, b, s)])
                                g.link(bwd[(r, b, s)], cb)
                                if s > 0:
                                    g.link(cb, bwd[(r, b, s - 1)])
            sp.set(tasks=ep_tasks, peers=ep_span - 1, bytes=ep_bytes)

    # (3) gradient dependencies: DP ring sync per stage after last backward
    if job.dp >= 2:
        if reduce_replicas:
            # single-replica projection: model the ring link 0 -> 1 plus the
            # isomorphic wraparound image (dp-1 -> 0) mapped onto pods 1 -> 0.
            ring_pairs = [(0, 1), (1, 0)]
        else:
            ring_pairs = [(r, (r + 1) % job.dp) for r in range(job.dp)]
        for s in range(S):
            for r_src, r_dst in ring_pairs:
                pod_s = placement.pod_of(r_src, s)
                pod_d = placement.pod_of(r_dst, s)
                dpn = comm_node(pod_s, pod_d, job.dp_volume(s), job.tp,
                                placement.gpu_ids(r_src, s),
                                placement.gpu_ids(r_dst, s),
                                "dp", (r_src, r_dst, s))
                # collective start: every participating replica must finish
                # its last backward; in the projection replicas are
                # synchronized so replica 0's suffices.
                for r in replicas:
                    g.link(bwd[(r, MB, s)], dpn)
    return g


# ---------------------------------------------------------------- reduction
def reduce_dag(full: FullDAG, cluster: ClusterSpec,
               prune_dominated: bool = True,
               meta: dict | None = None) -> CommDAG:
    """Collapse intra-pod nodes into rigid-delay edges between inter-pod
    tasks (paper Fig. 3b) with dominance pruning."""
    n = len(full.nodes)
    preds: list[list[int]] = [[] for _ in range(n)]
    succs: list[list[int]] = [[] for _ in range(n)]
    indeg = [0] * n
    for u, v in full.edges:
        succs[u].append(v)
        preds[v].append(u)
        indeg[v] += 1

    # assign tids to inter-pod tasks in topological order
    order: list[int] = []
    queue = collections.deque(i for i in range(n) if indeg[i] == 0)
    deg = list(indeg)
    while queue:
        u = queue.popleft()
        order.append(u)
        for v in succs[u]:
            deg[v] -= 1
            if deg[v] == 0:
                queue.append(v)
    if len(order) != n:
        raise ValueError("full DAG has a cycle")

    tasks: list[CommTask] = [make_virtual()]
    tid_of: dict[int, int] = {}
    for u in order:
        node = full.nodes[u]
        if node.kind == "inter":
            tid = len(tasks)
            tid_of[u] = tid
            tasks.append(dataclasses.replace(node.task, tid=tid))

    # propagate {origin inter-pod task -> max accumulated intra-pod lag}
    lag: list[dict[int, float]] = [dict() for _ in range(n)]
    edges: dict[tuple[int, int], float] = {}
    for u in order:
        node = full.nodes[u]
        acc: dict[int, float] = {}
        if not preds[u]:
            acc[VIRTUAL] = 0.0
        for p in preds[u]:
            for o, d in lag[p].items():
                if d > acc.get(o, -1.0):
                    acc[o] = d
        if node.kind == "inter":
            tid = tid_of[u]
            for o, d in acc.items():
                key = (o, tid)
                if d > edges.get(key, -1.0):
                    edges[key] = d
            lag[u] = {tid: 0.0}
        else:
            dur = node.duration
            lag[u] = {o: d + dur for o, d in acc.items()}

    if prune_dominated:
        with span("dag.prune") as sp:
            edges_in = len(edges)
            edges, how = _prune_dominated(edges, tasks, cluster)
            sp.set(edges_in=edges_in, edges_kept=len(edges), **how)

    deps = [Dep(pre, succ, delta) for (pre, succ), delta in sorted(edges.items())]
    return CommDAG(tasks=tasks, deps=deps, cluster=cluster, meta=meta or {})


def _prune_dominated(edges: dict[tuple[int, int], float],
                     tasks: list[CommTask], cluster: ClusterSpec,
                     eps: float = 1e-12
                     ) -> tuple[dict[tuple[int, int], float], dict]:
    """Drop (o, n, delta) if some 2-path o -> m -> n already enforces it:
    delta1 + tau_min[m] + delta2 >= delta - eps, in float64.

    On a TPU whose free memory holds the dense (n_pad, n_pad) matrices,
    one max-plus product decides the edges on the device and the host
    decides again, by the same predicate, the few that float32 leaves too
    close to their bound; elsewhere a loop over each edge's 2-paths decides
    them.  Both keep the same edges.  Returns the kept edges and the
    `dag.prune` span's attributes: the path, the edges rechecked on the
    host and n_pad."""
    tau_min = np.zeros(len(tasks))
    for t in tasks:
        if not t.is_virtual:
            tau_min[t.tid] = t.volume / (t.flows * cluster.nic_bandwidth)
    n_pad = -(-len(tasks) // TILE) * TILE
    if edges and _prune_fits_device(n_pad):
        kept, rechecked = _prune_device(edges, tau_min, n_pad, eps)
        path = "device"
    else:
        kept = _prune_host(edges, tau_min.tolist(), eps)
        rechecked, path = 0, "host"
    return kept, {"path": path, "rechecked": rechecked, "n_pad": n_pad}


def _prune_host(edges: dict[tuple[int, int], float], tau_min: list[float],
                eps: float) -> dict[tuple[int, int], float]:
    """The kept edges, each tested against its origin's 2-paths in turn."""
    out_of: dict[int, list[tuple[int, float]]] = collections.defaultdict(list)
    for (o, m), d in edges.items():
        out_of[o].append((m, d))
    kept: dict[tuple[int, int], float] = {}
    for (o, nn), delta in edges.items():
        dominated = False
        for m, d1 in out_of[o]:
            if m == nn:
                continue
            d2 = edges.get((m, nn))
            if d2 is not None and d1 + tau_min[m] + d2 >= delta - eps:
                dominated = True
                break
        if not dominated:
            kept[(o, nn)] = delta
    return kept


# The device prune's codes of an edge.
_KEPT, _DOMINATED, _UNDECIDED = 0, 1, 2
# The matrices' side n_pad is padded to the max-plus kernel's tile, so the
# kernel pads nothing and each job compiles once.
# Device bytes per entry of the (n_pad, n_pad) matrices: three float32
# buffers live at once (D, the kernel's transposed copy of D + tau_min, the
# product C) with the int8 codes, and one byte more covers tau_min and
# alignment (tests/test_tpu_compile.py).
_PRUNE_DEVICE_BYTES = 3 * 4 + 1 + 1


def _prune_fits_device(n_pad: int) -> bool:
    """Whether the device path runs: on a TPU (the rule by which `ops`
    picks the Pallas kernels) whose free memory holds its buffers."""
    if jax.default_backend() != "tpu":
        return False
    stats = jax.devices()[0].memory_stats() or {}
    free = stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)
    return _PRUNE_DEVICE_BYTES * n_pad * n_pad <= free


def _f32_band(delta: np.ndarray, tau_min: np.ndarray, eps: float) -> float:
    """How far the float32 test may stand from the float64 one.

    With u = 2**-24 and S = 2 max|delta| + max tau_min, which bounds every
    operand and partial sum, the float32 sum (d1 + tau) + d2 differs from
    the exact one by at most u S for converting its three operands and
    u S for each of its two additions; delta's conversion and forming
    delta -/+ band add u S / 2 each, and the float64 predicate's own
    roundings about 2**-52 S.  That is under 4.01 u S with rounding to
    nearest; 8 u S also holds where hardware rounds only faithfully (an
    error of up to one ulp an operation).  Beyond the band, the float32
    comparison decides as the float64 one with its eps does."""
    s = 2.0 * float(np.abs(delta).max()) + float(tau_min.max())
    return 8.0 * 2.0**-24 * s + eps


@functools.partial(jax.jit, static_argnames=("backend", "interpret"))
def _dominance_codes(d, tau, band, *, backend=None, interpret=None):
    """Per entry of the lag matrix d (NEG_INF where no edge): _DOMINATED
    where the best 2-path C = max_m (d[o, m] + tau[m]) + d[m, n] reaches
    d + band, _KEPT where it stays below d - band, else _UNDECIDED.  The
    diagonal is NEG_INF, so m == o and m == n take no part."""
    c = ops.maxplus(d + tau[None, :], d, backend=backend, interpret=interpret)
    return jnp.where(c >= d + band, _DOMINATED,
                     jnp.where(c < d - band, _KEPT, _UNDECIDED)
                     ).astype(jnp.int8)


def _prune_device(edges: dict[tuple[int, int], float], tau_min: np.ndarray,
                  n_pad: int, eps: float, *, backend: str | None = None,
                  interpret: bool | None = None
                  ) -> tuple[dict[tuple[int, int], float], int]:
    """The kept edges, decided on the device, and how many of them the
    host decided again in float64."""
    e = len(edges)
    keys = np.fromiter(itertools.chain.from_iterable(edges), np.int32,
                       2 * e).reshape(e, 2)
    delta = np.fromiter(edges.values(), np.float64, e)
    src, dst = keys[:, 0], keys[:, 1]
    d = np.full((n_pad, n_pad), ops.NEG_INF, np.float32)
    d[src, dst] = delta
    tau = np.zeros(n_pad, np.float32)
    tau[:len(tau_min)] = tau_min
    band = np.float32(_f32_band(delta, tau_min, eps))
    codes = np.asarray(_dominance_codes(d, tau, band, backend=backend,
                                        interpret=interpret))[src, dst]
    near = np.flatnonzero(codes == _UNDECIDED)
    if len(near):
        codes[near] = np.where(_dominated_f64(keys, delta, tau_min, eps, near),
                               _DOMINATED, _KEPT)
    keep = codes == _KEPT
    kept = dict(zip(map(tuple, keys[keep].tolist()), delta[keep].tolist()))
    return kept, len(near)


def _dominated_f64(keys: np.ndarray, delta: np.ndarray, tau_min: np.ndarray,
                   eps: float, rows: np.ndarray) -> np.ndarray:
    """The loop's float64 predicate for the edges `rows`, each vectorised
    over the out-edges of its origin, summed in the loop's order."""
    n = len(tau_min)
    flat = keys[:, 0].astype(np.int64) * n + keys[:, 1]
    by_key = np.argsort(flat)
    flat_sorted = flat[by_key]
    by_src = np.argsort(keys[:, 0], kind="stable")
    start = np.searchsorted(keys[by_src, 0], np.arange(n + 1))
    out = np.zeros(len(rows), bool)
    for i, r in enumerate(rows):
        o, nn = keys[r]
        edge = by_src[start[o]:start[o + 1]]
        m = keys[edge, 1]
        want = m.astype(np.int64) * n + nn
        at = np.minimum(np.searchsorted(flat_sorted, want), len(flat) - 1)
        two_path = delta[edge] + tau_min[m] + delta[by_key[at]]
        out[i] = bool(np.any((flat_sorted[at] == want)
                             & (two_path >= delta[r] - eps)))
    return out


# ------------------------------------------------------------------- facade
def build_comm_dag(job: JobSpec, inter_pod_gbps: float = 400.0,
                   reduce_replicas: bool = True,
                   reverse_stages: bool = False,
                   cluster: ClusterSpec | None = None,
                   prune_dominated: bool = True) -> CommDAG:
    """JobSpec -> reduced inter-pod CommDAG (the paper's (M, D) input).

    Traced as the `dag.build` span, with the reduced DAG's task,
    dependency and pod counts and its expert-parallel all-to-all tasks."""
    with span("dag.build") as sp:
        placement = job.placement(reverse_stages)
        if cluster is None:
            cluster = job.cluster(inter_pod_gbps,
                                  reverse_stages=reverse_stages)
        full = build_full_dag(job, cluster, placement,
                              reduce_replicas=reduce_replicas)
        meta = {"job": job.name, "full_dag": full.stats(),
                "reduce_replicas": reduce_replicas,
                "reverse_stages": reverse_stages,
                "inter_pod_gbps": inter_pod_gbps}
        dag = reduce_dag(full, cluster, prune_dominated=prune_dominated,
                         meta=meta)
        sp.set(tasks=dag.num_real_tasks, deps=len(dag.deps),
               pods=cluster.num_pods,
               ep_tasks=sum(t.kind.startswith("ep_a2a") for t in dag.tasks))
    return dag
