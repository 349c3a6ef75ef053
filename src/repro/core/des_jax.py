"""JAX discrete-event simulator: fixed-trip-count, vmap-able over topologies.

TPU-native adaptation of the paper's "ParallelEvalDES" (Alg. 3 line 2): the
simulator state is a pytree of fixed-shape arrays and every state transition
is one `lax.while_loop` step, so a whole GA population evaluates as a single
batched XLA computation via `jax.vmap` (instead of the paper's 4 CPU
threads).  Semantics match `repro.core.des.simulate` exactly (validated by
tests/test_des_jax.py); only makespan/feasibility/start/finish are produced
(critical-path extraction stays on the numpy engine).

Three layers make repeated evaluation cheap (paper Sec. V's dual-track
acceleration argument only pays off when per-evaluation cost is flat):

  * the event loop advances to the next *distinct* event time each trip and
    retires every completion AND every start landing there in one step, so
    the trip count is bounded by distinct event times (<= 2n + eps), not by
    a per-task event budget.  Each trip's dependency readiness is one
    masked max-plus pass over a dense (n, n) lag matrix built once per
    simulation, streamed instead of scattered over the d dependencies; an
    unfinished predecessor's finish is INF, so a finite ready time alone
    says every predecessor is done;
  * the inner max-min fair-share rounds run their fused (used, denom)
    reduction pair through `repro.kernels.waterfill` (Pallas on TPU, dense
    jnp `ref` oracle as the CPU/interpret fallback, the legacy segment-sum
    path kept as `backend='segment'`), selectable via `DESOptions` or
    ``REPRO_DES_BACKEND``;
  * problems are padded up to quantized (tasks, deps, incidence, links)
    buckets and the jitted entry points live in a module-level LRU keyed by
    the bucket signature, so fleet replans, ensemble members, and trim
    candidates whose problems land in an existing bucket reuse compiled
    executables instead of re-jitting per `JaxDES(...)` instance (cache
    hit/miss counters: `des_cache_stats()`).

Bucket padding reuses the ensemble ghost semantics (`stack_problems`):
ghost tasks are born done, ghost deps target the virtual task, ghost
incidence entries carry zero weight, so padded results are identical to
the exact-shape simulation up to float summation order.
"""
from __future__ import annotations

import functools
import math
import os
from collections import OrderedDict
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.des import DESProblem
from repro.obs import get_counter, get_gauge, get_logger, span

INF = jnp.inf

_log = get_logger("repro.des_jax")

# compile-cache accounting lives in the shared metrics registry so callers
# (e.g. a FleetPlanner) can read *scoped* deltas instead of process-wide
# totals; `des_cache_stats()` stays the dict-shaped view of the same series
_HITS = get_counter("des_compile_hits_total",
                    "simulator constructions reusing a compiled bucket")
_MISSES = get_counter("des_compile_miss_total",
                      "simulator constructions forcing an XLA recompile")
_EVICTIONS = get_counter("des_compile_evictions_total",
                         "compile-cache LRU evictions")
_ENTRIES = get_gauge("des_compile_cache_entries",
                     "live compile-cache buckets")

MAXMIN_BACKENDS = ("auto", "pallas", "ref", "segment")


# ------------------------------------------------------------------ options
@dataclass(frozen=True)
class DESOptions:
    """Engine knobs for `JaxDES`/`EnsembleJaxDES`.

    Every ``None`` field resolves from the environment (so benchmarks and
    fleet deployments can flip backends without code changes):

      backend            $REPRO_DES_BACKEND or 'auto'
                         ('auto' -> 'pallas' on TPU, 'ref' elsewhere;
                          'segment' keeps the pre-kernel segment-sum path)
      interpret          Pallas interpret mode ('auto': on iff not on TPU)
      bucket             $REPRO_DES_BUCKET != '0'   (default on)
      bucket_quantum     $REPRO_DES_BUCKET_QUANTUM  (default 64; tasks,
                         deps and incidence entries round up to this)
      bucket_quantum_cons $REPRO_DES_BUCKET_QUANTUM_CONS (default 8; the
                         link and NIC constraint blocks round up to this)

    `warn_on_miss` logs a warning whenever constructing the simulator lands
    in a new compile bucket (an XLA recompile); the fleet loop sets it so
    jit churn inside online replanning is visible in benchmark logs.
    """

    backend: str | None = None
    interpret: bool | None = None
    bucket: bool | None = None
    bucket_quantum: int | None = None
    bucket_quantum_cons: int | None = None
    warn_on_miss: bool = False

    def resolve(self) -> "ResolvedDESOptions":
        backend = self.backend or os.environ.get(
            "REPRO_DES_BACKEND", "").strip() or "auto"
        if backend not in MAXMIN_BACKENDS:
            raise ValueError(f"unknown DES backend {backend!r}; "
                             f"pick from {MAXMIN_BACKENDS}")
        on_tpu = jax.default_backend() == "tpu"
        if backend == "auto":
            backend = "pallas" if on_tpu else "ref"
        interpret = self.interpret if self.interpret is not None \
            else not on_tpu
        bucket = self.bucket if self.bucket is not None \
            else os.environ.get("REPRO_DES_BUCKET", "1") != "0"
        q = int(self.bucket_quantum
                or os.environ.get("REPRO_DES_BUCKET_QUANTUM", "64"))
        qc = int(self.bucket_quantum_cons
                 or os.environ.get("REPRO_DES_BUCKET_QUANTUM_CONS", "8"))
        return ResolvedDESOptions(backend=backend, interpret=bool(interpret),
                                  bucket=bool(bucket), quantum=max(q, 1),
                                  quantum_cons=max(qc, 1),
                                  warn_on_miss=self.warn_on_miss)


@dataclass(frozen=True)
class ResolvedDESOptions:
    backend: str
    interpret: bool
    bucket: bool
    quantum: int
    quantum_cons: int
    warn_on_miss: bool


class PadSpec(NamedTuple):
    """Padded array sizes: tasks, deps, incidence entries, link constraints
    and total constraints (links + NIC classes, by position in `caps`)."""
    n: int
    d: int
    e: int
    links: int
    cons: int

    @classmethod
    def exact(cls, p: DESProblem) -> "PadSpec":
        return cls(n=p.n, d=len(p.dep_pre), e=len(p.con_task),
                   links=p.num_link_cons, cons=p.num_cons)

    def bucketed(self, opt: ResolvedDESOptions) -> "PadSpec":
        q, qc = opt.quantum, opt.quantum_cons
        links = _round_up(self.links, qc)
        return PadSpec(n=_round_up(self.n, q), d=_round_up(self.d, q),
                       e=_round_up(self.e, q), links=links,
                       cons=links + _round_up(self.cons - self.links, qc))


def _round_up(v: int, q: int) -> int:
    return int(math.ceil(max(int(v), 1) / q) * q)


def default_max_events(n: int) -> int:
    """Safety bound on event-loop trips: every trip retires at least one
    start or one completion event (see `_simulate`), and each task does
    each exactly once."""
    return 2 * int(n) + 16


class DESArrays(NamedTuple):
    """Static problem arrays (all jnp) for the JAX DES."""
    volume: jax.Array          # (n,)
    flows: jax.Array           # (n,)
    dep_pre: jax.Array         # (d,)
    dep_succ: jax.Array        # (d,)
    dep_delta: jax.Array       # (d,)
    con_task: jax.Array        # (e,) incidence: task index
    con_id: jax.Array          # (e,) incidence: constraint index
    con_w: jax.Array           # (e,) weight on phi (F_m for links, 1 for NIC)
    link_pair_a: jax.Array     # (L,) src pod per link constraint
    link_pair_b: jax.Array     # (L,) dst pod per link constraint
    task_valid: jax.Array    # (n,) False for padding ghost tasks
    num_cons: int
    num_link_cons: int
    nic_bandwidth: float
    n: int

    @classmethod
    def from_problem(cls, problem: DESProblem,
                     pad: PadSpec | None = None) -> "DESArrays":
        pad = pad or PadSpec.exact(problem)
        fields = _problem_fields(problem, pad)
        return cls(**{k: jnp.asarray(v) for k, v in fields.items()},
                   num_cons=pad.cons, num_link_cons=pad.links,
                   nic_bandwidth=1.0,   # rescaled (see volume)
                   n=pad.n)


def _pad_to(a: np.ndarray, size: int, fill) -> np.ndarray:
    """Right-pad a 1-D array to `size` with `fill`."""
    a = np.asarray(a)
    if len(a) == size:
        return a
    out = np.full(size, fill, dtype=a.dtype)
    out[:len(a)] = a
    return out


def _problem_fields(p: DESProblem, pad: PadSpec) -> dict[str, np.ndarray]:
    """One problem's DES arrays padded to `pad` with ghost semantics.

      * ghost tasks: volume 0, flows 1, `task_valid` False -- born done,
        never scheduled (see `_simulate`);
      * ghost deps: (0 -> 0, delta 0) -- target the virtual task, which is
        done at t=0, so they never gate readiness;
      * ghost incidence entries: (task 0, constraint 0, weight 0) -- zero
        contribution to every used/denom reduction;
      * ghost link constraints: pair (0, 0) -- capacity x[0,0] * B == 0
        with no members, never binding;
      * ghost NIC constraints: capacity B with no members, never binding.

    Constraint ids are remapped so the NIC block starts at the padded link
    count (the caps vector in `_simulate` is [links..., NICs...] by
    position).  Unit rescaling: volumes in "seconds at one-circuit rate"
    (B == 1) keeps every quantity O(1) so the simulation stays accurate in
    float32 (x64 disabled).
    """
    cp = p.con_ptr
    con_id = np.repeat(np.arange(p.num_cons), np.diff(cp))
    con_id = np.where(con_id >= p.num_link_cons,
                      con_id + (pad.links - p.num_link_cons), con_id)
    pairs = np.array(p.pairs, dtype=np.int32).reshape(-1, 2)
    if p.volume[1:].min(initial=np.inf) <= 0:
        raise ValueError("JAX DES requires positive real-task volumes")
    return {
        "volume": _pad_to(p.volume / p.B, pad.n, 0.0),
        "flows": _pad_to(p.flows, pad.n, 1.0),
        "dep_pre": _pad_to(p.dep_pre.astype(np.int32), pad.d, 0),
        "dep_succ": _pad_to(p.dep_succ.astype(np.int32), pad.d, 0),
        "dep_delta": _pad_to(p.dep_delta, pad.d, 0.0),
        "con_task": _pad_to(p.con_task.astype(np.int32), pad.e, 0),
        "con_id": _pad_to(con_id.astype(np.int32), pad.e, 0),
        "con_w": _pad_to(p.con_w, pad.e, 0.0),
        "link_pair_a": _pad_to(pairs[:, 0], pad.links, 0),
        "link_pair_b": _pad_to(pairs[:, 1], pad.links, 0),
        "task_valid": _pad_to(np.ones(p.n, dtype=bool), pad.n, False),
    }


# --------------------------------------------------------- fair-share rates
def _dense_incidence(arr: DESArrays) -> jax.Array:
    """(C, n) constraint-task weight matrix for the kernel backends (ghost
    incidence entries scatter zero weight)."""
    return jnp.zeros((arr.num_cons, arr.n), dtype=arr.con_w.dtype) \
        .at[arr.con_id, arr.con_task].add(arr.con_w)


def _maxmin(arr: DESArrays, active: jax.Array, caps: jax.Array,
            backend: str = "segment", interpret: bool = False,
            W: jax.Array | None = None) -> jax.Array:
    """Weighted max-min fair task rates (progressive filling).

    Each filling round needs, per constraint c, the fused reduction pair
    ``used_c = sum_m W[c,m] phi_m active_m`` / ``denom_c = sum_m W[c,m]
    unfrozen_m``.  Backend 'segment' computes it as one stacked
    `segment_sum` over the incidence entries; 'pallas'/'ref' stream the
    dense incidence matrix through `repro.kernels.waterfill.fill_round`
    (one MXU pass for both right-hand sides on TPU, a dense jnp matmul on
    the ref oracle)."""
    n, C = arr.n, arr.num_cons
    dense = backend != "segment"
    if dense and W is None:
        W = _dense_incidence(arr)
    if dense:
        from repro.kernels import ops
        active_f = active.astype(caps.dtype)

    # hoist the loop-invariant active-membership weights out of the filling
    # loop; `active` is fixed for the duration of one rate computation
    act_w = jnp.where(active[arr.con_task], arr.con_w, 0.0)

    def cond(state):
        i, phi, unfrozen = state
        return jnp.logical_and(i < C + 1, unfrozen.any())

    def body(state):
        i, phi, unfrozen = state
        if dense:
            used, denom = ops.fill_round(W, phi * active_f,
                                         unfrozen.astype(caps.dtype),
                                         backend=backend,
                                         interpret=interpret)
        else:
            unf_w = jnp.where(unfrozen[arr.con_task], arr.con_w, 0.0)
            # one fused segment reduction for (used, denom) instead of two
            used, denom = jax.ops.segment_sum(
                jnp.stack([act_w * phi[arr.con_task], unf_w], axis=1),
                arr.con_id, num_segments=C).T
        slack = caps - used
        alpha_c = jnp.where(denom > 0, slack / jnp.maximum(denom, 1e-300), INF)
        alpha = jnp.maximum(jnp.min(alpha_c), 0.0)
        phi = jnp.where(unfrozen, phi + alpha, phi)
        sat = jnp.isfinite(alpha_c) & (alpha_c <= alpha * (1 + 1e-9) + 1e-18)
        task_sat = jnp.zeros(n, dtype=bool).at[arr.con_task].max(
            sat[arr.con_id])
        unfrozen = unfrozen & ~task_sat
        return i + 1, phi, unfrozen

    _, phi, _ = jax.lax.while_loop(
        cond, body, (0, jnp.zeros(n), active))
    return arr.flows * phi * active


# --------------------------------------------------------------- event loop
def _ready_fn(arr: DESArrays):
    """``finish -> ready``: each task's earliest start, the largest
    ``finish[pre] + delta`` over its dependencies (0 with none).

    The lags are scattered once into a dense (n, n) matrix, -INF where no
    dependency runs (the largest delta of duplicates), and each call
    reduces over the predecessor axis, so the successors stay on the
    lanes: a TPU scatter over the d dependencies retires its elements one
    after another, while this masked max streams through the vector
    units.  The mask is needed: an unfinished predecessor's INF finish
    plus a finite -1e30 stays INF in float32, and INF + -INF is NaN."""
    lags = jnp.full((arr.n, arr.n), -INF, dtype=arr.dep_delta.dtype).at[
        arr.dep_pre, arr.dep_succ].max(arr.dep_delta)

    def ready(finish):
        lag = jnp.where(lags > -INF, finish[:, None] + lags, -INF)
        return jnp.maximum(jnp.max(lag, axis=0), 0.0)
    return ready


class _StaticCfg(NamedTuple):
    """Hashable trace-static DES configuration (one compile bucket)."""
    n: int
    num_cons: int
    num_link_cons: int
    P: int
    max_events: int
    backend: str
    interpret: bool
    members: int            # 0 = single problem, M = stacked ensemble


def _simulate(arr: DESArrays, x: jax.Array, ideal_flag: jax.Array,
              mask: jax.Array, max_events: int, backend: str = "segment",
              interpret: bool = False) -> tuple[jax.Array, jax.Array,
                                                jax.Array, jax.Array,
                                                jax.Array]:
    """Returns (makespan, feasible, start, finish, trips), `trips` being
    the event loop's trip count.

    Event-retirement loop: every trip computes the active fair-share rates
    once, advances to the next distinct event time, and retires *all*
    events landing there -- every completion inside the float-coalescing
    band around `t_next` and every start whose (post-completion) ready
    time has arrived.  Each trip therefore retires at least one start or
    completion, bounding the trip count by the number of distinct event
    times (`default_max_events`), independent of how many tasks share one.

    ``mask`` is the (P, P) per-link availability factor (1 = healthy,
    0 = dark, fractional = partially failed plane set).  It multiplies the
    link capacities only -- NIC caps are pod-local and unaffected -- and is
    a *traced* operand, so pricing a failure never leaves the compile
    bucket the healthy plan was jitted into.
    """
    B = arr.nic_bandwidth
    # cap dtype follows the simulation dtype: hard-coding float64 is a
    # silent no-op downcast to float32 under default x64-disabled jax
    link_caps = x[arr.link_pair_a, arr.link_pair_b].astype(
        arr.volume.dtype) * mask[arr.link_pair_a, arr.link_pair_b].astype(
        arr.volume.dtype) * B
    link_caps = jnp.where(ideal_flag, INF, link_caps)
    caps = jnp.concatenate(
        [link_caps, jnp.full(arr.num_cons - arr.num_link_cons, B)])
    # dense incidence for the kernel backends, built once per simulation
    # (one scatter) and reused by every fair-share round of every event
    W = _dense_incidence(arr) if backend != "segment" else None
    ready_at = _ready_fn(arr)

    eps = 1e-6 if arr.volume.dtype == jnp.float32 else 1e-12
    veps = 1e-5 if arr.volume.dtype == jnp.float32 else 1e-9
    # tasks whose remaining *time* is below the float time resolution at t
    # complete too -- otherwise `t + dt == t` stalls the simulation
    teps = 1e-5 if arr.volume.dtype == jnp.float32 else 1e-12

    def retire_starts(t_now, started, finish):
        """Start every pending task whose ready time has arrived at
        `t_now`; returns the next pending ready time as well.  A task
        with an unfinished predecessor reads INF (that finish is INF)."""
        ready = jnp.where(started, INF, ready_at(finish))
        newly = ready <= t_now * (1 + eps) + eps * 1e-3
        t_ready = jnp.min(jnp.where(newly, INF, ready))
        return started | newly, newly, ready, t_ready

    # initial state: virtual task 0 done at t=0.  Padding ghost tasks
    # (task_valid False -- bucket padding or ensemble members stacked to a
    # common shape) are born done with finish 0, so they never contend,
    # never gate readiness and never contribute to the makespan.
    rem = arr.volume
    started = jnp.logical_not(arr.task_valid).at[0].set(True)
    done = started
    start = jnp.where(started, 0.0, INF)
    finish = start
    # retire the t=0 start events before the loop
    started, newly, ready, t_ready = retire_starts(0.0, started, finish)
    start = jnp.where(newly, ready, start)
    feasible = jnp.array(True)

    def cond(state):
        i, t, *_ , feasible = state
        return (i < max_events) & jnp.isfinite(t) & feasible

    def body(state):
        i, t, t_ready, rem, started, done, start, finish, feasible = state
        active = started & ~done
        rates = _maxmin(arr, active, caps, backend, interpret, W)
        feasible = feasible & jnp.all(jnp.where(active, rates > 0, True))
        dt_done = jnp.where(active & (rates > 0), rem / jnp.maximum(rates,
                                                                    1e-300),
                            INF)
        t_complete = t + jnp.min(dt_done)
        t_next = jnp.minimum(t_complete, t_ready)
        dt = jnp.maximum(t_next - t, 0.0)
        rem = jnp.where(active, jnp.maximum(rem - rates * dt, 0.0), rem)
        dt_rem = dt_done - dt   # remaining volume / rate after the advance
        newdone = active & jnp.isfinite(t_next) & (
            (rem <= veps * jnp.maximum(arr.volume, 1e-9))
            | (dt_rem <= teps * jnp.maximum(t_next, 1e-9)))
        finish = jnp.where(newdone, t_next, finish)
        done = done | newdone
        # retire the start events at t_next in the same trip (readiness
        # recomputed against the post-completion finish state)
        started, newly, ready, t_ready = retire_starts(t_next, started,
                                                       finish)
        start = jnp.where(newly, ready, start)
        all_done = done.all()
        t_out = jnp.where(all_done, -INF, t_next)  # exit condition
        return (i + 1, t_out, t_ready, rem, started, done, start, finish,
                feasible)

    state = (0, jnp.array(0.0), t_ready, rem, started, done, start, finish,
             feasible)
    state = jax.lax.while_loop(cond, body, state)
    trips, _, _, _, _, done, start, finish, feasible = state
    feasible = feasible & done.all()
    makespan = jnp.where(feasible, jnp.max(jnp.where(jnp.isfinite(finish),
                                                     finish, -INF)), INF)
    return makespan, feasible, start, finish, trips


# ------------------------------------------------- compiled-executable LRU
# array-valued DESArrays leaves: everything before the first static field,
# derived from the NamedTuple itself so a future field insertion/reorder
# cannot silently misalign the leaves <-> statics reassembly
_ARRAY_FIELDS = DESArrays._fields[:DESArrays._fields.index("num_cons")]


class CompiledDES:
    """Lazily-built jitted entry points for one compile bucket.

    Shared by every `JaxDES`/`EnsembleJaxDES` whose padded problem lands in
    the bucket: the jitted callables close over only the static `_StaticCfg`
    and take the problem arrays as arguments, so XLA compiles each entry
    point once per bucket (batch-size variations are handled by jax's own
    per-shape cache on the same callable)."""

    def __init__(self, cfg: _StaticCfg):
        self.cfg = cfg

    def _rebuild(self, leaves: tuple) -> DESArrays:
        cfg = self.cfg
        return DESArrays(*leaves, num_cons=cfg.num_cons,
                         num_link_cons=cfg.num_link_cons,
                         nic_bandwidth=1.0, n=cfg.n)

    def _run(self, leaves, x, ideal, mask):
        cfg = self.cfg
        return _simulate(self._rebuild(leaves), x, ideal, mask,
                         cfg.max_events, cfg.backend, cfg.interpret)

    def _scatter(self, g, eu, ev):
        P = self.cfg.P
        x = jnp.zeros((P, P), dtype=g.dtype)
        return x.at[eu, ev].set(g).at[ev, eu].set(g)

    def _traced(self, entry: str, fn):
        """First-call `des.jit` span around a jitted entry point: the
        first invocation pays trace + XLA compile, so its duration IS the
        jit cost the benchmark span summaries separate from steady-state
        simulate time.  (Later batch-shape recompiles inside jax's own
        per-shape cache are not individually distinguished.)"""
        cfg = self.cfg
        state = {"first": True}

        def wrapper(*args):
            if state["first"]:
                state["first"] = False
                with span("des.jit", entry=entry, n=cfg.n,
                          members=cfg.members, backend=cfg.backend):
                    return fn(*args)
            return fn(*args)
        return wrapper

    @functools.cached_property
    def single(self):
        return self._traced("single", jax.jit(self._run))

    @functools.cached_property
    def batch_x(self):
        def f(leaves, xs, mask):
            return jax.vmap(
                lambda x: self._run(leaves, x, jnp.asarray(False),
                                    mask)[:2])(xs)
        return self._traced("batch_x", jax.jit(f))

    @functools.cached_property
    def batch_genomes(self):
        # (makespan, feasible, trips) per genome: each lane's own trip
        # count, though the vmapped loop runs as long as its slowest lane
        def f(leaves, genomes, eu, ev, mask):
            def one(g):
                ms, feas, _, _, trips = self._run(
                    leaves, self._scatter(g, eu, ev), jnp.asarray(False),
                    mask)
                return ms, feas, trips
            return jax.vmap(one)(genomes)
        return self._traced("batch_genomes", jax.jit(f))

    @functools.cached_property
    def ensemble_genomes(self):
        # masks carries a leading member axis (M, P, P): the robust path
        # passes jnp.ones, the k-failure objective one failure scenario
        # per stacked member -- same compiled executable either way
        def one_member(leaves, x, mask):
            ms, feas, _, _, trips = self._run(leaves, x, jnp.asarray(False),
                                              mask)
            return ms, feas, trips

        def one_genome(leaves, g, eu, ev, masks):
            x = self._scatter(g, eu, ev)
            return jax.vmap(one_member, in_axes=(0, None, 0))(leaves, x,
                                                              masks)

        return self._traced(
            "ensemble_genomes",
            jax.jit(jax.vmap(one_genome,
                             in_axes=(None, 0, None, None, None))))


_COMPILE_CACHE: OrderedDict[tuple, CompiledDES] = OrderedDict()


def _cache_max() -> int:
    return int(os.environ.get("REPRO_DES_CACHE_SIZE", "64"))


def des_cache_stats() -> dict:
    """Module-level compile-cache counters: `hits` are simulator
    constructions that reused an existing bucket's jitted executables,
    `misses` forced a fresh XLA compile.  Backed by the `repro.obs`
    registry (`des_compile_*` series), so planner-scoped deltas are
    available via `REGISTRY.scope()`."""
    return {"hits": int(_HITS.value()), "misses": int(_MISSES.value()),
            "evictions": int(_EVICTIONS.value()),
            "entries": len(_COMPILE_CACHE)}


def des_cache_clear() -> None:
    _COMPILE_CACHE.clear()
    for c in (_HITS, _MISSES, _EVICTIONS):
        c.reset()
    _ENTRIES.set(0)


def _compiled_for(cfg: _StaticCfg, pad: PadSpec,
                  warn_on_miss: bool = False) -> tuple[CompiledDES, bool]:
    """The bucket's jitted entry points, and whether the bucket existed."""
    key = (cfg, pad.d, pad.e)
    ent = _COMPILE_CACHE.get(key)
    if ent is not None:
        _HITS.inc()
        _COMPILE_CACHE.move_to_end(key)
        return ent, True
    # jit churn: every miss increments des_compile_miss_total whether or
    # not the caller opted into the warning, so the counter is the one
    # authoritative recompile signal (the log line is just its echo)
    _MISSES.inc()
    if warn_on_miss:
        _log.warning(
            "DES compile-cache miss: new bucket n=%d deps=%d inc=%d "
            "cons=%d/%d P=%d members=%d backend=%s -- XLA recompile inside "
            "a hot loop; widen the bucket quanta if this repeats",
            cfg.n, pad.d, pad.e, cfg.num_link_cons, cfg.num_cons, cfg.P,
            cfg.members, cfg.backend)
    ent = CompiledDES(cfg)
    _COMPILE_CACHE[key] = ent
    while len(_COMPILE_CACHE) > _cache_max():
        _COMPILE_CACHE.popitem(last=False)
        _EVICTIONS.inc()
    _ENTRIES.set(len(_COMPILE_CACHE))
    return ent, False


def _host_results(sp, ms, feas, trips) -> tuple[np.ndarray, np.ndarray]:
    """(makespans, feasible) on the host.  With the `des.simulate` span
    `sp` live, the lanes' trip counts come back in the same transfer and
    go on the span: `trips` (the slowest lane's, which every lane of the
    vmapped loop runs) and `lane_trips` (their sum); otherwise they stay
    on the device."""
    if not sp.live:
        return np.asarray(ms), np.asarray(feas)
    ms, feas, trips = jax.device_get((ms, feas, trips))
    sp.set(trips=int(trips.max()), lane_trips=int(trips.sum()))
    return np.asarray(ms), np.asarray(feas)


# ------------------------------------------------------------------ engines
class JaxDES:
    """Convenience wrapper: single + batched simulation of a CommDAG."""

    def __init__(self, problem: DESProblem, max_events: int | None = None,
                 options: DESOptions | None = None):
        self.problem = problem
        self.options = options or DESOptions()
        with span("des.prepare") as sp:
            ropt = self.options.resolve()
            pad = PadSpec.exact(problem)
            if ropt.bucket:
                pad = pad.bucketed(ropt)
            self.pad = pad
            self.arrays = DESArrays.from_problem(problem, pad)
            self.max_events = int(max_events or default_max_events(pad.n))
            cfg = _StaticCfg(n=pad.n, num_cons=pad.cons,
                             num_link_cons=pad.links,
                             P=problem.dag.cluster.num_pods,
                             max_events=self.max_events,
                             backend=ropt.backend,
                             interpret=ropt.interpret, members=0)
            self._compiled, hit = _compiled_for(cfg, pad, ropt.warn_on_miss)
            self._leaves = tuple(getattr(self.arrays, f)
                                 for f in _ARRAY_FIELDS)
            sp.set(n=pad.n, hit=hit)
        self.P = problem.dag.cluster.num_pods

    def _mask(self, mask) -> jax.Array:
        """(P, P) link-availability factor; None means a healthy fabric.
        Always materialized (ones when healthy) so degraded calls hit the
        exact same traced signature -- no re-jit on the first failure."""
        if mask is None:
            return jnp.ones((self.P, self.P))
        return jnp.asarray(mask, dtype=jnp.float32)

    def makespan(self, x, ideal: bool = False, mask=None) -> float:
        with span("des.simulate", entry="single", n=self.pad.n):
            ms = self._compiled.single(
                self._leaves, jnp.asarray(x), jnp.asarray(ideal),
                self._mask(mask))[0]
            return float(ms)

    def simulate(self, x, ideal: bool = False, mask=None):
        with span("des.simulate", entry="single", n=self.pad.n):
            ms, feas, start, finish, _ = self._compiled.single(
                self._leaves, jnp.asarray(x), jnp.asarray(ideal),
                self._mask(mask))
            n = self.problem.n    # strip bucket-padding ghost tasks
            return (float(ms), bool(feas), np.asarray(start)[:n],
                    np.asarray(finish)[:n])

    def batch_makespan(self, xs, mask=None) -> tuple[np.ndarray, np.ndarray]:
        """Makespans + feasibility for a (pop, P, P) batch of topologies."""
        xs = jnp.asarray(xs)
        with span("des.simulate", entry="batch_x", n=self.pad.n,
                  pop=int(xs.shape[0])):
            ms, feas = self._compiled.batch_x(self._leaves, xs,
                                              self._mask(mask))
            return np.asarray(ms), np.asarray(feas)

    def batch_genome_makespan(self, genomes, edge_u, edge_v, mask=None
                              ) -> tuple[np.ndarray, np.ndarray]:
        """Fused GA generation-step fitness: scatter a (pop, E) genome batch
        onto (pop, P, P) topologies *on device* and simulate, all in one
        jitted call -- one host->device transfer for the genomes, one
        device->host for (makespan, feasible), independent of pop size."""
        genomes = jnp.asarray(genomes)
        with span("des.simulate", entry="batch_genomes", n=self.pad.n,
                  pop=int(genomes.shape[0])) as sp:
            ms, feas, trips = self._compiled.batch_genomes(
                self._leaves, genomes,
                jnp.asarray(edge_u, dtype=jnp.int32),
                jnp.asarray(edge_v, dtype=jnp.int32), self._mask(mask))
            return _host_results(sp, ms, feas, trips)


# ------------------------------------------------------------------ ensemble
def plane_state_genomes(lane_genomes: np.ndarray) -> np.ndarray:
    """Fabric-state expansion of a k-plane lane decomposition.

    `lane_genomes` is (..., k, E): per-plane circuit counts on the E
    union pairs, summing (over planes) to the total topology genome.
    Returns a float (..., k+1, E) stack -- state 0 is the full fabric
    (lane sum) and state p+1 is plane p dark (total minus lane p).  A
    pair carried entirely by the dark plane keeps a fractional
    ``total / k`` trickle instead of zeroing out: circuits are the only
    route between a pair, so a hard zero would price every single-lane
    pair as an infinite makespan (the same transient-buffering
    convention as `repro.core.ga.failure_scenarios`).  These are exactly
    the states a staggered rewire visits, so the GA's spare-lane fitness
    and the transition scheduler price the same physics.
    """
    lanes = np.asarray(lane_genomes, dtype=np.float64)
    if lanes.ndim < 2:
        raise ValueError(f"lane_genomes needs a (k, E) tail, "
                         f"got shape {lanes.shape}")
    k = lanes.shape[-2]
    total = lanes.sum(axis=-2, keepdims=True)           # (..., 1, E)
    eff = total - lanes                                 # (..., k, E)
    eff = np.where((eff <= 0) & (total > 0), total / k, eff)
    return np.concatenate([total, eff], axis=-2)        # (..., k+1, E)


def stack_problems(problems: list[DESProblem],
                   pad: PadSpec | None = None) -> DESArrays:
    """Pad member DES problems to one fixed shape and stack them.

    Every array field gains a leading member axis; the static shape fields
    take the across-member maxima (or the caller's larger `pad`, e.g. a
    compile bucket) so a single jitted `_simulate` serves all members
    (vmap over the member axis).  Ghost-padding semantics are documented on
    `_problem_fields`.
    """
    if not problems:
        raise ValueError("stack_problems needs at least one member")
    if pad is None:
        pad = member_pad(problems)
    B = problems[0].B
    if any(p.B != B for p in problems):
        raise ValueError("ensemble members must share the NIC bandwidth")
    member_fields = [_problem_fields(p, pad) for p in problems]
    stacked = {k: jnp.asarray(np.stack([f[k] for f in member_fields]))
               for k in _ARRAY_FIELDS}
    return DESArrays(**stacked, num_cons=pad.cons, num_link_cons=pad.links,
                     nic_bandwidth=1.0, n=pad.n)


def member_pad(problems: list[DESProblem]) -> PadSpec:
    """Across-member maxima of the exact per-member pad specs."""
    links = max(p.num_link_cons for p in problems)
    return PadSpec(
        n=max(p.n for p in problems),
        d=max(len(p.dep_pre) for p in problems),
        e=max(len(p.con_task) for p in problems),
        links=links,
        cons=links + max(p.num_cons - p.num_link_cons for p in problems))


class EnsembleJaxDES:
    """Batched DES over a `DagEnsemble`: members x genomes in one jit.

    Member problems are padded to a fixed shape (`stack_problems`) so GA
    fitness over a whole population stays O(1) host<->device transfers per
    generation regardless of ensemble size: one (pop, E) genome upload, one
    (pop, M) (makespan, feasible) download.
    """

    def __init__(self, problems: list[DESProblem],
                 max_events: int | None = None,
                 options: DESOptions | None = None):
        self.problems = problems
        self.options = options or DESOptions()
        self.P = problems[0].dag.cluster.num_pods
        with span("des.prepare") as sp:
            ropt = self.options.resolve()
            pad = member_pad(problems)
            if ropt.bucket:
                pad = pad.bucketed(ropt)
            self.pad = pad
            self.arrays = stack_problems(problems, pad)
            self.max_events = int(max_events or default_max_events(pad.n))
            cfg = _StaticCfg(n=pad.n, num_cons=pad.cons,
                             num_link_cons=pad.links, P=self.P,
                             max_events=self.max_events,
                             backend=ropt.backend,
                             interpret=ropt.interpret,
                             members=len(problems))
            self._compiled, hit = _compiled_for(cfg, pad, ropt.warn_on_miss)
            self._leaves = tuple(getattr(self.arrays, f)
                                 for f in _ARRAY_FIELDS)
            sp.set(n=pad.n, hit=hit)

    def _masks(self, masks) -> jax.Array:
        """(M, P, P) per-member availability factors (ones when healthy).
        The k-failure objective stacks one DAG M times and passes one
        failure scenario per member slot; the robust path leaves them at
        ones -- both share the compiled executable."""
        if masks is None:
            return jnp.ones((len(self.problems), self.P, self.P))
        masks = jnp.asarray(masks, dtype=jnp.float32)
        if masks.ndim == 2:
            masks = jnp.broadcast_to(masks, (len(self.problems), self.P,
                                             self.P))
        return masks

    def ensemble_genome_makespan(self, genomes, edge_u, edge_v, masks=None
                                 ) -> tuple[np.ndarray, np.ndarray]:
        """(pop, E) genomes over the union pairs -> (pop, M) makespans and
        feasibility, one fused jitted call (scatter + members x genomes
        vmap'd `_simulate`)."""
        genomes = jnp.asarray(genomes)
        with span("des.simulate", entry="ensemble_genomes", n=self.pad.n,
                  pop=int(genomes.shape[0]), members=len(self.problems)) as sp:
            ms, feas, trips = self._compiled.ensemble_genomes(
                self._leaves, genomes,
                jnp.asarray(edge_u, dtype=jnp.int32),
                jnp.asarray(edge_v, dtype=jnp.int32), self._masks(masks))
            return _host_results(sp, ms, feas, trips)

    def makespans(self, x, masks=None) -> tuple[np.ndarray, np.ndarray]:
        """Per-member (makespan, feasible) for one symmetric (P, P)
        topology, via the genome entry point (full-matrix scatter)."""
        eu = np.arange(self.P).repeat(self.P)
        ev = np.tile(np.arange(self.P), self.P)
        genome = np.asarray(x).reshape(-1)[None]
        ms, feas = self.ensemble_genome_makespan(genome, eu, ev, masks)
        return ms[0], feas[0]
