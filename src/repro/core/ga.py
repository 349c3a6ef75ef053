"""DELTA-Fast: DES-accelerated domain-adapted genetic algorithm
(paper Sec. IV-B, Algs. 3, 5, 6) -- population-array-resident engine.

Genome = integer circuit counts over the active undirected pod pairs,
bounded by the Alg. 2 capacity bounds X̄ and repaired against the physical
port budgets U.  Fitness = DES makespan (primary) and total allocated
circuits (secondary, lexicographic tie-break exploiting O4's port saving).

The whole search loop is array-at-a-time: the population is a single
(pop, E) int array, Alg. 5 init / Alg. 6 repair / tournament selection /
uniform crossover / ±1 mutation are whole-population numpy ops, and fitness
is one fused genome->topology scatter + vmap DES per generation
(`JaxDES.batch_genome_makespan`), padded to a fixed batch shape so XLA
compiles the generation step exactly once.  A vectorized `np.unique` dedup
backed by a bytes-keyed cache keeps duplicate genomes away from the
simulator entirely.

Fitness backends:
  'numpy' -- repro.core.des.simulate per unique candidate
  'jax'   -- repro.core.des_jax fused batched evaluation (TPU-native
             adaptation of ParallelEvalDES)
  'auto'  -- jax for small/medium DAGs, numpy beyond.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Sequence

import numpy as np

from repro.core.dag import CommDAG, DagEnsemble
from repro.core.des import DESProblem, simulate
from repro.core.xbound import x_upper_bound
from repro.obs import get_counter, span

_GENERATIONS = get_counter(
    "ga_generations_total", "GA generations executed")
_EVALUATIONS = get_counter(
    "ga_fitness_evaluations_total",
    "unique genomes scored by the DES (cache misses)")

if TYPE_CHECKING:   # pragma: no cover - annotation-only import
    from repro.core.des_jax import DESOptions

INF = float("inf")

# float32 relative slack for the batched-DES pre-filter in the trimming
# sweeps: accepts are always certified with the exact numpy DES, so the
# filter margin only guards against false *negatives*
_TRIM_FILTER_SLACK = 1e-3
# candidates the float32 filter rejected by more than this relative band
# are not exact-rechecked on termination: the engines agree to ~1e-5 on
# the equivalence suites, so a >5% f32 overshoot of an exactly-acceptable
# drop would need an f32 fair-share freeze flip with outsized schedule
# impact.  If one ever occurs, the cost is bounded -- the sweep retains
# ports it could have dropped; an accepted drop is always numpy-certified,
# so the makespan budget is never violated either way.
_TRIM_BACKSTOP_BAND = 5e-2


def _trim_filter_bands(ms: np.ndarray, feas: np.ndarray, budgets
                       ) -> tuple[np.ndarray, np.ndarray]:
    """(fits, near) f32 pre-filter bands shared by the trimming sweeps.

    `fits` passes the conservative accept filter; `near` is the ambiguous
    band exact-rechecked before termination.  f32-infeasible rows stay in
    the ambiguous band: the band bounds makespan divergence only, not a
    feasibility misjudgment (rare, and cheap to recheck exactly).
    Elementwise -- the ensemble sweep reduces across members afterwards.
    """
    fits = feas & (ms <= budgets * (1 + _TRIM_FILTER_SLACK) + 1e-12)
    near = ~feas | (ms <= budgets * (1 + _TRIM_BACKSTOP_BAND) + 1e-12)
    return fits, near


@dataclass
class GAOptions:
    pop_size: int = 48
    max_generations: int = 400
    patience: int = 60            # stop after N gens without improvement
    elite_frac: float = 0.15
    tournament: int = 3
    crossover_rate: float = 0.9
    mutation_rate: float = 0.25   # per-gene probability of a +/-1 step
    seed: int = 0
    backend: str = "auto"         # numpy | jax | auto
    jax_task_limit: int = 4096
    time_limit: float = 120.0
    port_weight: float = 1e-9     # lexicographic secondary objective
    # engine knobs for the jax DES (kernel backend, bucketed jit cache);
    # None inherits the env-driven defaults (see des_jax.DESOptions)
    des_options: "DESOptions | None" = None


@dataclass
class GAResult:
    x: np.ndarray
    makespan: float
    generations: int
    evaluations: int
    elapsed: float
    history: list[float] = field(default_factory=list)
    feasible: bool = True

    @property
    def total_ports(self) -> int:
        return int(self.x.sum())


class TopologySpace:
    """Genome <-> symmetric topology matrix mapping + Algs. 5/6.

    All hot-path operations take whole populations: genomes are rows of a
    (S, E) int array and every transform below is a single numpy expression
    over that array (incidence matvecs, fancy-indexed scatters).
    """

    def __init__(self, dag: CommDAG, xbar: np.ndarray | None = None):
        self.dag = dag
        xbar_m = np.asarray(xbar if xbar is not None else x_upper_bound(dag))
        self._setup(dag.cluster, dag.undirected_pairs(), xbar_m)

    @classmethod
    def for_ensemble(cls, ensemble: DagEnsemble,
                     xbar: np.ndarray | None = None, *,
                     port_limits: Sequence[int] | None = None,
                     min_circuits: int = 1) -> "TopologySpace":
        """Search space over the *union* of the members' active pairs.

        Per-pair capacity bound: the member-wise max of the Alg. 2 bounds
        (a circuit count useful to any member must stay reachable).

        `port_limits` overrides the cluster's per-pod budgets -- the
        k-plane decomposition searches sub-fabrics (a subset of each pod's
        ports) over the same pair space.  `min_circuits=0` admits empty
        pairs, which a *supplementary* plane needs (its lane only tops up
        pairs the base planes already connect)."""
        obj = cls.__new__(cls)
        obj.dag = None
        xbar_m = np.asarray(xbar if xbar is not None
                            else ensemble_x_upper_bound(ensemble))
        obj._setup(ensemble.cluster, ensemble.undirected_pairs(), xbar_m,
                   port_limits=port_limits, min_circuits=min_circuits)
        return obj

    def _setup(self, cluster, edges: list[tuple[int, int]],
               xbar_m: np.ndarray, *,
               port_limits: Sequence[int] | None = None,
               min_circuits: int = 1) -> None:
        self.P = cluster.num_pods
        self.U = np.asarray(port_limits if port_limits is not None
                            else cluster.port_limits, dtype=np.int64)
        if self.U.shape != (self.P,):
            raise ValueError(f"port_limits needs {self.P} entries, "
                             f"got shape {self.U.shape}")
        if min_circuits not in (0, 1):
            raise ValueError(f"min_circuits must be 0 or 1, "
                             f"got {min_circuits}")
        self.g_min = int(min_circuits)
        self.edges = edges
        self.E = len(self.edges)
        earr = np.asarray(self.edges, dtype=np.int64).reshape(-1, 2)
        self.edge_u = earr[:, 0]
        self.edge_v = earr[:, 1]
        self.xbar = np.maximum(
            self.g_min,
            np.minimum(xbar_m[self.edge_u, self.edge_v].astype(np.int64),
                       np.minimum(self.U[self.edge_u],
                                  self.U[self.edge_v])))
        # pod x edge incidence (each edge touches exactly two pods)
        self.inc = np.zeros((self.P, self.E), dtype=np.int64)
        self.inc[self.edge_u, np.arange(self.E)] = 1
        self.inc[self.edge_v, np.arange(self.E)] = 1
        self.degree = self.inc.sum(axis=1)
        # quick feasibility: connectivity needs one port per incident edge
        # (moot when empty pairs are admitted)
        if self.g_min > 0 and (self.degree > self.U).any():
            p = int(np.argmax(self.degree - self.U))
            raise ValueError(
                f"pod {p} has {int(self.degree[p])} active pairs but "
                f"only {self.U[p]} ports; placement is infeasible")

    # ------------------------------------------------------ genome <-> matrix
    def genome_of(self, x: np.ndarray) -> np.ndarray:
        """Project a (P, P) topology matrix onto the active-pair genome."""
        return np.asarray(x)[self.edge_u, self.edge_v].astype(np.int64)

    def to_matrix_batch(self, genomes: np.ndarray) -> np.ndarray:
        """(S, E) genomes -> (S, P, P) symmetric topologies in one scatter:
        integer circuit counts, or float for the fractional fabric states
        of a plane stack (`plane_state_genomes`)."""
        G = np.atleast_2d(genomes)
        X = np.zeros((len(G), self.P, self.P),
                     dtype=np.result_type(G, np.int64))
        X[:, self.edge_u, self.edge_v] = G
        X[:, self.edge_v, self.edge_u] = G
        return X

    def to_matrix(self, genome: np.ndarray) -> np.ndarray:
        return self.to_matrix_batch(np.asarray(genome)[None])[0]

    # ------------------------------------------------------------ feasibility
    def port_usage_batch(self, genomes: np.ndarray) -> np.ndarray:
        """(S, E) genomes -> (S, P) ports used per pod (incidence matvec)."""
        return np.asarray(genomes, dtype=np.int64).reshape(-1, self.E) \
            @ self.inc.T

    def port_usage(self, genome: np.ndarray) -> np.ndarray:
        return self.port_usage_batch(np.asarray(genome)[None])[0]

    def is_feasible_batch(self, genomes: np.ndarray) -> np.ndarray:
        G = np.asarray(genomes, dtype=np.int64).reshape(-1, self.E)
        return ((G >= self.g_min).all(axis=1) & (G <= self.xbar).all(axis=1)
                & (self.port_usage_batch(G) <= self.U).all(axis=1))

    def is_feasible(self, genome: np.ndarray) -> bool:
        return bool(self.is_feasible_batch(np.asarray(genome)[None])[0])

    # ---------------------------------------------------------------- Alg. 5
    def random_init_batch(self, rng: np.random.Generator,
                          size: int) -> np.ndarray:
        """Feasible random population: uniform in [1, X̄] then batched
        Alg. 6 repair.  Repair always succeeds here: the constructor
        guarantees degree <= U, and any over-budget pod necessarily has an
        incident edge with g > 1 to reduce."""
        if self.E == 0:
            return np.zeros((size, 0), dtype=np.int64)
        G = rng.integers(self.g_min, self.xbar + 1, size=(size, self.E),
                         dtype=np.int64)
        return self.repair_batch(G, rng)[0]

    def feasible_random_init(self, rng: np.random.Generator) -> np.ndarray:
        return self.random_init_batch(rng, 1)[0]

    # ---------------------------------------------------------------- Alg. 6
    def repair_batch(self, genomes: np.ndarray, rng: np.random.Generator
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Whole-population repair: clip to [1, X̄], then per round every
        over-budget pod of every genome drops one circuit from a random
        reducible incident edge (all genomes and pods act simultaneously;
        total over-usage strictly decreases each round, so the loop is
        bounded by the initial excess).  Returns (repaired, ok) where ok[s]
        marks genomes whose port budgets are satisfied."""
        G = np.clip(np.asarray(genomes, dtype=np.int64).reshape(-1, self.E),
                    self.g_min, self.xbar)
        S = len(G)
        if self.E == 0 or S == 0:
            return G, np.ones(S, dtype=bool)
        inc_b = self.inc.astype(bool)
        rounds = int(self.xbar.sum()) - self.E + 1
        for _ in range(max(rounds, 1)):
            over = self.port_usage_batch(G) > self.U        # (S, P)
            viol = np.nonzero(over.any(axis=1))[0]
            if len(viol) == 0:
                break
            Gv, overv = G[viol], over[viol]
            keys = rng.random((len(viol), self.E))
            cand = overv[:, :, None] & inc_b[None] \
                & (Gv > self.g_min)[:, None, :]
            masked = np.where(cand, keys[:, None, :], -1.0)  # (V, P, E)
            e_star = masked.argmax(axis=2)                   # (V, P)
            valid = masked.max(axis=2) >= 0.0                # (V, P)
            if not valid.any():
                break
            dec = np.zeros_like(Gv)
            s_idx, p_idx = np.nonzero(valid)
            np.add.at(dec, (s_idx, e_star[s_idx, p_idx]), 1)
            G[viol] = np.maximum(Gv - dec, self.g_min)
        return G, (self.port_usage_batch(G) <= self.U).all(axis=1)

    def repair(self, genome: np.ndarray, rng: np.random.Generator
               ) -> tuple[np.ndarray, bool]:
        G, ok = self.repair_batch(np.asarray(genome)[None], rng)
        return G[0], bool(ok[0])


class _CachedFitness:
    """Shared population-fitness plumbing for the single-DAG and ensemble
    engines: vectorized `np.unique` dedup backed by a bytes-keyed score
    cache, fixed-shape padding (a multiple of `pop_size`, so the jitted
    batch compiles once and every generation does O(1) host<->device
    transfers), and the lexicographic port penalty.  Subclasses provide
    `_raw_scores` mapping unique (S, E) genomes to makespan-like scores
    (lower is better, INF marks infeasible), and the exact (numpy DES)
    evaluation `exact` of one genome with its scalar `exact_score`, which
    `_rerank` certifies the winner with."""

    def __init__(self, space: TopologySpace, opts: GAOptions, n_tasks: int):
        self.space = space
        self.opts = opts
        self.cache: dict[bytes, float] = {}
        self.evaluations = 0
        self.batch_calls = 0
        self._use_jax = opts.backend == "jax" or (
            opts.backend == "auto" and n_tasks <= opts.jax_task_limit)
        self._pad = max(int(opts.pop_size), 1)

    def _padded(self, genomes: np.ndarray) -> tuple[np.ndarray, int]:
        """Pad to the fixed batch shape; extra lanes are near-free on the
        batched while_loop, whose cost is the max-lane trip count."""
        k = len(genomes)
        pad = (-k) % self._pad
        if pad:
            genomes = np.concatenate(
                [genomes, np.repeat(genomes[:1], pad, axis=0)])
        return genomes, k

    def _raw_scores(self, genomes: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def exact(self, genome: np.ndarray):
        raise NotImplementedError

    def exact_score(self, raw) -> float:
        raise NotImplementedError

    def __call__(self, population: np.ndarray) -> np.ndarray:
        G = np.ascontiguousarray(
            np.asarray(population, dtype=np.int64).reshape(-1, self.space.E))
        uniq, inv = np.unique(G, axis=0, return_inverse=True)
        inv = np.asarray(inv).reshape(-1)   # numpy 2.x inverse-shape drift
        keys = [row.tobytes() for row in uniq]
        miss = [i for i, key in enumerate(keys) if key not in self.cache]
        if miss:
            self.evaluations += len(miss)
            _EVALUATIONS.inc(len(miss))
            with span("ga.fitness_batch", pop=len(G), unique=len(uniq),
                      misses=len(miss)):
                vals = self._raw_scores(uniq[miss])
            sums = uniq[miss].sum(axis=1)
            for i, v, s in zip(miss, vals, sums):
                score = float(v)
                if np.isfinite(score):
                    score += self.opts.port_weight * float(s)
                self.cache[keys[i]] = score
        return np.array([self.cache[k] for k in keys])[inv]


class BatchedFitness(_CachedFitness):
    """Single-DAG fitness: one fused genome-scatter + vmap-DES call per
    generation on the jax backend (`JaxDES.batch_genome_makespan`)."""

    def __init__(self, dag: CommDAG, space: TopologySpace, opts: GAOptions):
        self.problem = DESProblem(dag)
        super().__init__(space, opts, self.problem.n)
        self._jd = None
        if self._use_jax and space.E > 0:
            from repro.core.des_jax import JaxDES
            self._jd = JaxDES(self.problem, options=opts.des_options)

    def _raw_scores(self, genomes: np.ndarray) -> np.ndarray:
        """Makespan (INF if infeasible) for each unique genome row."""
        if self._jd is not None:
            genomes, k = self._padded(genomes)
            ms, feas = self._jd.batch_genome_makespan(
                genomes, self.space.edge_u, self.space.edge_v)
            self.batch_calls += 1
            return np.where(feas, ms, INF)[:k]
        return np.array([self.exact(g) for g in genomes])

    def exact(self, genome: np.ndarray) -> float:
        """Exact (numpy DES) makespan of one genome."""
        return simulate(self.problem, self.space.to_matrix(genome)).makespan

    def exact_score(self, raw: float) -> float:
        return float(raw)


def _tournament_batch(fitness: np.ndarray, rng: np.random.Generator,
                      num: int, k: int) -> np.ndarray:
    """`num` independent k-way tournaments over the population, at once."""
    idx = rng.integers(0, len(fitness), size=(num, k))
    return idx[np.arange(num), np.argmin(fitness[idx], axis=1)]


def _variation_batch(pop: np.ndarray, fitness: np.ndarray,
                     space: TopologySpace, opts: GAOptions,
                     rng: np.random.Generator, num: int) -> np.ndarray:
    """Selection + uniform crossover + ±1 mutation for `num` children,
    as whole-population array ops (no per-genome loops)."""
    pa = _tournament_batch(fitness, rng, num, opts.tournament)
    pb = _tournament_batch(fitness, rng, num, opts.tournament)
    A, B = pop[pa], pop[pb]
    cross = rng.random(num) < opts.crossover_rate
    take_b = rng.random((num, space.E)) < 0.5
    children = np.where(cross[:, None] & take_b, B, A)
    mut = rng.random((num, space.E)) < opts.mutation_rate
    step = rng.integers(0, 2, size=(num, space.E)) * 2 - 1
    return np.clip(children + np.where(mut, step, 0), space.g_min,
                   space.xbar)


def _evolve(space: TopologySpace, fit, opts: GAOptions,
            rng: np.random.Generator, t0: float,
            seeds: list[np.ndarray] | None = None
            ) -> tuple[np.ndarray, float, list[float], int]:
    """The shared GA driver (Alg. 3 body): init + repair + generational
    loop, fitness-agnostic.  `fit` maps a (S, E) population to (S,) scores
    (lower is better); every DELTA engine routes through this exact loop,
    so a singleton ensemble consumes the RNG identically to the single-DAG
    path.  Returns (best_g, best_f, history, gen)."""
    pop = space.random_init_batch(rng, opts.pop_size)
    # seed candidates (e.g. baselines) -- repaired into the population
    for s in (seeds or []):
        g, ok = space.repair(space.genome_of(s), rng)
        if ok:
            pop[rng.integers(len(pop))] = g
    fitness = fit(pop)
    best_i = int(np.argmin(fitness))
    best_g, best_f = pop[best_i].copy(), float(fitness[best_i])
    history = [best_f]
    n_elite = max(1, int(opts.elite_frac * opts.pop_size))
    num_children = opts.pop_size - n_elite
    stall = 0
    gen = 0

    for gen in range(1, opts.max_generations + 1):
        if time.time() - t0 > opts.time_limit or stall >= opts.patience:
            break
        with span("ga.generation", gen=gen, pop=opts.pop_size):
            order = np.argsort(fitness, kind="stable")
            elite = pop[order[:n_elite]]
            children = _variation_batch(pop, fitness, space, opts, rng,
                                        num_children)
            children, _ = space.repair_batch(children, rng)
            pop = np.concatenate([elite, children], axis=0)
            fitness = fit(pop)
        _GENERATIONS.inc()
        i = int(np.argmin(fitness))
        if fitness[i] < best_f - 1e-15:
            best_f, best_g = float(fitness[i]), pop[i].copy()
            stall = 0
        else:
            stall += 1
        history.append(best_f)
    return best_g, best_f, history, gen


def _rerank(fit: _CachedFitness, best_g: np.ndarray, top: int):
    """Exact re-rank of the `top` best distinct genomes in the fitness
    cache (the batched jax fitness may run in float32; ~1e-5 ranking
    noise): each finite one is scored with `fit.exact` plus the port
    penalty, and the winner -- `best_g` if none is finite -- is evaluated
    once more.  Returns (winner genome, its `fit.exact` evaluation)."""
    ranked = sorted(fit.cache.items(), key=lambda kv: kv[1])[:top]
    best_key, best_score = best_g.tobytes(), INF
    with span("ga.rerank") as sp:
        candidates = 0
        for key, fval in ranked:
            if not np.isfinite(fval):
                continue
            candidates += 1
            g = np.frombuffer(key, dtype=np.int64)
            score = fit.exact_score(fit.exact(g))
            if np.isfinite(score):
                score += fit.opts.port_weight * float(g.sum())
            if score < best_score:
                best_score, best_key = score, key
        sp.set(candidates=candidates)
        best_g = np.frombuffer(best_key, dtype=np.int64)
        return best_g, fit.exact(best_g)


def _search(fit: _CachedFitness, rng: np.random.Generator,
            seeds: list[np.ndarray] | None, kind: str, top: int = 8,
            **attrs):
    """Alg. 3 end to end, as every DELTA engine runs it: `_evolve` under
    the fitness' own wall-clock budget, then `_rerank`.  An empty pair
    space (no inter-pod traffic) skips the search: the empty topology is
    the one evaluation.  Returns (genome, its `fit.exact` evaluation,
    generations, history)."""
    space, opts = fit.space, fit.opts
    t0 = time.time()
    if space.E == 0:
        g = np.zeros(0, dtype=np.int64)
        raw = fit.exact(g)
        fit.evaluations += 1
        return g, raw, 0, [fit.exact_score(raw)]
    with span("ga.evolve", kind=kind, pop=opts.pop_size, edges=space.E,
              **attrs):
        best_g, _, history, gen = _evolve(space, fit, opts, rng, t0, seeds)
    g, raw = _rerank(fit, best_g, top)
    return g, raw, gen, history


def delta_fast(dag: CommDAG, opts: GAOptions | None = None,
               xbar: np.ndarray | None = None,
               seeds: list[np.ndarray] | None = None) -> GAResult:
    """Alg. 3: SimBasedDomainAdaptedGA (population-array-resident)."""
    opts = opts or GAOptions()
    rng = np.random.default_rng(opts.seed)
    space = TopologySpace(dag, xbar)
    fit = BatchedFitness(dag, space, opts)
    t0 = time.time()
    g, ms, gen, history = _search(fit, rng, seeds, "delta_fast")
    return GAResult(x=space.to_matrix(g), makespan=float(ms),
                    generations=gen, evaluations=fit.evaluations,
                    elapsed=time.time() - t0, history=history,
                    feasible=np.isfinite(ms))


# ------------------------------------------------------------- DELTA-Robust
ROBUST_OBJECTIVES = ("weighted", "max-regret")


def ensemble_x_upper_bound(ensemble: DagEnsemble) -> np.ndarray:
    """Union-space Alg. 2 bound: elementwise max of the member bounds."""
    return np.maximum.reduce([x_upper_bound(m) for m in ensemble.members])


class EnsembleFitness(_CachedFitness):
    """Population fitness over a `DagEnsemble`.

    Same plumbing as `BatchedFitness` (shared `_CachedFitness` base), but
    every unique genome is scored against *all* ensemble members in one
    fused `EnsembleJaxDES.ensemble_genome_makespan` call (members x
    genomes vmap), then scalarized:

      weighted   : sum_m w_m * makespan_m
      max-regret : max_m  makespan_m / refs_m

    Any member-infeasible genome scores INF.  `masks`, one (P, P)
    capacity factor per member, degrades each member's fabric (the
    k-failure objective stacks one DAG once per failure scenario).
    """

    def __init__(self, ensemble: DagEnsemble, space: TopologySpace,
                 opts: GAOptions, objective: str, refs: np.ndarray,
                 masks: Sequence[np.ndarray] | None = None):
        self.ensemble = ensemble
        self.problems = [DESProblem(m) for m in ensemble.members]
        super().__init__(space, opts, max(p.n for p in self.problems))
        self.objective = objective
        self.refs = np.asarray(refs, dtype=np.float64)
        self.weights = np.asarray(ensemble.weights, dtype=np.float64)
        self.masks = None if masks is None else np.stack(
            [np.asarray(m, dtype=np.float64) for m in masks])
        self._jd = None
        if self._use_jax and space.E > 0:
            from repro.core.des_jax import EnsembleJaxDES
            self._jd = EnsembleJaxDES(self.problems,
                                      options=opts.des_options)

    def scalarize(self, ms: np.ndarray) -> np.ndarray:
        """(S, M) member makespans -> (S,) objective values (INF-safe)."""
        ms = np.asarray(ms, dtype=np.float64).reshape(-1, len(self.problems))
        with np.errstate(invalid="ignore"):
            if self.objective == "weighted":
                out = ms @ self.weights
            else:
                out = (ms / self.refs).max(axis=1)
        out[~np.isfinite(ms).all(axis=1)] = INF
        return out

    def member_makespans(self, genomes: np.ndarray) -> np.ndarray:
        """(S, E) genomes -> (S, M) makespans (INF where infeasible)."""
        genomes = np.asarray(genomes).reshape(-1, self.space.E)
        if self._jd is not None:
            genomes, k = self._padded(genomes)
            ms, feas = self._jd.ensemble_genome_makespan(
                genomes, self.space.edge_u, self.space.edge_v,
                masks=self.masks)
            self.batch_calls += 1
            return np.where(feas, ms, INF)[:k]
        return np.array([self.exact_member_makespans(g) for g in genomes])

    def exact_member_makespans(self, genome: np.ndarray) -> np.ndarray:
        """Exact (numpy DES) per-member makespans of one genome."""
        x = self.space.to_matrix(genome)
        masks = [1] * len(self.problems) if self.masks is None \
            else self.masks
        return np.array([simulate(p, x * m).makespan
                         for p, m in zip(self.problems, masks)])

    def exact(self, genome: np.ndarray) -> np.ndarray:
        return self.exact_member_makespans(genome)

    def exact_score(self, raw: np.ndarray) -> float:
        return float(self.scalarize(raw).max())

    def _raw_scores(self, genomes: np.ndarray) -> np.ndarray:
        return self.scalarize(self.member_makespans(genomes))


@dataclass
class RobustGAResult:
    """One static topology scored against every ensemble member."""

    x: np.ndarray
    makespans: np.ndarray          # (M,) exact per-member DES makespans
    regrets: np.ndarray            # (M,) makespans / refs
    refs: np.ndarray               # (M,) reference (best single-DAG) spans
    weights: np.ndarray            # (M,) normalized mixture weights
    objective: str
    objective_value: float
    generations: int
    evaluations: int
    elapsed: float
    history: list[float] = field(default_factory=list)
    feasible: bool = True

    @property
    def worst_regret(self) -> float:
        return float(self.regrets.max()) if len(self.regrets) else INF

    @property
    def weighted_makespan(self) -> float:
        return float(self.makespans @ self.weights)

    @property
    def total_ports(self) -> int:
        return int(self.x.sum())


def _ensemble_search(fit: EnsembleFitness, seeds: list[np.ndarray] | None,
                     objective: str, kind: str,
                     t_start: float) -> RobustGAResult:
    """`_search` over an ensemble fitness, as `RobustGAResult`."""
    rng = np.random.default_rng(fit.opts.seed)
    g, ms, gen, history = _search(fit, rng, seeds, kind,
                                  members=len(fit.problems))
    return RobustGAResult(
        x=fit.space.to_matrix(g), makespans=ms, regrets=ms / fit.refs,
        refs=fit.refs, weights=np.asarray(fit.ensemble.weights),
        objective=objective, objective_value=fit.exact_score(ms),
        generations=gen, evaluations=fit.evaluations,
        elapsed=time.time() - t_start, history=history,
        feasible=bool(np.isfinite(ms).all()))


def delta_robust(ensemble: DagEnsemble, opts: GAOptions | None = None,
                 objective: str = "max-regret",
                 refs: np.ndarray | None = None,
                 xbar: np.ndarray | None = None,
                 seeds: list[np.ndarray] | None = None,
                 port_limits: Sequence[int] | None = None) -> RobustGAResult:
    """DELTA-Robust: one static topology for a *set* of DAGs.

    Runs the same domain-adapted GA as `delta_fast` (identical RNG stream
    and loop -- a singleton ensemble reduces exactly to the single-DAG
    path) over the union pair space, with per-genome fitness scored
    against every member in one fused vmap DES call.

    `refs` are the per-member reference makespans defining regret
    (member's best single-DAG plan).  When omitted they are computed here
    by running `delta_fast` per member with the same options; the robust
    GA still gets its own full time budget.

    `port_limits` overrides the cluster's per-pod budgets: the k-plane
    decomposition (`delta_planes`) searches the base topology inside the
    first k-1 planes' combined budget.
    """
    opts = opts or GAOptions()
    if objective not in ROBUST_OBJECTIVES:
        raise ValueError(f"unknown objective {objective!r}; "
                         f"pick from {ROBUST_OBJECTIVES}")
    t_start = time.time()
    if refs is None:
        refs = np.array([delta_fast(m, opts).makespan
                         for m in ensemble.members])
    refs = np.asarray(refs, dtype=np.float64)
    if refs.shape != (ensemble.num_members,):
        raise ValueError("refs must have one entry per ensemble member")
    if not (np.isfinite(refs) & (refs > 0)).all():
        raise ValueError(f"refs must be finite positive makespans: {refs}")
    space = TopologySpace.for_ensemble(ensemble, xbar,
                                       port_limits=port_limits)
    fit = EnsembleFitness(ensemble, space, opts, objective, refs)
    return _ensemble_search(fit, seeds, objective, "delta_robust", t_start)


# ----------------------------------------------------------- DELTA-Failsafe
FAILSAFE_OBJECTIVES = ("worst", "weighted")


def failure_scenarios(dag: CommDAG, num_planes: int = 4, k: int = 1,
                      include_healthy: bool = True) -> list[np.ndarray]:
    """Fractional k-plane-loss masks for the k-failure worst-case plan.

    One scenario per active pod pair: k of the `num_planes` OCS planes
    serving that pair go dark, leaving (num_planes - k)/num_planes of its
    circuit capacity.  The haircut is *fractional* on purpose -- circuits
    are the only route between a pair, so a full kill would make the worst
    case inf for every topology.  The healthy fabric is scenario 0, keeping
    the worst-case plan honest on the intact fabric too.
    """
    P = dag.cluster.num_pods
    frac = max(num_planes - k, 0) / num_planes
    out = [np.ones((P, P))] if include_healthy else []
    for (i, j) in dag.undirected_pairs():
        m = np.ones((P, P))
        m[i, j] = m[j, i] = frac
        out.append(m)
    return out


def delta_failsafe(dag: CommDAG, opts: GAOptions | None = None,
                   scenarios: list[np.ndarray] | None = None,
                   num_planes: int = 4, k: int = 1,
                   objective: str = "worst",
                   xbar: np.ndarray | None = None,
                   seeds: list[np.ndarray] | None = None) -> RobustGAResult:
    """k-failure worst-case plan: one topology whose DES makespan is
    minimized across a set of fabric-degradation scenarios (capacity
    masks), scored in one fused masks x genomes vmap call per generation.

    `scenarios` is a list of (P, P) availability masks (1 = healthy);
    omitted, it defaults to `failure_scenarios(dag, num_planes, k)`.
    `objective` is 'worst' (minimize the max scenario makespan) or
    'weighted' (uniform mean).  The repair policy also calls this with a
    single scenario -- the *current* fabric damage -- to produce a full
    replan optimized for the degraded fabric.  Each scenario is one
    member of an `EnsembleFitness` over the same DAG, masked.
    """
    opts = opts or GAOptions()
    if objective not in FAILSAFE_OBJECTIVES:
        raise ValueError(f"unknown objective {objective!r}; "
                         f"pick from {FAILSAFE_OBJECTIVES}")
    if scenarios is None:
        scenarios = failure_scenarios(dag, num_planes=num_planes, k=k)
    scenarios = [np.asarray(m, dtype=np.float64) for m in scenarios]
    if not scenarios:
        raise ValueError("delta_failsafe needs at least one scenario")
    t_start = time.time()
    space = TopologySpace(dag, xbar)
    refs = np.ones(len(scenarios))   # worst == max-regret w.r.t. unit refs
    eff = "max-regret" if objective == "worst" else "weighted"
    fit = EnsembleFitness(DagEnsemble([dag] * len(scenarios)), space, opts,
                          eff, refs, masks=scenarios)
    return _ensemble_search(fit, seeds, objective, "delta_failsafe",
                            t_start)


# -------------------------------------------------------------- DELTA-Planes
def split_across_planes(x: np.ndarray, plane_budgets) -> np.ndarray:
    """Split one topology across OCS planes, balanced per pair.

    `x` is a (P, P) symmetric circuit matrix; `plane_budgets` is (k', P)
    per-plane per-pod port budgets.  Circuits are assigned one at a time,
    heaviest pair first; each circuit goes to the plane with the smallest
    share of that pair so far (then the most endpoint headroom, then the
    lowest plane id), so every pair's per-plane share is within one of
    c/k' wherever budgets permit -- losing any single plane then costs a
    pair at most ceil(c/k') of its c circuits.  Deterministic: the fleet
    rebuilds plane books from journal replays and must land on identical
    arrays.

    When the balanced choice has no port headroom the circuit falls to
    any plane that fits; if none fits, one single-circuit swap between
    planes is attempted before giving up (per-plane budgets are near-
    uniform, so a feasible global topology virtually always splits).
    """
    x = np.asarray(x)
    budgets = np.asarray(plane_budgets, dtype=np.int64)
    if budgets.ndim != 2 or budgets.shape[1] != x.shape[0]:
        raise ValueError(f"plane_budgets shape {budgets.shape} does not "
                         f"match {x.shape[0]} pods")
    k, P = budgets.shape
    planes = np.zeros((k, P, P), dtype=np.int64)
    head = budgets.copy()

    def place(u: int, v: int) -> bool:
        fits = np.nonzero((head[:, u] > 0) & (head[:, v] > 0))[0]
        if len(fits) == 0:
            return False
        share = planes[fits, u, v]
        room = np.minimum(head[fits, u], head[fits, v])
        p = fits[np.lexsort((fits, -room, share))[0]]
        planes[p, u, v] += 1
        planes[p, v, u] += 1
        head[p, u] -= 1
        head[p, v] -= 1
        return True

    def swap_then_place(u: int, v: int) -> bool:
        # free a slot: move one circuit (a, b) out of a plane p that has
        # headroom at one endpoint, into a plane q that fits it, so (u, v)
        # can land in p
        for u0, v0 in ((u, v), (v, u)):
            for p in np.nonzero(head[:, u0] > 0)[0]:
                for b in np.nonzero(planes[p, v0] > 0)[0]:
                    for q in np.nonzero((head[:, v0] > 0)
                                        & (head[:, b] > 0))[0]:
                        if q == p:
                            continue
                        planes[p, v0, b] -= 1
                        planes[p, b, v0] -= 1
                        planes[q, v0, b] += 1
                        planes[q, b, v0] += 1
                        head[p, v0] += 1
                        head[p, b] += 1
                        head[q, v0] -= 1
                        head[q, b] -= 1
                        if place(u, v):
                            return True
        return False

    iu, iv = np.triu_indices(P, k=1)
    counts = np.asarray(x)[iu, iv].astype(np.int64)
    for idx in np.lexsort((iv, iu, -counts)):
        u, v, c = int(iu[idx]), int(iv[idx]), int(counts[idx])
        for _ in range(c):
            if not place(u, v) and not swap_then_place(u, v):
                raise ValueError(
                    f"cannot split pair ({u}, {v}) of {x[u, v]} circuits "
                    f"across plane budgets {budgets.tolist()}")
    return planes


class PlanesFitness(EnsembleFitness):
    """Spare-plane fitness for the k-plane decomposition.

    The genome is the SPARE plane's lane only; the first k-1 lanes are
    frozen to the balanced split of the stage-A weighted optimum.  Every
    candidate is expanded into k+1 fabric states -- the full fabric plus
    each single plane dark (`plane_state_genomes`, the staggered-rewire /
    PlaneFailure states the scheduler actually visits) -- and the float
    (S*(k+1), E) state stack is scored against all M ensemble members by
    the ensemble fitness, in ONE fused `ensemble_genome_makespan` call.
    Objective: worst state/member regret against the stage-A reference
    makespans, so the spare lane is shaped to absorb whichever plane loss
    hurts the worst member most.
    """

    def __init__(self, ensemble: DagEnsemble, base_lanes: np.ndarray,
                 space: TopologySpace, opts: GAOptions, refs: np.ndarray):
        super().__init__(ensemble, space, opts, "max-regret", refs)
        self.base_lanes = np.asarray(base_lanes, dtype=np.int64) \
            .reshape(-1, space.E)
        self.num_planes = len(self.base_lanes) + 1

    def states(self, genomes: np.ndarray) -> np.ndarray:
        """(S, E) spare lanes -> (S*(k+1), E) float fabric states."""
        from repro.core.des_jax import plane_state_genomes
        G = np.asarray(genomes, dtype=np.int64).reshape(-1, self.space.E)
        base = np.broadcast_to(self.base_lanes[None],
                               (len(G),) + self.base_lanes.shape)
        lanes = np.concatenate([base, G[:, None, :]], axis=1)
        return plane_state_genomes(lanes).reshape(-1, self.space.E)

    def exact(self, genome: np.ndarray) -> np.ndarray:
        """Exact (numpy DES) (k+1, M) state/member makespans of one
        spare lane."""
        return np.array([self.exact_member_makespans(s)
                         for s in self.states(genome)])

    def _raw_scores(self, genomes: np.ndarray) -> np.ndarray:
        regrets = self.scalarize(self.member_makespans(self.states(genomes)))
        return regrets.reshape(len(genomes), -1).max(axis=1)


@dataclass
class PlanesGAResult:
    """k-plane decomposition of one robust topology."""

    planes: np.ndarray             # (k, P, P) per-plane circuit counts
    lane_genomes: np.ndarray       # (k, E) the same, on the union pairs
    edges: list                    # the E union pairs
    x: np.ndarray                  # (P, P) total topology (planes.sum(0))
    makespans: np.ndarray          # (M,) exact full-fabric member makespans
    dark_makespans: np.ndarray     # (k, M) exact one-plane-dark makespans
    refs: np.ndarray               # (M,) stage-A reference makespans
    plane_port_limits: tuple       # (k, P) per-plane per-pod budgets
    objective_value: float         # worst state/member regret
    generations: int
    evaluations: int
    elapsed: float
    history: list = field(default_factory=list)
    feasible: bool = True

    @property
    def num_planes(self) -> int:
        return len(self.planes)

    @property
    def worst_dark_regret(self) -> float:
        if not len(self.dark_makespans):
            return INF
        return float((self.dark_makespans / self.refs).max())

    @property
    def total_ports(self) -> int:
        return int(self.x.sum())


def delta_planes(ensemble: DagEnsemble, opts: GAOptions | None = None,
                 num_planes: int = 4,
                 xbar: np.ndarray | None = None,
                 seeds: list[np.ndarray] | None = None) -> PlanesGAResult:
    """DELTA-Planes: decompose one robust topology across a k-plane OCS
    fabric so any single plane can go dark (fault OR staggered rewire)
    with bounded, pre-certified inflation.

    Two structured stages over the plane-indexed genome:

      1. base -- `delta_robust` (weighted objective) confined to the
         first k-1 planes' combined port budget, then split balanced
         across those planes (`split_across_planes`): the always-on
         carry capacity.
      2. spare -- a GA over the k-th plane's lane alone
         (`TopologySpace.for_ensemble(..., port_limits=spare,
         min_circuits=0)`), scored on the k+1 fabric states every
         staggered transition actually visits; the spare lane is shaped
         to absorb the worst-case member under the worst plane loss.

    Exact numpy re-rank certifies the winner's full state/member matrix
    before it is returned (same f32-noise guard as the other engines).
    """
    opts = opts or GAOptions()
    if num_planes < 2:
        raise ValueError(f"num_planes must be >= 2, got {num_planes}")
    t_start = time.time()
    budgets = np.asarray(ensemble.plane_port_limits(num_planes),
                         dtype=np.int64)
    base_budget = budgets[:-1].sum(axis=0)

    base = delta_robust(ensemble, opts, objective="weighted",
                        refs=np.ones(ensemble.num_members),
                        port_limits=base_budget)
    refs = np.asarray(base.makespans, dtype=np.float64)
    if not (np.isfinite(refs) & (refs > 0)).all():
        raise ValueError(
            f"base stage is infeasible under the first {num_planes - 1} "
            f"planes' budget {base_budget.tolist()}: makespans {refs}")
    base_planes = split_across_planes(base.x, budgets[:-1])

    space = TopologySpace.for_ensemble(ensemble, xbar,
                                       port_limits=budgets[-1],
                                       min_circuits=0)
    # the spare lane tops up what the base left under the union Alg. 2
    # bound (at least one extra circuit per pair stays searchable)
    extra = ensemble_x_upper_bound(ensemble)[
        space.edge_u, space.edge_v].astype(np.int64) \
        - base.x[space.edge_u, space.edge_v].astype(np.int64)
    space.xbar = np.minimum(space.xbar, np.maximum(extra, 1))
    base_lanes = base_planes[:, space.edge_u, space.edge_v]
    fit = PlanesFitness(ensemble, base_lanes, space, opts, refs)
    rng = np.random.default_rng(opts.seed + 1)   # distinct from stage 1
    spare_g, exact, gen, history = _search(
        fit, rng, seeds, "delta_planes", top=4,
        members=ensemble.num_members, planes=num_planes)
    planes = np.concatenate([base_planes, space.to_matrix(spare_g)[None]],
                            axis=0)
    lanes = np.concatenate([base_lanes, spare_g[None].astype(np.int64)],
                           axis=0)
    return PlanesGAResult(
        planes=planes, lane_genomes=lanes, edges=list(space.edges),
        x=planes.sum(axis=0), makespans=exact[0], dark_makespans=exact[1:],
        refs=refs, plane_port_limits=tuple(map(tuple, budgets.tolist())),
        objective_value=fit.exact_score(exact), generations=gen,
        evaluations=fit.evaluations, elapsed=time.time() - t_start,
        history=history, feasible=bool(np.isfinite(exact).all()))


# ------------------------------------------------------------ port trimming
def _trim_sweep(x: np.ndarray, eu: np.ndarray, ev: np.ndarray,
                problems: list[DESProblem], budgets,
                batch=None) -> np.ndarray:
    """The greedy cyclic drop-one sweep both trimmers run.

    Each round builds every drop-one candidate of `x` over the pairs
    (eu, ev) and accepts the first, in cyclic pair order from the last
    accepted drop, whose exact numpy makespan stays within `budgets` for
    every problem; the sweep ends when no candidate is accepted.

    `batch`, when given, maps the candidates (padded to E rows, so XLA
    compiles once) to float32 (makespans, feasible) from the device DES
    and turns on the `_trim_filter_bands` pre-filter: the first pass
    certifies the filter-approved candidates, and the termination
    backstop the ambiguous band (~fits & near) -- candidates the float32
    filter rejected by less than `_TRIM_BACKSTOP_BAND` or flagged
    infeasible, the only ones a bounded f32 DES error could have
    misjudged -- so termination needs no exact re-check of every
    clearly-over-budget candidate.  A float32 false negative mid-round can
    at most reorder accepts relative to the serial sweep; every accept is
    numpy-certified, so the budget is never violated.
    """
    E = len(eu)
    checks = list(zip(problems, np.atleast_1d(budgets)))
    ptr = 0   # cyclic sweep pointer
    while True:
        droppable = np.nonzero(x[eu, ev] > 1)[0]
        k = len(droppable)
        if k == 0:
            return x
        xs = np.repeat(x[None], k, axis=0)
        rows = np.arange(k)
        xs[rows, eu[droppable], ev[droppable]] -= 1
        xs[rows, ev[droppable], eu[droppable]] -= 1
        if batch is None:
            passes = (np.ones(k, dtype=bool),)   # certified serially below
        else:
            ms, feas = batch(np.concatenate(
                [xs, np.repeat(xs[:1], E - k, axis=0)]))
            fits, near = _trim_filter_bands(ms, feas, budgets)
            # a candidate is worth exact-checking only if EVERY member is
            # in band: one member clearly over budget rejects it outright
            fits = fits.reshape(E, -1).all(axis=1)[:k]
            near = near.reshape(E, -1).all(axis=1)[:k]
            passes = (fits, ~fits & near)
        scan = np.argsort((droppable - ptr) % E, kind="stable")
        hit = next((i for band in passes for i in scan if band[i]
                    and all(simulate(p, xs[i]).makespan <= b
                            for p, b in checks)), None)
        if hit is None:
            return x
        x = xs[hit]
        ptr = (int(droppable[hit]) + 1) % E


def trim_ports_ensemble(ensemble: DagEnsemble, x: np.ndarray,
                        rel_tol: float = 1e-6,
                        backend: str = "auto") -> np.ndarray:
    """Robust analog of `trim_ports`: greedy port minimization certified
    against EVERY ensemble member -- a circuit is dropped only if no
    member's exact (numpy DES) makespan degrades beyond `rel_tol` of its
    value under the input topology.

    Batched like the single-DAG `trim_ports` (`_trim_sweep`): each round
    scores all drop-one candidates against all members in ONE
    `EnsembleJaxDES.ensemble_genome_makespan` call (candidates x members
    vmap over the shared compile bucket).  'auto' engages the batched
    path on wide fabrics (large union-pair count with enough droppable
    circuits to amortize the jit) and keeps the serial member sweep on
    fleet-scale ensembles of small phase DAGs, where that is faster."""
    problems = [DESProblem(m) for m in ensemble.members]
    x = np.asarray(x)
    base = np.array([simulate(p, x).makespan for p in problems])
    if not np.isfinite(base).all():
        return x
    x = x.copy()
    pairs = ensemble.undirected_pairs()
    E = len(pairs)
    if E == 0:
        return x
    earr = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
    eu, ev = earr[:, 0], earr[:, 1]
    droppable_total = int(np.maximum(x[eu, ev] - 1, 0).sum())
    # the genome view only covers the union pairs: circuits anywhere else
    # would be invisible to the batched scatter, so fall back to serial
    off_pair = x.copy()
    off_pair[eu, ev] = 0
    off_pair[ev, eu] = 0
    batch = None
    if off_pair.sum() == 0 and (
            backend == "jax"
            or (backend == "auto"
                and max(p.n for p in problems) <= GAOptions.jax_task_limit
                and E >= 16 and droppable_total >= 32)):
        from repro.core.des_jax import EnsembleJaxDES
        jd = EnsembleJaxDES(problems)

        def batch(xs):
            return jd.ensemble_genome_makespan(
                xs[:, eu, ev].astype(np.int64), eu, ev)
    return _trim_sweep(x, eu, ev, problems, base * (1 + rel_tol), batch)


def trim_ports(dag: CommDAG, x: np.ndarray, rel_tol: float = 1e-6,
               backend: str = "auto") -> np.ndarray:
    """Greedy port minimization for heuristic topologies (beyond-paper
    DELTA-Fast counterpart of Eq. 4): repeatedly drop the circuit whose
    removal leaves the DES makespan unchanged, exploiting the temporal
    slack of non-critical tasks.

    Batched (`_trim_sweep`): each round scores *all* drop-one candidates
    from the current topology in a single `JaxDES.batch_makespan` call,
    then accepts the first fitting drop in the serial cyclic sweep order
    after certifying it against the exact numpy DES.  The float32 batch
    is only a pre-filter (with a conservative `_TRIM_FILTER_SLACK`
    margin); on the tested workloads the results are identical to the
    serial sweep (see tests/test_ga_vectorized.py).
    """
    problem = DESProblem(dag)
    base = simulate(problem, np.asarray(x)).makespan
    if not np.isfinite(base):
        return x
    x = np.asarray(x).copy()
    pairs = dag.undirected_pairs()
    E = len(pairs)
    if E == 0:
        return x
    earr = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
    eu, ev = earr[:, 0], earr[:, 1]
    # 'auto' picks the batched path only where it can win: one batched call
    # evaluates E candidates in a single max-lane while_loop pass, so it
    # needs a wide fabric (large E) plus enough potential drops to amortize
    # the one-time XLA compile; on narrow pipeline DAGs (E < 16) the serial
    # numpy sweep is strictly faster
    droppable_total = int(np.maximum(x[eu, ev] - 1, 0).sum())
    batch = None
    if backend == "jax" or (backend == "auto"
                            and problem.n <= GAOptions.jax_task_limit
                            and E >= 16 and droppable_total >= 32):
        from repro.core.des_jax import JaxDES
        batch = JaxDES(problem).batch_makespan
    return _trim_sweep(x, eu, ev, [problem], base * (1 + rel_tol), batch)


def exhaustive_search(dag: CommDAG, limit: int = 200000
                      ) -> tuple[np.ndarray, float, int]:
    """Exact topology search by enumeration (tests / tiny instances)."""
    space = TopologySpace(dag)
    problem = DESProblem(dag)
    ranges = [range(1, int(b) + 1) for b in space.xbar]
    total = int(np.prod([len(r) for r in ranges]))
    if total > limit:
        raise ValueError(f"{total} combinations exceed limit {limit}")
    best = (INF, None)
    count = 0
    for combo in itertools.product(*ranges):
        g = np.asarray(combo, dtype=np.int64)
        if not space.is_feasible(g):
            continue
        count += 1
        ms = simulate(problem, space.to_matrix(g)).makespan
        if ms < best[0]:
            best = (ms, g)
    if best[1] is None:
        raise RuntimeError("no feasible topology")
    return space.to_matrix(best[1]), float(best[0]), count
