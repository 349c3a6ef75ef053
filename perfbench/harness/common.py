"""Pieces the traffic generators share: a configuration turned into the
program's job, per-request seeds, and the DAG check against the one a
configuration file states."""
from __future__ import annotations

import hashlib
import math

import numpy as np

# the precision one step below each that a configuration can state: the
# control of a number computed at that precision
LOWER_PRECISION = {"float64": "float32", "float32": "bfloat16"}


def request_seed(seed: int, index: int) -> int:
    """GA seed of request `index` of a run with `--seed seed`."""
    ss = np.random.SeedSequence([int(seed) % 2**64, int(index)])
    return int(ss.generate_state(1, np.uint64)[0] >> 1)


def check_rng(seed: int) -> np.random.Generator:
    """The generator that draws the sample the reference checks."""
    return np.random.default_rng([int(seed) % 2**64, 0x5EED])


def make_job(config: dict):
    """The program's JobSpec of a deployment file."""
    from repro.configs.base import ArchSpec, ModelConfig, ParallelismPlan
    from repro.configs.base import make_job as program_make_job
    par = config["parallelism"]
    model = ModelConfig(**config["model"])
    if "parameters" in config and \
            model.total_params() != config["parameters"]:
        raise ValueError(f"{config['name']}: the widths give "
                         f"{model.total_params()} parameters, the file "
                         f"states {config['parameters']}")
    arch = ArchSpec(model, ParallelismPlan(**par))
    cl = config["cluster"]
    return program_make_job(arch, seq_len=cl["seq_len"],
                            microbatches=par["microbatches"],
                            act_bytes=cl["act_bytes"],
                            grad_bytes=cl["grad_bytes"])


def _num(v: float) -> str:
    """A float to ten significant digits: a builder that sums in another
    order lands on the same text."""
    return f"{float(v):.9e}"


def dag_fingerprint(dag) -> dict:
    """What pins the DAG a configuration yields: sizes, sums, each ordered
    pod pair's tasks, flows and bytes, and a digest of every task (its kind,
    tag, pods, flows and bytes) and every dependency (its two tasks, named
    by kind and tag, and its delay).  Naming tasks by kind and tag leaves
    the digest alone when a builder numbers the same tasks otherwise, and
    changes it when a task moves to other pods, bytes move between tasks,
    or a dependency is rewired."""
    tasks = [t for t in dag.tasks if not t.is_virtual]
    name = {t.tid: repr((t.kind, t.tag)) for t in tasks}
    pairs: dict[str, dict] = {}
    for t in tasks:
        p = pairs.setdefault(f"{t.src_pod}>{t.dst_pod}",
                             {"tasks": 0, "flows": 0.0, "volume_bytes": 0.0})
        p["tasks"] += 1
        p["flows"] += float(t.flows)
        p["volume_bytes"] += float(t.volume)
    lines = sorted(f"task {name[t.tid]} {t.src_pod}>{t.dst_pod} {t.flows} "
                   f"{_num(t.volume)}" for t in tasks)
    lines += sorted(f"dep {name.get(d.pre, 'source')} -> {name[d.succ]} "
                    f"{_num(d.delta)}" for d in dag.deps)
    undirected = {tuple(sorted((t.src_pod, t.dst_pod))) for t in tasks}
    return {"tasks": len(tasks), "deps": len(dag.deps),
            "pods": int(dag.cluster.num_pods),
            "active_pairs": len(undirected),
            "ports": int(sum(dag.cluster.port_limits)),
            "flows": float(sum(t.flows for t in tasks)),
            "volume_bytes": float(sum(t.volume for t in tasks)),
            "delay_s": float(sum(d.delta for d in dag.deps)),
            "pairs": dict(sorted(pairs.items())),
            "digest": hashlib.sha256("\n".join(lines).encode()).hexdigest()}


def dag_mismatches(found: dict, stated: dict, rtol: float = 1e-9,
                   prefix: str = "") -> list[str]:
    """Keys on which a DAG's fingerprint differs from the one the
    configuration states: integers and text exactly, sums to `rtol`,
    groups key by key and with the same keys."""
    bad = []
    for key in sorted(set(found) | set(stated)):
        want, got = stated.get(key), found.get(key)
        where = prefix + str(key)
        if want is None or got is None:
            bad.append(where)
        elif isinstance(want, dict):
            bad += dag_mismatches(got, want, rtol, where + ".")
        elif isinstance(want, (int, str)) and not isinstance(want, bool):
            if got != want:
                bad.append(where)
        elif not math.isclose(got, want, rel_tol=rtol):
            bad.append(where)
    return bad


def plan_faults(x: np.ndarray, port_limits: np.ndarray,
                pairs: list[tuple[int, int]]) -> list[str]:
    """What makes `x` no valid plan: it must be a symmetric matrix of
    non-negative whole circuits, within every pod's port budget, with at
    least one circuit on every pair that carries traffic."""
    x = np.asarray(x)
    out = []
    if x.shape != (len(port_limits),) * 2:
        return [f"shape {x.shape}"]
    if not np.array_equal(x, np.round(x)):
        out.append("fractional circuits")
    if not np.array_equal(x, x.T):
        out.append("not symmetric")
    if (x < 0).any():
        out.append("negative circuits")
    if np.diag(x).any():
        out.append("circuits from a pod to itself")
    over = np.nonzero(x.sum(axis=1) > port_limits)[0]
    if len(over):
        out.append(f"pods {over.tolist()} over their port budget")
    dead = [p for p in pairs if x[p] < 1]
    if dead:
        out.append(f"pairs {dead} without a circuit")
    return out
