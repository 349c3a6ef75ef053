"""The dominance prune's device path keeps exactly the edges of the loop.

`_prune_device` decides each edge by a float32 max-plus product and
decides again, in float64, the edges within the rounding band of their
bound; `_prune_host` is the loop it replaces on a TPU.  Here the kernel
runs in Pallas interpret mode on the CPU.
"""
from __future__ import annotations

import functools

import numpy as np
import pytest

from repro.configs import PAPER_WORKLOADS, make_job
from repro.core import schedule
from repro.core.schedule import build_comm_dag
from repro.kernels.maxplus import TILE
from repro.obs import TRACER

EPS = 1e-12


def _layered_edges(seed: int, layers: int = 4, width: int = 30,
                   p: float = 0.4):
    """Random forward edges between layers of `width` tasks, task 0 the
    virtual source.  The largest lag is the pinned edge (0, last) of 10 s,
    so rewriting the other lags below it leaves the band where it is."""
    rng = np.random.default_rng(seed)
    n = 1 + layers * width
    layer = np.r_[-1, np.arange(n - 1) // width]
    tau = np.r_[0.0, rng.uniform(0.0, 0.5, n - 1)]
    edges = {}
    for o in range(n):
        for nn in range(1, n):
            if layer[o] < layer[nn] and rng.random() < p:
                edges[(o, nn)] = float(rng.uniform(0.0, 2.0))
    edges[(0, n - 1)] = 10.0
    return edges, tau, layer


def _on_eps_bound(c: float) -> float:
    """A lag delta with delta - EPS == c in float64: the loop's `>=` holds
    and a `>` would not."""
    d = c + EPS
    for _ in range(64):
        if d - EPS == c:
            return d
        d = np.nextafter(d, np.inf if d - EPS < c else -np.inf)
    raise AssertionError(f"no lag on the eps bound of {c!r}")


def _with_ties(edges, tau, layer, offsets):
    """Set each layer-0 -> layer-2 edge's lag to its best 2-path, summed as
    the loop sums it, plus the next offset in turn (None: on the eps
    bound).  Its 2-paths run through layer 1 alone, whose edges stay as
    they are."""
    out_of = {}
    for (o, m), d in edges.items():
        out_of.setdefault(o, []).append((m, d))
    edges = dict(edges)
    ties = [k for k in edges if layer[k[0]] == 0 and layer[k[1]] == 2]
    for i, (o, nn) in enumerate(ties):
        paths = [d1 + tau[m] + edges[(m, nn)] for m, d1 in out_of[o]
                 if (m, nn) in edges]
        if paths:
            off = offsets[i % len(offsets)]
            edges[(o, nn)] = float(_on_eps_bound(max(paths)) if off is None
                                   else max(paths) + off)
    return edges


def _band(edges, tau):
    return schedule._f32_band(np.fromiter(edges.values(), float), tau, EPS)


def _case(name):
    edges, tau, layer = _layered_edges(
        seed={"random": 0, "random-wide": 1, "exact-ties": 2,
              "near-ties-inside": 3, "near-ties-outside": 4}[name],
        width=50 if name == "random-wide" else 30)
    band = _band(edges, tau)
    if name == "exact-ties":
        # on the bound, on and either side of the float64 predicate's eps
        edges = _with_ties(edges, tau, layer,
                           [0.0, None, EPS / 2, 2 * EPS, -EPS])
    elif name == "near-ties-inside":
        edges = _with_ties(edges, tau, layer,
                           [band / 4, -band / 4, band / 2, -band / 2])
    elif name == "near-ties-outside":
        edges = _with_ties(edges, tau, layer, [2 * band, -2 * band])
    assert _band(edges, tau) == band
    return edges, tau


@pytest.mark.parametrize("name", ["random", "random-wide", "exact-ties",
                                  "near-ties-inside", "near-ties-outside",
                                  "megatron-177b"])
def test_device_prune_keeps_the_loops_edges(name, monkeypatch):
    if name == "megatron-177b":
        arch = PAPER_WORKLOADS[name]
        job = make_job(arch, seq_len=4096,
                       microbatches=arch.plan.num_microbatches)
        host = build_comm_dag(job)
        monkeypatch.setattr(schedule, "_prune_fits_device", lambda n_pad: True)
        monkeypatch.setattr(schedule, "_prune_device", functools.partial(
            schedule._prune_device, backend="pallas", interpret=True))
        with TRACER.enabled():
            TRACER.clear()
            dev = build_comm_dag(job)
            prune = next(r.attrs for r in TRACER.records
                         if r.name == "dag.prune")
            TRACER.clear()
        assert prune["path"] == "device" and prune["n_pad"] == 256
        assert prune["rechecked"] == 0
        assert [(d.pre, d.succ) for d in dev.deps] == \
            [(d.pre, d.succ) for d in host.deps]
        bits = [np.array([d.delta for d in g.deps]).view(np.int64)
                for g in (dev, host)]
        np.testing.assert_array_equal(*bits)
        return
    edges, tau = _case(name)
    n_pad = -(-len(tau) // TILE) * TILE
    kept, rechecked = schedule._prune_device(edges, tau, n_pad, EPS,
                                             backend="pallas", interpret=True)
    want = schedule._prune_host(edges, tau.tolist(), EPS)
    assert list(kept.items()) == list(want.items())
    assert 0 < len(want) < len(edges)
    if name in ("exact-ties", "near-ties-inside"):
        assert rechecked > 0
    else:
        assert rechecked == 0
