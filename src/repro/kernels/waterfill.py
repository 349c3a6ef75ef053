"""Pallas TPU kernel: fused water-filling matvec pair.

One progressive-filling round of the max-min fair-share computation (DES
inner loop) needs, per constraint c:

    used_c  = sum_m W[c, m] * (phi_m * active_m)
    denom_c = sum_m W[c, m] * unfrozen_m

Both are matvecs against the same incidence matrix W.  A matvec on the MXU
wastes 127/128 lanes, so we stack the two right-hand sides into an (N, R)
matrix padded to R=128 lanes: the extra lanes are free (the systolic array
processes 128 lanes regardless), and W -- the bandwidth-dominant operand --
is streamed through VMEM exactly once for both reductions.

`fill_round` is the per-event DES layout of the same kernel: it takes the
two per-task vectors of one filling round (active flow levels, unfrozen
mask) and returns the per-constraint `(used, denom)` pair.  The DES event
loop (`repro.core.des_jax._maxmin`) calls it once per filling round; it is
vmap-safe (batched over GA populations and ensemble members).  On the CPU
backend the DES uses `repro.kernels.ref.fill_round_ref` instead; interpret
mode is for tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _fill_kernel(w_ref, rhs_ref, out_ref, *, nsteps_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(w_ref[...], rhs_ref[...],
                            preferred_element_type=jnp.float32)
    del nsteps_k


@functools.partial(jax.jit, static_argnames=("bc", "bk", "interpret"))
def fill_matvec(w: jax.Array, rhs: jax.Array, *, bc: int = 128,
                bk: int = 128, interpret: bool = False) -> jax.Array:
    """(C, N) @ (N, R) -> (C, R) with R padded to the 128-lane MXU width."""
    c, n = w.shape
    n2, r = rhs.shape
    assert n == n2 and r <= LANES
    w = w.astype(jnp.float32)
    rhs = rhs.astype(jnp.float32)
    cp = max(((c + bc - 1) // bc) * bc, bc)
    np_ = max(((n + bk - 1) // bk) * bk, bk)
    w = jnp.pad(w, ((0, cp - c), (0, np_ - n)))
    rhs = jnp.pad(rhs, ((0, np_ - n), (0, LANES - r)))
    grid = (cp // bc, np_ // bk)

    out = pl.pallas_call(
        functools.partial(_fill_kernel, nsteps_k=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bk, LANES), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bc, LANES), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, LANES), jnp.float32),
        interpret=interpret,
    )(w, rhs)
    return out[:c, :r]


def fill_round(w: jax.Array, level: jax.Array, unfrozen: jax.Array, *,
               interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """One DES fair-share filling round: per-constraint (used, denom).

    w:        (C, N) constraint-task incidence weights
    level:    (N,)   current active flow levels (phi * active)
    unfrozen: (N,)   unfrozen-task mask (float)
    Both reductions share one pass over `w` (stacked 2-lane RHS).
    """
    rhs = jnp.stack([level, unfrozen], axis=1)
    out = fill_matvec(w, rhs, interpret=interpret)
    return out[:, 0], out[:, 1]
