"""Pallas TPU kernel: tropical (max, +) matrix product.

Longest-path propagation over the dependency DAG (EST/LCT windows of
Algs. 1/4) is a max-plus matrix product; repeated squaring of the adjacency
matrix (diagonal = 0, missing edge = NEG_INF) yields all-pairs longest
paths in ceil(log2 n) products.

The MXU cannot evaluate a (max, +) semiring, so this kernel targets the VPU:
for each (BM, BN) output tile we stream (BK, BM) x (BK, BN) operand tiles
through VMEM, K on the sublanes of both.  Every block is (8, 128)-aligned,
as the TPU compiler requires (a (BM, 8) block of `a` is refused); `a` is
passed transposed for that reason.  Inside a grid step a loop walks the
K-block in 8-row chunks: the (8, BM) chunk of `a` is transposed in
registers, and its 8 columns are unrolled as rank-1 broadcast max-adds
against the matching rows of `b`.  Chunks are taken along the sublanes
only: a dynamic 8-wide slice along the lanes cannot be proven
128-aligned, and the compiler refuses it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import NEG_INF

SUBLANES = 8
# Side of an output tile: on a v5e, 256-wide tiles take half the time of
# 128-wide ones at n 3712 (0.10 against 0.21 s).
TILE = 256


def _maxplus_kernel(at_ref, b_ref, out_ref, *, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, NEG_INF)

    def chunk(c, acc):
        row = pl.multiple_of(c * SUBLANES, SUBLANES)
        a = at_ref[pl.ds(row, SUBLANES), :].T     # (BM, 8)
        b = b_ref[pl.ds(row, SUBLANES), :]        # (8, BN)
        for k in range(SUBLANES):
            acc = jnp.maximum(acc, a[:, k:k + 1] + b[k:k + 1, :])
        return acc

    out_ref[...] = jax.lax.fori_loop(0, bk // SUBLANES, chunk, out_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def maxplus(a: jax.Array, b: jax.Array, *, bm: int = TILE, bn: int = TILE,
            bk: int = 128, interpret: bool = False) -> jax.Array:
    """out[i, j] = max_k (a[i, k] + b[k, j]); NEG_INF encodes 'no path'."""
    m, ka = a.shape
    kb, n = b.shape
    assert ka == kb, "inner dimensions must match"
    assert bk % SUBLANES == 0, "bk must be a multiple of 8"
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    mp = max(((m + bm - 1) // bm) * bm, bm)
    np_ = max(((n + bn - 1) // bn) * bn, bn)
    kp = max(((ka + bk - 1) // bk) * bk, bk)
    at = jnp.pad(a, ((0, mp - m), (0, kp - ka)),
                 constant_values=NEG_INF).T
    b = jnp.pad(b, ((0, kp - kb), (0, np_ - n)), constant_values=NEG_INF)
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(_maxplus_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(at, b)
    return jnp.maximum(out[:m, :n], NEG_INF)
