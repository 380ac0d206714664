"""Pallas TPU kernel: batched supermodular set scoring.

``f[b, s] = X[b,s,:] . u[b,:] + 1/2 * X[b,s,:] (C[b] X[b,s,:]^T)``

This powers (i) the Type-II probability checks of MMP step 7, (ii) the
UB upper-bound scheme of §6.1, and (iii) exact subset enumeration over
small entailment components, where ``S = 2^m`` candidate sets are scored
in one launch (the MXU-native replacement for per-set Alchemy calls).

Strategy per (b, s-tile): loop P-tiles twice —
  pass k: Y_tile = X_tile @ C[:, ktile]   (accumulated in VMEM scratch)
  epilogue: lin = X @ u, quad = 1/2 rowsum(Y * X), out = lin + quad.

We fuse by computing, for each contraction tile k:
  acc[s] += X[s, ktile] . u[ktile]                 (linear part)
  acc[s] += 1/2 * rowsum((X[s,:] @ C[:, ktile]) * X[s, ktile])
where the inner matmul loops over the *other* P axis with its own grid
dim, giving grid (B, S/bs, P/bp, P/bk): the quad term accumulates the
full X @ C product restricted to the output ktile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pad_axis, pick_tile, round_up


def _score_kernel(u_ref, x_ref, xj_ref, c_ref, o_ref, y_acc, f_acc):
    # grid = (B, S/bs, P/bj, P/bk); for fixed (b, s-tile, j-tile):
    #   y_acc (bs, bj) accumulates (X @ C)[:, jtile] over k
    #   at last k: f_acc += rowsum(0.5 * y * xj) + (j==0 ? X@u : 0)
    j = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when((j == 0) & (k == 0))
    def _init_f():
        f_acc[...] = jnp.zeros_like(f_acc)

    @pl.when(k == 0)
    def _init_y():
        y_acc[...] = jnp.zeros_like(y_acc)

    y_acc[0] += jnp.dot(
        x_ref[0], c_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(3) - 1)
    def _epilogue():
        xj = xj_ref[0]  # (bs, bj)
        f_acc[0] += jnp.sum(0.5 * y_acc[0] * xj, axis=1, keepdims=True)
        f_acc[0] += jnp.sum(xj * u_ref[0], axis=1, keepdims=True)

    @pl.when(
        (j == pl.num_programs(2) - 1) & (k == pl.num_programs(3) - 1)
    )
    def _done():
        o_ref[0] = f_acc[0]


@functools.partial(jax.jit, static_argnames=("interpret", "bs", "bj", "bk"))
def score_sets(u, C, X, *, interpret: bool = False, bs=128, bj=512, bk=512):
    """u (B,P), C (B,P,P), X (B,S,P) -> (B,S) f32."""
    B, S, P = X.shape
    bs = pick_tile(S, bs)
    bj = pick_tile(P, bj)
    bk = pick_tile(P, bk)
    Sp, Pj, Pk = round_up(S, bs), round_up(P, bj), round_up(P, bk)

    u_p = pad_axis(u.astype(jnp.float32), 1, Pj)[:, None, :]  # (B,1,Pj)
    X_k = pad_axis(pad_axis(X.astype(jnp.float32), 1, Sp), 2, Pk)
    X_j = pad_axis(pad_axis(X.astype(jnp.float32), 1, Sp), 2, Pj)
    C_p = pad_axis(pad_axis(C.astype(jnp.float32), 1, Pk), 2, Pj)

    grid = (B, Sp // bs, Pj // bj, Pk // bk)
    out = pl.pallas_call(
        _score_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bj), lambda b, s, j, k: (b, 0, j)),
            pl.BlockSpec((1, bs, bk), lambda b, s, j, k: (b, s, k)),
            pl.BlockSpec((1, bs, bj), lambda b, s, j, k: (b, s, j)),
            pl.BlockSpec((1, bk, bj), lambda b, s, j, k: (b, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bs, 1), lambda b, s, j, k: (b, s, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, bs, bj), jnp.float32),
            pltpu.VMEM((1, bs, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(u_p, X_k, X_j, C_p)
    return out[:, :S, 0]
