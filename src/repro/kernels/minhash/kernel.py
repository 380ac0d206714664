"""Pallas TPU kernel: batched MinHash signatures for streaming ingest.

The streaming LSH index (``repro.stream.index``) needs MinHash
signatures for every arriving micro-batch.  A signature is a masked min
reduction: ``sig[n, h] = min_d { A[h, d] : X[n, d] > 0 }`` over the
shingle axis ``d`` — a "min-plus matmul" shape, so we tile it like the
``ngram_sim`` matmul but with the VPU's elementwise min instead of the
MXU.

Within a block the shingle axis is walked one slot at a time: presence
column ``X[:, d]`` (bn, 1) against hash row ``At[d, :]`` (1, bh), both
broadcast to the (bn, bh) accumulator.  Only static slices and 2-D
broadcasts appear, which Mosaic lowers; a (bn, bd, bh) intermediate or
an in-kernel transpose would need shape casts it refuses.  ``A`` is fed
transposed — ``At (D, H)`` — so hash rows are sublane slices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pad_axis, pick_tile, round_up
from repro.kernels.minhash.ref import EMPTY


def _minhash_kernel(x_ref, at_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, EMPTY)

    x = x_ref[...]  # (bn, bd)
    at = at_ref[...]  # (bd, bh)
    acc = acc_ref[...]
    for d in range(x.shape[1]):
        acc = jnp.minimum(acc, jnp.where(x[:, d : d + 1] > 0, at[d : d + 1, :], EMPTY))
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bh", "bd"))
def minhash(X, A, *, interpret: bool = False, bn=128, bh=128, bd=128):
    """X (N, D) presence, A (H, D) int32 -> (N, H) int32 signatures."""
    N, D = X.shape
    H, _ = A.shape
    bn = pick_tile(N, bn)
    bh = pick_tile(H, bh)
    bd = pick_tile(D, bd)
    Np, Hp, Dp = round_up(N, bn), round_up(H, bh), round_up(D, bd)
    Xp = pad_axis(pad_axis((X > 0).astype(jnp.int32), 0, Np), 1, Dp)
    At = pad_axis(pad_axis(A.astype(jnp.int32).T, 0, Dp, fill=EMPTY), 1, Hp)

    grid = (Np // bn, Hp // bh, Dp // bd)
    out = pl.pallas_call(
        _minhash_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bd, bh), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bn, bh), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Np, Hp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bn, bh), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(Xp, At)
    return out[:N, :H]
