"""Pallas TPU kernel for the batched ICM conditional-delta sweep.

Computes ``delta[s, p] = u[p] + sum_q X[s, q] * C[q, p]`` — the inner
loop of both greedy closure and the entailment-matrix construction
(DESIGN §3).  On TPU this is a tiled MXU matmul with the unary add fused
into the epilogue, so the sweep never round-trips the (S, P) delta
through HBM between the matmul and the bias.

Tiling: output tiles (bs, bp) held in a VMEM f32 scratch accumulator;
the contraction dim is the innermost ("arbitrary") grid axis.  Every
pair axis of an EM bin (P <= 496 for k <= 32) is one whole block, so the
(P, P) coupling is never padded; longer axes are cut into 128-multiples
(``common.pick_tile``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pad_axis, pick_tile, round_up


def _sweep_kernel(u_ref, x_ref, c_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], c_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...] + u_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "bs", "bp", "bk"))
def sweep_matrix(u, C, X, *, interpret: bool = False, bs=128, bp=512, bk=512):
    """u (P,), C (P, P), X (S, P) -> (S, P) f32 via pallas_call."""
    S, P = X.shape
    bs = pick_tile(S, bs)
    bp = pick_tile(P, bp)
    bk = pick_tile(P, bk)
    Sp, Pp = round_up(S, bs), round_up(P, bp)
    Kp = round_up(P, bk)

    u2 = pad_axis(u.astype(jnp.float32)[None, :], 1, Pp)
    Xp = pad_axis(pad_axis(X.astype(jnp.float32), 0, Sp), 1, Kp)
    Cp = pad_axis(pad_axis(C.astype(jnp.float32), 0, Kp), 1, Pp)

    grid = (Sp // bs, Pp // bp, Kp // bk)
    out = pl.pallas_call(
        _sweep_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bp), lambda i, j, k: (0, j)),
            pl.BlockSpec((bs, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bp), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bs, bp), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Sp, Pp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bs, bp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(u2, Xp, Cp)
    return out[:S, :P]


def sweep(u, C, x, *, interpret: bool = False):
    return sweep_matrix(u, C, x[None, :], interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def sweep_batch(u, C, X, *, interpret: bool = False):
    """u (B, P), C (B, P, P), X (B, P) -> (B, P) f32.

    Batched over the bin axis via the pallas_call batching rule — each
    lane is one neighborhood's conditional-delta sweep.
    """
    return jax.vmap(
        lambda ub, Cb, xb: sweep(ub, Cb, xb, interpret=interpret)
    )(u, C, X)
