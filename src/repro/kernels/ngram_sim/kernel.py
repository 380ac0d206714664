"""Pallas TPU kernel: blocked n-gram cosine similarity with fused threshold.

Canopy blocking (§4, [McCallum et al. 2000]) needs all-pairs similarity
between candidate entities.  With entities embedded as L2-normalized
hashed n-gram profiles (see ``repro.core.similarity``), similarity is a
dense ``A @ B^T`` — we tile it over the MXU and fuse the loose-threshold
cut in the epilogue so sub-threshold lanes are zeroed before leaving
VMEM (the host then only materializes the sparse survivors).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pad_axis, pick_tile, round_up


def _sim_kernel(a_ref, b_ref, o_ref, acc_ref, *, threshold: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...].T, preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        s = acc_ref[...]
        o_ref[...] = jnp.where(s >= threshold, s, 0.0)


@functools.partial(
    jax.jit, static_argnames=("threshold", "interpret", "bm", "bn", "bf")
)
def sim_above(
    A, B, threshold: float = 0.0, *, interpret: bool = False, bm=128, bn=128, bf=128
):
    """A (M,F), B (N,F) -> (M,N) f32, entries < threshold zeroed."""
    M, F = A.shape
    N, _ = B.shape
    bm = pick_tile(M, bm)
    bn = pick_tile(N, bn)
    bf = pick_tile(F, bf)
    Mp, Np, Fp = round_up(M, bm), round_up(N, bn), round_up(F, bf)
    Ap = pad_axis(pad_axis(A.astype(jnp.float32), 0, Mp), 1, Fp)
    Bp = pad_axis(pad_axis(B.astype(jnp.float32), 0, Np), 1, Fp)

    grid = (Mp // bm, Np // bn, Fp // bf)
    out = pl.pallas_call(
        functools.partial(_sim_kernel, threshold=threshold),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bf), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bf), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(Ap, Bp)
    return out[:M, :N]


def sim_matrix(A, B, *, interpret: bool = False):
    return sim_above(A, B, threshold=-2.0, interpret=interpret)
