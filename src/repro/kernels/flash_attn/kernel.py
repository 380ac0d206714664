"""Pallas TPU flash attention (GQA, causal, online softmax).

The TPU-native replacement for the XLA query-chunked path
(``repro.models.layers.chunked_attention``): one fused kernel holding a
``(bq, hd)`` output accumulator and running (max, sum) statistics in
VMEM while streaming ``(bk, hd)`` key/value tiles from HBM — the
``(S, T)`` score matrix never exists, and *fully-masked causal tiles
are skipped* (`pl.when` over the whole tile body), which removes the
2x causal-compute waste the XLA path pays.

Adaptation note (DESIGN §3): FlashAttention's CUDA formulation tunes
shared-memory banking and warp occupancy; on TPU the same insight maps
to VMEM block residency + MXU-aligned (128) tiles, with the grid's
innermost axis ("arbitrary" semantics) carrying the kv stream.

Grid: (B * H, S/bq, T/bk); q/k/v are reshaped to head-major 3-D outside
the kernel, and the GQA group maps query-head -> kv-head in the index
map (no materialized head repetition).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pad_axis, pick_tile, round_up

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, bq, bk, t_valid):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal skip: tile is dead when every key index > every query index —
    # the whole body is predicated off, removing the 2x causal waste.
    q_last = qi * bq + bq - 1
    k_first = ki * bk
    live = (k_first <= q_last) if causal else (ki >= 0)

    @pl.when(live)
    def _tile():
        q = q_ref[0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)          # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                  # (bq, bk)

        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < t_valid                      # key padding
        if causal:
            mask &= rows >= cols
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                       # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = alpha * l_ref[:, 0] + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "causal", "interpret", "bq", "bk")
)
def flash_attention(q, k, v, scale, *, causal: bool = True,
                    interpret: bool = False, bq: int = 128, bk: int = 128):
    """q (B,S,H,hd), k/v (B,T,Hkv,hd) -> (B,S,H*hd) f32."""
    B, S, H, hd = q.shape
    T, hkv = k.shape[1], k.shape[2]
    g = H // hkv
    bq = pick_tile(S, bq)
    bk = pick_tile(T, bk)
    Sp, Tp = round_up(S, bq), round_up(T, bk)

    # head-major layout: (B*H, S, hd) / (B*Hkv, T, hd)
    qh = pad_axis(q.transpose(0, 2, 1, 3).reshape(B * H, S, hd), 1, Sp)
    kh = pad_axis(k.transpose(0, 2, 1, 3).reshape(B * hkv, T, hd), 1, Tp)
    vh = pad_axis(v.transpose(0, 2, 1, 3).reshape(B * hkv, T, hd), 1, Tp)

    grid = (B * H, Sp // bq, Tp // bk)
    kernel = functools.partial(
        _flash_kernel, scale=float(scale), causal=causal,
        bq=bq, bk=bk, t_valid=T,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j, g=g: (h // g, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j, g=g: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qh, kh, vh)
    out = out[:, :S, :]  # strip seq padding
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3).reshape(B, S, H * hd)
