"""Shared transformer building blocks (pure functions, bf16 compute).

Conventions:
  * params are plain dicts (pytrees) built from PSpec declarations;
  * activations are bf16, norms/softmax/logits in f32;
  * tensor-parallel sharding is megatron-style over the ``model`` axis:
    QKV/up projections column-sharded, O/down projections row-sharded,
    embeddings vocab-sharded;
  * attention is einsum-based with an explicit GQA grouping (no head
    repetition materialized);
  * decode uses a KV cache ``[B, n_kv, S_max, hd]`` updated with
    ``dynamic_update_slice`` at position ``pos``.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.param import PSpec

COMPUTE_DTYPE = jnp.bfloat16
NEG_INF = -1e9

# Sequences longer than this use the query-block-chunked attention path
# (bounded (q_block, T) score working set instead of (S, T)).  The fused
# single-einsum path stays for short sequences where S^2 scores are cheap
# and XLA fuses better.
ATTN_CHUNK_THRESHOLD = int(os.environ.get("REPRO_ATTN_CHUNK_THRESHOLD", 4096))
ATTN_Q_BLOCK = int(os.environ.get("REPRO_ATTN_Q_BLOCK", 1024))


def mp(x):
    """Cast to the compute (mixed-precision) dtype."""
    return x.astype(COMPUTE_DTYPE)


def mixed_einsum(spec, a, b):
    """bf16 x bf16 -> f32 contraction.

    TPU form: operands stay bf16 with f32 accumulation on the MXU
    (``preferred_element_type``) — the ``.astype(f32)`` form makes XLA
    materialize f32 copies of whole K/V tensors (for decode: of the
    entire KV cache, observed +4x cache memory).  The XLA *CPU* runtime
    cannot execute BF16xBF16=F32 dots, so tests upcast there; the
    dry-run pins the TPU form (it lowers but never executes).
    """
    mode = os.environ.get("REPRO_MIXED_DOT", "")
    if mode == "preferred" or (not mode and jax.default_backend() != "cpu"):
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))


# Pure-DP layout (launcher-owned): the tensor axis carries batch too.
DP_OVER_MODEL = False


def _dp_axes():
    """Data-parallel axes of the ambient mesh ('pod' shards batch too)."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return None, 1
    names = am.axis_names
    dp_names = ("pod", "data", "model") if DP_OVER_MODEL else ("pod", "data")
    axes = tuple(a for a in dp_names if a in names)
    if not axes:
        return None, 1
    n = 1
    for a in axes:
        n *= am.shape[a]
    return axes, n


def shard_spec(x, entries):
    """Pin an activation to an explicit spec; 'dp' resolves to the
    data-parallel axes (('pod','data') on a multi-pod mesh).  Entries
    whose axes do not divide the dim are dropped. No-op without a mesh."""
    axes, _ = _dp_axes()
    if axes is None:
        return x
    am = jax.sharding.get_abstract_mesh()
    out = []
    for dim, e in zip(x.shape, entries):
        ee = axes if e == "dp" else e
        if ee is None:
            out.append(None)
            continue
        names = ee if isinstance(ee, tuple) else (ee,)
        n = 1
        for a in names:
            n *= am.shape.get(a, 1)
        out.append((ee if len(names) > 1 else names[0]) if dim % n == 0 and n > 1 else None)
    try:
        return jax.lax.with_sharding_constraint(x, P(*out))
    except (RuntimeError, ValueError):
        return x


# Megatron-style sequence parallelism at layer boundaries: when enabled
# (launcher sets it for long-sequence train shapes), the residual stream
# is pinned (dp, model, None) so remat-boundary activations shrink by the
# TP degree; GSPMD inserts the all-gather before attention/SSM mixing and
# the reduce-scatter after.  Module-level because model code is
# mesh-agnostic; the launcher owns the policy.
SEQ_SHARD_BOUNDARY = False


def shard_batch(x, batch_dim: int = 0, model_dim: int | None = None):
    """Pin an activation's batch dim to the data-parallel mesh axes.

    GSPMD sharding propagation is heuristic; through gathers (embedding
    lookups) and FSDP-sharded weights it can drop the batch sharding and
    silently replicate the whole layer stack over ``data``.  Pinning the
    residual-stream batch dim at every layer boundary keeps the
    propagation anchored — the standard megatron/MaxText discipline.

    ``model_dim`` additionally pins that dim to ``model`` (used for the
    vocab dim of logits).  No-op when there is no mesh context (CPU
    smoke tests), or when the dim does not divide evenly.
    """
    axes, n = _dp_axes()
    if axes is None or n == 1 or x.shape[batch_dim] % n != 0:
        return x
    am = jax.sharding.get_abstract_mesh()
    msize = am.shape.get("model", 1)
    entries: list = [None] * x.ndim
    entries[batch_dim] = axes if len(axes) > 1 else axes[0]
    if model_dim is not None and not DP_OVER_MODEL:
        if msize > 1 and x.shape[model_dim] % msize == 0:
            entries[model_dim] = "model"
    elif (
        SEQ_SHARD_BOUNDARY
        and x.ndim == 3
        and batch_dim == 0
        and msize > 1
        and x.shape[1] % msize == 0
    ):
        entries[1] = "model"  # sequence parallelism (residual stream)
    try:
        return jax.lax.with_sharding_constraint(x, P(*entries))
    except (RuntimeError, ValueError):  # no concrete mesh resolvable
        return x


# ---------------------------------------------------------------------------
# Norms / activations / embeddings
# ---------------------------------------------------------------------------


def rmsnorm_spec(d: int) -> PSpec:
    return PSpec((d,), P(), init="ones")


def rmsnorm(scale, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm_spec(d: int) -> dict:
    return {"scale": PSpec((d,), P(), init="ones"), "bias": PSpec((d,), P(), init="zeros")}


def layernorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(
        x.dtype
    )


def embed_spec(vocab: int, d: int) -> PSpec:
    return PSpec((vocab, d), P("model", None), init="embed", scale=0.02)


def embed_lookup(table, ids):
    return mp(jnp.take(table, ids, axis=0))


def unembed(table, x):
    """Logits in f32; vocab axis sharded on `model` (GSPMD inserts the
    collective for the downstream softmax reduction)."""
    return jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32), table.astype(jnp.float32))


def softmax_xent(logits, labels, mask=None):
    """Token-mean cross entropy in f32. labels (B,S) int32, mask (B,S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32), axis=-1)[
        ..., 0
    ]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE + M-RoPE)
# ---------------------------------------------------------------------------


def _rope_freqs(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def rope(x, positions, theta: float):
    """x (..., S, H, hd), positions (..., S) -> rotated x (same dtype)."""
    hd = x.shape[-1]
    freqs = _rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope(x, positions3, theta: float, sections: tuple[int, int, int]):
    """Multimodal RoPE (Qwen2-VL): positions3 (3, B, S) are the
    temporal/height/width position ids; frequency channels are split
    into three sections, each rotated by its own position stream."""
    hd = x.shape[-1]
    freqs = _rope_freqs(hd, theta)  # (hd/2,)
    sec = jnp.cumsum(jnp.asarray((0,) + sections))
    chan = jnp.arange(hd // 2)
    which = jnp.clip(jnp.searchsorted(sec[1:], chan, side="right"), 0, 2)  # (hd/2,)
    # pos_c (B, S, hd/2): per-channel position stream
    pos = jnp.take(positions3, which, axis=0)  # (hd/2, B, S) -> transpose
    pos = jnp.moveaxis(pos, 0, -1).astype(jnp.float32)  # (B, S, hd/2)
    ang = pos * freqs  # (B, S, hd/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def attention_specs(cfg: ModelConfig) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": PSpec((d, h * hd), P(None, "model")),
        "wk": PSpec((d, hkv * hd), P(None, "model")),
        "wv": PSpec((d, hkv * hd), P(None, "model")),
        "wo": PSpec((h * hd, d), P("model", None)),
    }
    if cfg.qkv_bias:
        p["bq"] = PSpec((h * hd,), P("model"), init="zeros")
        p["bk"] = PSpec((hkv * hd,), P("model"), init="zeros")
        p["bv"] = PSpec((hkv * hd,), P("model"), init="zeros")
    return p


def _qkv(cfg: ModelConfig, p, x):
    B, S, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, mp(p["wq"]))
    k = jnp.einsum("bsd,dh->bsh", x, mp(p["wk"]))
    v = jnp.einsum("bsd,dh->bsh", x, mp(p["wv"]))
    if cfg.qkv_bias:
        q = q + mp(p["bq"])
        k = k + mp(p["bk"])
        v = v + mp(p["bv"])
    return (
        q.reshape(B, S, h, hd),
        k.reshape(B, S, hkv, hd),
        v.reshape(B, S, hkv, hd),
    )


def _apply_rope(cfg: ModelConfig, q, k, positions):
    if not cfg.use_rope:
        return q, k
    if cfg.mrope:
        q = mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k


def _gqa_scores(q, k, scale):
    """q (B,S,H,hd), k (B,T,Hkv,hd) -> scores (B,Hkv,G,S,T) f32."""
    B, S, H, hd = q.shape
    hkv = k.shape[2]
    g = H // hkv
    qg = q.reshape(B, S, hkv, g, hd)
    # bf16 operands + f32 accumulation (preferred_element_type): the
    # .astype(f32) form makes XLA materialize f32 copies of whole
    # K tensors (for decode: of the whole KV cache).
    return mixed_einsum("bskgh,btkh->bkgst", qg, k) * scale


def _gqa_out(probs, v, out_dtype):
    """probs (B,Hkv,G,S,T), v (B,T,Hkv,hd) -> (B,S,H*hd)."""
    B, hkv, g, S, T = probs.shape
    hd = v.shape[-1]
    o = mixed_einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v)
    return o.reshape(B, S, hkv * g * hd).astype(out_dtype)


def chunked_attention(q, k, v, scale, *, causal=True, q_block: int | None = None,
                      out_dtype=None):
    """Query-block-chunked exact attention (the XLA long-context path).

    q (B,S,H,hq), k (B,T,Hkv,hq), v (B,T,Hkv,hv) -> (B,S,H*hv).

    Each query block takes its full-row softmax against all T keys —
    numerically identical to the naive path — but only a (q_block, T)
    score tile is ever live.  The block body is rematerialized
    (``jax.checkpoint``) so the backward pass recomputes score tiles
    instead of storing S*T floats.  The Pallas ``flash_attn`` kernel is
    the TPU-target replacement (online softmax + triangular block skip);
    this path is what the dry-run lowers through XLA.
    """
    B, S, H, hq = q.shape
    T, hkv = k.shape[1], k.shape[2]
    g = H // hkv
    hv = v.shape[-1]
    out_dtype = out_dtype or v.dtype
    qb = min(q_block or ATTN_Q_BLOCK, S)
    nb = S // qb
    assert nb * qb == S, f"seq {S} not divisible by q_block {qb}"

    # Sequence-shard K/V over `model` (flash-decoding layout): at one
    # sequence per device GSPMD otherwise "parallelizes" the block
    # contraction across ad-hoc device subgroups and all-reduces the
    # full (qb, T) partial scores every q-block — measured 22 TB/chip
    # on llama4-scout prefill_32k.  With T sharded, the score tile
    # stays sharded and only the softmax statistics and the (qb, H*hv)
    # block output are reduced.  Works for any head count (no
    # divisibility constraint, unlike head sharding).
    def _pin_seq(t):
        try:
            return jax.lax.with_sharding_constraint(
                t, P(None, "model", None, None)
            )
        except (RuntimeError, ValueError):
            return t

    am = jax.sharding.get_abstract_mesh()
    if (
        not am.empty
        and "model" in am.axis_names
        and not DP_OVER_MODEL
        and T % am.shape.get("model", 1) == 0
    ):
        k, v = _pin_seq(k), _pin_seq(v)

    qr = q.reshape(B, nb, qb, hkv, g, hq).transpose(1, 0, 2, 3, 4, 5)
    rows0 = jnp.arange(qb)
    cols = jnp.arange(T)

    def block(blk, qblk):
        s = mixed_einsum("bskgh,btkh->bkgst", qblk, k) * scale
        if causal:
            rows = blk * qb + rows0
            m = rows[:, None] >= cols[None, :]
            s = jnp.where(m[None, None, None], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o = mixed_einsum("bkgst,btkh->bskgh", pr.astype(v.dtype), v)
        return o.reshape(B, qb, H * hv).astype(out_dtype)

    block = jax.checkpoint(block, policy=jax.checkpoint_policies.nothing_saveable)

    def body(blk, qblk):
        return blk + 1, block(blk, qblk)

    _, ob = jax.lax.scan(body, jnp.int32(0), qr)
    return ob.transpose(1, 0, 2, 3).reshape(B, S, H * hv)


def attention_train(cfg: ModelConfig, p, x, positions, *, causal: bool = True):
    """Full-sequence attention. x (B,S,D) bf16, positions (B,S) or (3,B,S)."""
    q, k, v = _qkv(cfg, p, x)
    if not cfg.mla:
        q, k = _apply_rope(cfg, q, k, positions)
    scale = 1.0 / jnp.sqrt(cfg.head_dim).astype(jnp.float32)
    if x.shape[1] > ATTN_CHUNK_THRESHOLD:
        o = chunked_attention(q, k, v, scale, causal=causal, out_dtype=x.dtype)
    else:
        scores = _gqa_scores(q, k, scale)
        if causal:
            S = x.shape[1]
            mask = jnp.tril(jnp.ones((S, S), dtype=bool))
            scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        o = _gqa_out(probs, v, x.dtype)
    return jnp.einsum("bsh,hd->bsd", o, mp(p["wo"]))


def cross_attention_train(cfg: ModelConfig, p, x, memory):
    """Encoder-decoder cross attention (no positions, no mask)."""
    B, S, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, mp(p["wq"])).reshape(B, S, h, hd)
    k = jnp.einsum("bsd,dh->bsh", memory, mp(p["wk"])).reshape(
        B, memory.shape[1], hkv, hd
    )
    v = jnp.einsum("bsd,dh->bsh", memory, mp(p["wv"])).reshape(
        B, memory.shape[1], hkv, hd
    )
    scores = _gqa_scores(q, k, 1.0 / jnp.sqrt(hd).astype(jnp.float32))
    probs = jax.nn.softmax(scores, axis=-1)
    o = _gqa_out(probs, v, x.dtype)
    return jnp.einsum("bsh,hd->bsd", o, mp(p["wo"]))


def attention_cache_specs(cfg: ModelConfig, batch: int, s_max: int) -> dict:
    """KV cache sharding:

    * many KV heads (>=16, divisible): batch on `data`, heads on `model`
      (pure TP decode — no softmax collectives);
    * few KV heads (GQA): batch on `data`, *sequence* on `model`
      (flash-decoding-style partial attention; GSPMD inserts the 2-pass
      softmax reduction);
    * batch == 1 (long-context single stream): sequence sharded over
      both axes.
    """
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    if batch == 1:
        spec = P(None, None, ("data", "model"), None)
    elif hkv >= 16 and hkv % 16 == 0:
        spec = P("data", "model", None, None)
    else:
        spec = P("data", None, "model", None)
    return {
        "k": PSpec((batch, hkv, s_max, hd), spec, init="zeros", dtype=COMPUTE_DTYPE),
        "v": PSpec((batch, hkv, s_max, hd), spec, init="zeros", dtype=COMPUTE_DTYPE),
    }


def attention_decode(cfg: ModelConfig, p, x, cache, pos):
    """Single-token decode. x (B,1,D), cache {k,v} (B,Hkv,S,hd), pos (B,)
    current write position (same for all batch rows under SPMD: we use
    pos[0] as the dynamic slice index). Returns (out, new_cache)."""
    B = x.shape[0]
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _qkv(cfg, p, x)  # (B,1,·,hd)
    if not cfg.mla:
        q, k = _apply_rope(cfg, q, k, pos[:, None])
    # write k/v at pos
    kc = jax.lax.dynamic_update_slice(
        cache["k"], k.transpose(0, 2, 1, 3).astype(cache["k"].dtype), (0, 0, pos[0], 0)
    )
    vc = jax.lax.dynamic_update_slice(
        cache["v"], v.transpose(0, 2, 1, 3).astype(cache["v"].dtype), (0, 0, pos[0], 0)
    )
    S = kc.shape[2]
    g = h // hkv
    qg = q.reshape(B, 1, hkv, g, hd).astype(kc.dtype)
    scores = (
        mixed_einsum("bskgh,bkth->bkgst", qg, kc)
        / jnp.sqrt(hd).astype(jnp.float32)
    )  # (B,hkv,g,1,S)
    tmask = jnp.arange(S)[None, :] <= pos[:, None]  # (B,S)
    scores = jnp.where(tmask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mixed_einsum("bkgst,bkth->bskgh", probs.astype(vc.dtype), vc)
    o = o.reshape(B, 1, h * hd).astype(x.dtype)
    out = jnp.einsum("bsh,hd->bsd", o, mp(p["wo"]))
    return out, {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------


def mla_specs(cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "q_down": PSpec((d, qr), P(None, None)),
        "q_norm": rmsnorm_spec(qr),
        "q_up": PSpec((qr, h * (dn + dr)), P(None, "model")),
        "kv_down": PSpec((d, kr + dr), P(None, None)),
        "kv_norm": rmsnorm_spec(kr),
        "kv_up": PSpec((kr, h * (dn + dv)), P(None, "model")),
        "wo": PSpec((h * dv, d), P("model", None)),
    }


def mla_train(cfg: ModelConfig, p, x, positions):
    B, S, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kr = cfg.kv_lora_rank

    ql = rmsnorm(p["q_norm"], jnp.einsum("bsd,dr->bsr", x, mp(p["q_down"])), cfg.norm_eps)
    q = jnp.einsum("bsr,rh->bsh", ql, mp(p["q_up"])).reshape(B, S, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]

    kv = jnp.einsum("bsd,dr->bsr", x, mp(p["kv_down"]))
    c_kv, k_rope = kv[..., :kr], kv[..., kr:]
    c_kv = rmsnorm(p["kv_norm"], c_kv, cfg.norm_eps)
    kvu = jnp.einsum("bsr,rh->bsh", c_kv, mp(p["kv_up"])).reshape(B, S, h, dn + dv)
    k_nope, v = kvu[..., :dn], kvu[..., dn:]

    q_rope = rope(q_rope, positions, cfg.rope_theta)
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # (B,S,1,dr)

    scale = 1.0 / jnp.sqrt(dn + dr).astype(jnp.float32)
    if S > ATTN_CHUNK_THRESHOLD:
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)  # (B,S,h,dn+dr)
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, S, h, dr))], axis=-1
        )
        o = chunked_attention(qq, kk, v, scale, causal=True, out_dtype=x.dtype)
        return jnp.einsum("bsh,hd->bsd", o, mp(p["wo"]))
    s_nope = mixed_einsum("bshd,bthd->bhst", q_nope, k_nope)
    s_rope = mixed_einsum("bshd,btod->bhst", q_rope, k_rope)
    scores = (s_nope + s_rope) * scale
    mask = jnp.tril(jnp.ones((S, S), dtype=bool))
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mixed_einsum("bhst,bthd->bshd", probs.astype(v.dtype), v)
    o = o.reshape(B, S, h * dv).astype(x.dtype)
    return jnp.einsum("bsh,hd->bsd", o, mp(p["wo"]))


def mla_cache_specs(cfg: ModelConfig, batch: int, s_max: int) -> dict:
    """MLA caches the *compressed* latent + rope key — its whole point:
    cache bytes/token = kv_lora_rank + qk_rope_dim instead of
    2 * n_heads * head_dim (a ~17x reduction for MiniCPM3).

    The latent has no head dim to TP-shard, so the *sequence* shards
    over ``model`` (flash-decoding style: GSPMD inserts the two-pass
    softmax reduction); batch shards over ``data``."""
    seq = ("data", "model") if batch == 1 else "model"
    b_ax = None if batch == 1 else "data"
    return {
        "c_kv": PSpec((batch, s_max, cfg.kv_lora_rank), P(b_ax, seq, None),
                      init="zeros", dtype=COMPUTE_DTYPE),
        "k_rope": PSpec((batch, s_max, cfg.qk_rope_dim), P(b_ax, seq, None),
                        init="zeros", dtype=COMPUTE_DTYPE),
    }


def mla_decode(cfg: ModelConfig, p, x, cache, pos):
    """Absorbed-projection MLA decode: attention runs in the latent
    space (W_uk folded into q, W_uv applied after the probability-
    weighted latent sum)."""
    B = x.shape[0]
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kr = cfg.kv_lora_rank

    ql = rmsnorm(p["q_norm"], jnp.einsum("bsd,dr->bsr", x, mp(p["q_down"])), cfg.norm_eps)
    q = jnp.einsum("bsr,rh->bsh", ql, mp(p["q_up"])).reshape(B, 1, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, pos[:, None], cfg.rope_theta)

    kv = jnp.einsum("bsd,dr->bsr", x, mp(p["kv_down"]))
    c_new, kr_new = kv[..., :kr], kv[..., kr:]
    c_new = rmsnorm(p["kv_norm"], c_new, cfg.norm_eps)
    kr_new = rope(kr_new[:, :, None, :], pos[:, None], cfg.rope_theta)[:, :, 0, :]

    c_cache = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_new.astype(cache["c_kv"].dtype), (0, pos[0], 0)
    )
    r_cache = jax.lax.dynamic_update_slice(
        cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), (0, pos[0], 0)
    )

    # Absorb W_uk: q_lat[b,h,kr] = sum_dn q_nope[b,h,dn] * W_uk[kr,h,dn]
    kv_up = p["kv_up"].reshape(kr, h, dn + dv)
    w_uk = mp(kv_up[..., :dn])  # (kr, h, dn)
    w_uv = mp(kv_up[..., dn:])  # (kr, h, dv)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)  # (B,1,h,kr)

    S = c_cache.shape[1]
    scale = 1.0 / jnp.sqrt(dn + dr).astype(jnp.float32)
    s_lat = mixed_einsum("bshr,btr->bhst", q_lat.astype(c_cache.dtype), c_cache)
    s_rope = mixed_einsum("bshd,btd->bhst", q_rope.astype(r_cache.dtype), r_cache)
    scores = (s_lat + s_rope) * scale
    tmask = jnp.arange(S)[None, :] <= pos[:, None]
    scores = jnp.where(tmask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    lat = mixed_einsum("bhst,btr->bshr", probs.astype(c_cache.dtype), c_cache)  # (B,1,h,kr)
    o = jnp.einsum("bshr,rhd->bshd", lat, w_uv.astype(jnp.float32))
    o = o.reshape(B, 1, h * dv).astype(x.dtype)
    out = jnp.einsum("bsh,hd->bsd", o, mp(p["wo"]))
    return out, {"c_kv": c_cache, "k_rope": r_cache}


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_specs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    if cfg.act == "silu":  # gated: fused [gate; up]
        return {
            "w_in": PSpec((d, 2 * f), P(None, "model")),
            "w_out": PSpec((f, d), P("model", None)),
        }
    return {
        "w_in": PSpec((d, f), P(None, "model")),
        "b_in": PSpec((f,), P("model"), init="zeros"),
        "w_out": PSpec((f, d), P("model", None)),
        "b_out": PSpec((d,), P(), init="zeros"),
    }


def mlp(cfg: ModelConfig, p, x):
    if cfg.act == "silu":
        f = p["w_out"].shape[0]
        gu = jnp.einsum("bsd,df->bsf", x, mp(p["w_in"]))
        gate, up = gu[..., :f], gu[..., f:]
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        h = jnp.einsum("bsd,df->bsf", x, mp(p["w_in"])) + mp(p["b_in"])
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bsf,fd->bsd", h, mp(p["w_out"]))
    if cfg.act != "silu":
        out = out + mp(p["b_out"])
    return out
