"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Every kernel is swept over shapes (aligned + deliberately unaligned,
forcing the padding path) and dtypes, asserting allclose against its
``ref.py`` oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.common import assert_allclose

jax.config.update("jax_enable_x64", False)

# 600 exceeds one 512-wide pair block, so the multi-block accumulation runs
SHAPES_PP = [(8, 8), (16, 16), (128, 128), (96, 96), (130, 130), (33, 33), (600, 600)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


# ---------------------------------------------------------------------------
# icm_sweep: delta = u + X @ C
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("P", [p for p, _ in SHAPES_PP])
@pytest.mark.parametrize("S", [1, 8, 96])
@pytest.mark.parametrize("dtype", DTYPES)
def test_icm_sweep_matrix(P, S, dtype):
    from repro.kernels.icm_sweep import kernel, ref

    rng = np.random.default_rng(P * 1000 + S)
    u = _rand(rng, (P,), jnp.float32)
    C = np.abs(rng.standard_normal((P, P))).astype(np.float32)
    C = jnp.asarray(np.triu(C, 1) + np.triu(C, 1).T)
    X = (rng.random((S, P)) < 0.3).astype(np.float32)
    X = jnp.asarray(X, dtype=dtype)
    got = kernel.sweep_matrix(u, C, X, interpret=True)
    want = ref.sweep_matrix(u, C, X)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("P", [8, 128, 57])
def test_icm_sweep_vector(P):
    from repro.kernels.icm_sweep import kernel, ref

    rng = np.random.default_rng(P)
    u = _rand(rng, (P,), jnp.float32)
    C = jnp.asarray(np.abs(rng.standard_normal((P, P))).astype(np.float32))
    x = jnp.asarray((rng.random((P,)) < 0.5).astype(np.float32))
    assert_allclose(
        kernel.sweep(u, C, x, interpret=True), ref.sweep(u, C, x), rtol=1e-5
    )


@pytest.mark.parametrize("B,P", [(1, 8), (3, 28), (4, 96)])
def test_icm_sweep_batch(B, P):
    """Batched bin sweep: kernel and oracle agree with vmapped sweep."""
    from repro.kernels.icm_sweep import kernel, ref

    rng = np.random.default_rng(B * 100 + P)
    u = _rand(rng, (B, P), jnp.float32)
    C = np.abs(rng.standard_normal((B, P, P))).astype(np.float32)
    C = jnp.asarray(np.triu(C, 1) + np.triu(C, 1).transpose(0, 2, 1))
    X = jnp.asarray((rng.random((B, P)) < 0.4).astype(np.float32))
    want = jax.vmap(ref.sweep)(u, C, X)
    assert_allclose(ref.sweep_batch(u, C, X), want, rtol=1e-6)
    assert_allclose(kernel.sweep_batch(u, C, X, interpret=True), want, rtol=1e-5)


# ---------------------------------------------------------------------------
# mln_score: f(X_s) = u . x_s + 1/2 x_s C x_s  batched over candidate sets
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,P", [(1, 1, 8), (2, 4, 16), (3, 5, 96), (2, 2, 130), (2, 130, 600)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_mln_score_sets(B, S, P, dtype):
    from repro.kernels.mln_score import kernel, ref

    rng = np.random.default_rng(B * 100 + S * 10 + P)
    u = jnp.asarray(rng.standard_normal((B, P)).astype(np.float32))
    C = np.abs(rng.standard_normal((B, P, P))).astype(np.float32)
    C = jnp.asarray(np.triu(C, 1) + np.transpose(np.triu(C, 1), (0, 2, 1)))
    X = jnp.asarray((rng.random((B, S, P)) < 0.4).astype(dtype))
    got = kernel.score_sets(u, C, X, interpret=True)
    want = ref.score_sets(u, C, X)
    assert_allclose(got, want, rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------------------------
# ngram_sim: thresholded cosine similarity A @ B^T
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,N,F", [(8, 8, 32), (128, 64, 128), (100, 70, 96)])
@pytest.mark.parametrize("threshold", [0.0, 0.7])
def test_ngram_sim(M, N, F, threshold):
    from repro.kernels.ngram_sim import kernel, ref

    rng = np.random.default_rng(M + N + F)
    A = rng.standard_normal((M, F)).astype(np.float32)
    B = rng.standard_normal((N, F)).astype(np.float32)
    A /= np.linalg.norm(A, axis=1, keepdims=True)
    B /= np.linalg.norm(B, axis=1, keepdims=True)
    got = kernel.sim_above(jnp.asarray(A), jnp.asarray(B), threshold, interpret=True)
    want = ref.sim_above(jnp.asarray(A), jnp.asarray(B), threshold)
    assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# minhash: masked-min signatures for streaming LSH blocking
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,D,H", [(8, 64, 16), (128, 512, 128), (33, 96, 50), (1, 512, 128)])
def test_minhash(N, D, H):
    from repro.kernels.minhash import kernel, ops, ref

    rng = np.random.default_rng(N * 7 + D + H)
    X = jnp.asarray((rng.random((N, D)) < 0.1).astype(np.float32))
    A = jnp.asarray(ops.hash_table(H, D, seed=3))
    got = kernel.minhash(X, A, interpret=True)
    want = ref.minhash(X, A)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_minhash_empty_rows():
    from repro.kernels.minhash import ops, ref

    A = jnp.asarray(ops.hash_table(32, 64, seed=0))
    sig = ref.minhash(jnp.zeros((3, 64)), A)
    assert np.all(np.asarray(sig) == ref.EMPTY)


def test_minhash_jaccard_estimate():
    """Signature agreement rate estimates Jaccard similarity."""
    from repro.kernels.minhash import ops, ref

    rng = np.random.default_rng(0)
    D, H = 512, 256
    a = rng.random(D) < 0.2
    b = a.copy()
    flip = rng.choice(D, size=40, replace=False)
    b[flip] = ~b[flip]
    jac = (a & b).sum() / (a | b).sum()
    X = jnp.asarray(np.stack([a, b]).astype(np.float32))
    A = jnp.asarray(ops.hash_table(H, D, seed=1))
    sig = np.asarray(ref.minhash(X, A))
    est = (sig[0] == sig[1]).mean()
    assert abs(est - jac) < 0.12, (est, jac)


# ---------------------------------------------------------------------------
# flash_attn: online-softmax attention vs the naive oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,H,hkv,hd", [(128, 4, 2, 32), (256, 2, 2, 64), (192, 4, 1, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attn(S, H, hkv, hd, causal):
    from repro.kernels.flash_attn import kernel, ref

    rng = np.random.default_rng(S + H)
    B = 2
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, hkv, hd)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, hkv, hd)).astype(np.float32))
    scale = 1.0 / np.sqrt(hd)
    got = kernel.flash_attention(q, k, v, scale, causal=causal, interpret=True)
    want = ref.attention(q, k, v, scale, causal=causal)
    assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_flash_attn_matches_chunked_xla():
    """The Pallas kernel, the XLA chunked path and the naive path agree."""
    from repro.kernels.flash_attn import kernel
    from repro.models import layers

    rng = np.random.default_rng(0)
    B, S, H, hkv, hd = 2, 256, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, hkv, hd)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, hkv, hd)).astype(np.float32))
    scale = 1.0 / np.sqrt(hd)
    xla = layers.chunked_attention(q, k, v, scale, causal=True, q_block=64)
    pallas = kernel.flash_attention(q, k, v, scale, causal=True, interpret=True)
    assert_allclose(pallas.reshape(xla.shape), xla, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# routing and compile-cache placement (no chip needed)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("env", ["off", "interpret"])
def test_pallas_mode_refuses_fallback_on_tpu(monkeypatch, env):
    """On a TPU backend the kernels run compiled; asking for the
    references or the interpreter there raises instead of quietly
    running something else."""
    from repro.kernels import common

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    assert common.pallas_mode() == "compiled"
    monkeypatch.setenv("REPRO_PALLAS", env)
    with pytest.raises(RuntimeError, match="REPRO_PALLAS"):
        common.pallas_mode()


def test_pallas_mode_off_tpu(monkeypatch):
    from repro.kernels import common

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    assert common.pallas_mode() == "off"
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    assert common.pallas_mode() == "interpret"
    monkeypatch.setenv("REPRO_PALLAS", "compiled")
    with pytest.raises(ValueError, match="REPRO_PALLAS"):
        common.pallas_mode()


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """The compile cache follows JAX_COMPILATION_CACHE_DIR (which JAX
    reads itself, so nothing is set) or else sits at the fixed
    ``<checkout>/.jax_cache``."""
    import pathlib

    from repro.kernels import common

    checkout = pathlib.Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert common.use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(checkout / ".jax_cache")
            assert common.use_compile_cache() == want
            assert common.use_compile_cache() == want  # same path every call
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
