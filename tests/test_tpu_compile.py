"""Compile the entity-matching kernels and round engine for a TPU v5e.

No chip is needed: the installed TPU compiler compiles for a described
``v5e:2x2`` topology and refuses exactly what the chip would refuse —
block shapes off the (8, 128) tiling, kernel bodies Mosaic cannot
lower, programs that do not fit.  Interpret mode (``test_kernels.py``)
checks the kernels' results; this file checks that the chip accepts
them at the shapes the main path uses:

* ``icm_sweep`` / ``mln_score`` at every bin's pair count
  (k = 8, 16, 24, 32 entities -> P = 28, 120, 276, 496) and at the
  staged widths of compacted sub-bins (128, 256 slots);
* ``minhash`` at a micro-batch and a bulk arrival count;
* ``ngram_sim`` at the canopy probe (1 seed x pool) and an ingest probe;
* the fused multi-round program and a full maximal-message round over
  a small real cover, on a mesh of one described chip, and grounding
  plus a full round of a 32-entity bin staged at 128 and 256 slots.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the tests run under
several pytest-xdist workers.  The persistent compilation cache is off
while these tests run, because entries compiled for a described chip
cannot be read back without one.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import pairs as pairlib
from repro.core import parallel as par
from repro.core import pipeline
from repro.core.mln import PAPER_LEARNED
from repro.kernels import common as kcommon

BIN_PAIRS = [pairlib.num_pairs(k) for k in (8, 16, 24, 32)]  # 28 .. 496
SUB_BIN_PAIRS = [128, 256]  # staged widths of compacted sub-bins


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_mesh(topo):
    return Mesh(np.array(topo.devices[:1]), ("data",))


def _compiled_text(lowered) -> str:
    return lowered.compile().as_text()


@pytest.mark.parametrize("P_", BIN_PAIRS + SUB_BIN_PAIRS)
def test_icm_sweep_batch_compiles(one_chip, P_):
    from repro.kernels.icm_sweep import kernel

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    B = 64
    text = _compiled_text(kernel.sweep_batch.lower(s((B, P_)), s((B, P_, P_)), s((B, P_))))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("P_", BIN_PAIRS + SUB_BIN_PAIRS)
def test_mln_score_sets_compiles(one_chip, P_):
    from repro.kernels.mln_score import kernel

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    B = 64
    text = _compiled_text(kernel.score_sets.lower(s((B, P_)), s((B, P_, P_)), s((B, 1, P_))))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("N", [50, 256])
def test_minhash_compiles(one_chip, N):
    from repro.kernels.minhash import kernel
    from repro.stream.index import LSHConfig

    cfg = LSHConfig()
    X = jax.ShapeDtypeStruct((N, cfg.shingle_dim), jnp.float32, sharding=one_chip)
    A = jax.ShapeDtypeStruct((cfg.num_hashes, cfg.shingle_dim), jnp.int32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(kernel.minhash.lower(X, A))


@pytest.mark.parametrize("M,N", [(1, 1024), (7, 50)])
def test_ngram_sim_compiles(one_chip, M, N):
    from repro.kernels.ngram_sim import kernel

    F = 128  # cover feature_dim

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    assert "tpu_custom_call" in _compiled_text(kernel.sim_above.lower(s((M, F)), s((N, F)), 0.0))


@pytest.fixture(scope="module")
def small_cover(hepth_small):
    """Per-bin argument shapes of the round programs over a real cover."""
    packed, _, _ = pipeline.prepare(hepth_small.entities, hepth_small.relations)
    universe = np.sort(np.asarray(sorted(packed.pair_levels), dtype=np.int64))
    staging = par._prepare_bins(packed, universe)
    ground = par._ground_bin_fn("mln", PAPER_LEARNED)
    out = {}
    for key, bt in staging.bins.items():
        g = jax.eval_shape(
            ground, bt.entity_ids, bt.entity_mask, bt.coauthor, bt.sim_level,
            bt.pair_mask, bt.slot_i, bt.slot_j,
        )
        out[key] = (g, bt)
    return out, len(universe)


def _bin_args(mesh, g, bt):
    shd = NamedSharding(mesh, P("data"))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=shd)

    B, Pn = bt.pair_mask.shape
    return [s(a.shape, a.dtype) for a in g] + [
        s((B, Pn), jnp.int32), s((B, Pn), jnp.bool_), s((B,), jnp.bool_)
    ]


def test_fused_engine_compiles(chip_mesh, small_cover, monkeypatch):
    """The fused multi-round program, with the Pallas sweep inside its
    ``while_loop``, compiles for the chip at the cover's bin shapes."""
    monkeypatch.setattr(kcommon, "pallas_mode", lambda: "compiled")
    bins, Np = small_cover
    ks = tuple(bins)
    spec = par.FusedSpec(
        kinds=("mln_greedy",) * len(ks),
        ks=tuple(k for k, _ in ks),
        batch=tuple(bins[k][1].pair_mask.shape[0] for k in ks),
        num_pairs=tuple(bins[k][1].pair_mask.shape[1] for k in ks),
        universe_size=Np,
    )
    args = []
    for k in ks:
        args += _bin_args(chip_mesh, *bins[k])
    rep = NamedSharding(chip_mesh, P())
    fn = par.build_fused_fn(spec, chip_mesh, ("data",))
    text = _compiled_text(fn.lower(
        *args,
        jax.ShapeDtypeStruct((Np,), jnp.bool_, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
    ))
    assert "tpu_custom_call" in text


def test_full_round_compiles(chip_mesh, small_cover, monkeypatch):
    """A maximal-message round of the largest bin (the entailment-matrix
    sweep runs ``icm_sweep`` over P seed rows) compiles for the chip."""
    monkeypatch.setattr(kcommon, "pallas_mode", lambda: "compiled")
    bins, Np = small_cover
    key = max(bins)
    g, bt = bins[key]
    B, Pn = bt.pair_mask.shape
    spec = par.BinRoundSpec(kind="mln", k=key[0], batch=B, num_pairs=Pn, universe_size=Np)
    fn = par.build_bin_round_fn(spec, chip_mesh, ("data",))
    rep = NamedSharding(chip_mesh, P())
    text = _compiled_text(fn.lower(
        *_bin_args(chip_mesh, g, bt), jax.ShapeDtypeStruct((Np,), jnp.bool_, sharding=rep)
    ))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("Pc", SUB_BIN_PAIRS)
def test_sub_bin_ground_and_round_compile(chip_mesh, monkeypatch, Pc):
    """A 32-entity bin staged at ``Pc`` compact slots: its grounding and
    its maximal-message round compile for the chip at that width."""
    monkeypatch.setattr(kcommon, "pallas_mode", lambda: "compiled")
    k, B, Np = 32, 64, 4096
    shd = NamedSharding(chip_mesh, P("data"))
    rep = NamedSharding(chip_mesh, P())

    def s(shape, dtype, sharding=shd):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    ground = jax.jit(par._ground_bin_fn("mln", PAPER_LEARNED))
    rows = (
        s((B, k), jnp.int64 if jax.config.jax_enable_x64 else jnp.int32),
        s((B, k), jnp.bool_), s((B, k, k), jnp.bool_), s((B, Pc), jnp.int8),
        s((B, Pc), jnp.bool_), s((B, Pc), jnp.int16), s((B, Pc), jnp.int16),
    )
    g = jax.eval_shape(ground, *rows)
    assert [a.shape for a in g] == [(B, Pc), (B, Pc), (B, Pc, Pc), (B, Pc)]
    ground.lower(*rows).compile()
    spec = par.BinRoundSpec(kind="mln", k=k, batch=B, num_pairs=Pc, universe_size=Np)
    fn = par.build_bin_round_fn(spec, chip_mesh, ("data",))
    text = _compiled_text(fn.lower(
        *[s(a.shape, a.dtype) for a in g],
        s((B, Pc), jnp.int32), s((B, Pc), jnp.bool_), s((B,), jnp.bool_),
        s((Np,), jnp.bool_, rep),
    ))
    assert "tpu_custom_call" in text
