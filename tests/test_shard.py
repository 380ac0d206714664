"""Cross-host equivalence battery for sharded serving (ISSUE 9).

Three layers, cheapest first:

1. **Unit**: the FNV bucket partition is deterministic, exhaustive and
   disjoint; a bucket-partitioned LSH index whose per-shard answers are
   united reproduces the unsharded index exactly; a single-process
   :class:`~repro.stream.shard.ShardContext` degrades to the identity.
2. **Single-process multi-device**: ``tests/shard_worker.py`` under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` shards bin
   rows over N forced CPU devices; the state digest must equal the
   in-process single-device baseline — for N in {1, 2, 4}, smp and mmp,
   on the hepth stream and the evidence lattice, and under a permuted
   ingest schedule.
3. **Multi-process mesh**: N worker processes join a ``jax.distributed``
   CPU mesh (gloo collectives); every replica's digest must equal the
   single-host baseline, and the replicas must agree among themselves
   (``AGREE 1`` — a cross-process digest all-gather).

Digest equality is the ROADMAP item-1 correctness bar: bit-for-bit the
single-host fixpoint, not approximately it.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.data.synthetic import SynthConfig, arrival_stream, make_dataset
from repro.launch.sharding import ShardSpec, bucket_shard
from repro.stream.digest import match_digest, state_digest
from repro.stream.index import LSHConfig, MinHashLSHIndex

WORKER = str(Path(__file__).parent / "shard_worker.py")
N_BATCHES = 3


def _run_worker(mode, scheme, *, devices=1, perm_seed=-1, env_extra=None,
                timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + env.get("XLA_FLAGS", "")
    )
    env.update(env_extra or {})
    env["PYTHONPATH"] = str(Path(__file__).parent.parent / "src")
    return subprocess.run(
        [sys.executable, WORKER, mode, scheme, str(N_BATCHES), str(perm_seed)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


def _parse(proc):
    assert proc.returncode == 0, (
        f"worker failed rc={proc.returncode}\n{proc.stdout}\n{proc.stderr}"
    )
    out = dict(
        line.split(None, 1) for line in proc.stdout.splitlines() if line
    )
    return out["DIGEST"], int(out.get("AGREE", "1"))


# -- layer 1: units ---------------------------------------------------------


def test_bucket_shard_partition_deterministic_exhaustive():
    rng = np.random.default_rng(0)
    keys = [
        (int(b), tuple(int(v) for v in rng.integers(0, 1 << 31, size=2)))
        for b in rng.integers(0, 64, size=512)
    ]
    for n in (1, 2, 4):
        owners = [bucket_shard(b, k, n) for b, k in keys]
        assert owners == [bucket_shard(b, k, n) for b, k in keys]
        assert all(0 <= o < n for o in owners)
        specs = [ShardSpec(n, i) for i in range(n)]
        for (b, k), o in zip(keys, owners):
            # exhaustive + disjoint: exactly one shard owns each bucket
            assert [s.owns(b, k) for s in specs].count(True) == 1
            assert specs[o].owns(b, k)
    # not trivially degenerate: at 4 shards all shards own something
    assert len({bucket_shard(b, k, 4) for b, k in keys}) == 4


def test_shard_spec_validation():
    with pytest.raises(ValueError):
        ShardSpec(n_shards=2, shard_id=2)
    with pytest.raises(ValueError):
        ShardSpec(n_shards=0, shard_id=0)
    with pytest.raises(ValueError):
        ShardSpec(n_shards=4, shard_id=-1)


def test_partitioned_index_union_equals_unsharded():
    """N bucket-partitioned index replicas, answers united, reproduce the
    unsharded index bit-for-bit (the in-process model of the cross-host
    probe merge)."""
    ds = make_dataset(SynthConfig.hepth(scale=0.02, seed=3))
    ids = list(range(len(ds.entities.names)))
    names = list(ds.entities.names)
    cfg = LSHConfig()
    base = MinHashLSHIndex(cfg)
    base.add(ids, names)
    for n in (2, 4):
        replicas = [
            MinHashLSHIndex(cfg, shard=ShardSpec(n, i)) for i in range(n)
        ]
        for rep in replicas:
            rep.add(ids, names)
        # the bucket maps are disjoint slices of the unsharded map
        for b in range(cfg.num_bands):
            seen: set = set()
            for rep in replicas:
                dup = seen & rep.buckets[b].keys()
                assert not dup
                seen |= rep.buckets[b].keys()
            assert seen == base.buckets[b].keys()
        probe = base.signatures(names[:17])
        expect = base.query(probe)
        union: set[int] = set()
        for rep in replicas:
            union |= rep.query(probe)
        assert union == expect


def test_single_process_context_is_identity():
    from repro.stream.shard import ShardContext, ShardCoordinator

    ctx = ShardContext.create()
    assert ctx.n_shards == 1 and ctx.shard_id == 0
    assert ctx.spec.owns(0, (1, 2))
    assert ctx.merger.union({3, 5}) == {3, 5}

    batches = arrival_stream(
        make_dataset(SynthConfig.hepth(scale=0.02, seed=3)), N_BATCHES
    )
    from repro.stream.service import ResolveService

    plain = ResolveService(scheme="smp", parallel=True)
    coord = ShardCoordinator(ctx, scheme="smp", parallel=True)
    for b in batches:
        plain.ingest(list(b.names), b.edges)
        coord.ingest(list(b.names), b.edges)
    assert coord.digest() == state_digest(plain)
    assert coord.digests_agree()


# -- layer 2: single-process multi-device mesh ------------------------------


@pytest.fixture(scope="module")
def hepth_baseline():
    """In-process single-device digests per (scheme, perm_seed)."""
    from repro.stream.service import ResolveService

    batches = arrival_stream(
        make_dataset(SynthConfig.hepth(scale=0.02, seed=3)), N_BATCHES
    )
    memo: dict = {}

    def get(scheme: str, perm_seed: int = -1) -> str:
        key = (scheme, perm_seed)
        if key not in memo:
            order = list(range(len(batches)))
            if perm_seed >= 0:
                order = [
                    int(i)
                    for i in np.random.default_rng(perm_seed).permutation(
                        len(batches)
                    )
                ]
            svc = ResolveService(scheme=scheme, parallel=True)
            for i in order:
                b = batches[i]
                svc.ingest(list(b.names), b.edges, ids=[int(x) for x in b.ids])
            memo[key] = state_digest(svc)
        return memo[key]

    return get


@pytest.fixture(scope="module")
def lattice_baseline():
    from repro.core.global_grounding import build_global_grounding
    from repro.core.mln import MLNMatcher
    from repro.core.parallel import run_parallel
    from repro.data.synthetic import make_lattice_cover

    memo: dict = {}

    def get(scheme: str) -> str:
        if scheme not in memo:
            packed, relations, weights = make_lattice_cover(depth=6, width=4)
            gg = (
                build_global_grounding(packed.pair_levels, relations, weights)
                if scheme == "mmp"
                else None
            )
            res = run_parallel(packed, MLNMatcher(weights), gg, scheme=scheme)
            memo[scheme] = match_digest(res.matches)
        return memo[scheme]

    return get


@pytest.mark.parametrize("scheme", ["smp", "mmp"])
@pytest.mark.parametrize("devices", [1, 2, 4])
def test_multidevice_hepth_digest_equals_single_host(
    hepth_baseline, devices, scheme
):
    digest, agree = _parse(_run_worker("hepth", scheme, devices=devices))
    assert agree == 1
    assert digest == hepth_baseline(scheme)


@pytest.mark.parametrize("scheme", ["smp", "mmp"])
@pytest.mark.parametrize("devices", [2, 4])
def test_multidevice_lattice_digest_equals_single_host(
    lattice_baseline, devices, scheme
):
    digest, _ = _parse(_run_worker("lattice", scheme, devices=devices))
    assert digest == lattice_baseline(scheme)


def test_multidevice_permuted_schedule_digest(hepth_baseline):
    digest, _ = _parse(_run_worker("hepth", "smp", devices=2, perm_seed=5))
    assert digest == hepth_baseline("smp", 5)
    # the digest is also schedule-invariant outright (ids preserved)
    assert digest == hepth_baseline("smp")


# -- layer 3: multi-process jax.distributed mesh ----------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_mesh(mode, scheme, n_procs, *, perm_seed=-1, timeout=420):
    """Spawn one worker per shard on a jax.distributed CPU mesh."""
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for i in range(n_procs):
        procs.append(
            subprocess.Popen(
                [sys.executable, WORKER, mode, scheme, str(N_BATCHES),
                 str(perm_seed)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                env={
                    **os.environ,
                    "JAX_PLATFORMS": "cpu",
                    "PYTHONPATH": str(Path(__file__).parent.parent / "src"),
                    "REPRO_SHARD_COORD": coord,
                    "REPRO_SHARD_N": str(n_procs),
                    "REPRO_SHARD_ID": str(i),
                },
            )
        )
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs


@pytest.mark.parametrize("scheme", ["smp", "mmp"])
@pytest.mark.parametrize("n_procs", [2, 4])
def test_mesh_hepth_digest_equals_single_host(hepth_baseline, n_procs, scheme):
    outs = _run_mesh("hepth", scheme, n_procs)
    expect = hepth_baseline(scheme)
    for rc, out, err in outs:
        assert rc == 0, f"shard failed rc={rc}\n{out}\n{err}"
        parsed = dict(ln.split(None, 1) for ln in out.splitlines() if ln)
        assert parsed["DIGEST"] == expect
        assert parsed["AGREE"] == "1"


@pytest.mark.parametrize("scheme", ["smp", "mmp"])
def test_mesh_lattice_digest_equals_single_host(lattice_baseline, scheme):
    outs = _run_mesh("lattice", scheme, 2)
    for rc, out, err in outs:
        assert rc == 0, f"shard failed rc={rc}\n{out}\n{err}"
        parsed = dict(ln.split(None, 1) for ln in out.splitlines() if ln)
        assert parsed["DIGEST"] == lattice_baseline(scheme)


def test_mesh_permuted_schedule_digest(hepth_baseline):
    outs = _run_mesh("hepth", "smp", 2, perm_seed=5)
    for rc, out, err in outs:
        assert rc == 0, f"shard failed rc={rc}\n{out}\n{err}"
        parsed = dict(ln.split(None, 1) for ln in out.splitlines() if ln)
        assert parsed["DIGEST"] == hepth_baseline("smp", 5)
        assert parsed["AGREE"] == "1"
