"""Round-parallel SPMD message passing == sequential drivers (Thm 2/4
consistency), plus an 8-shard subprocess run proving the multi-device
path (this process holds exactly one CPU device).

The engine stages each neighborhood on its candidate pair slots
(``_prepare_bins``); the staging tests check that layout, and that it
resolves exactly as the full upper-triangle layout does for every
device-capable matcher kind and scheme, on one device and sharded.

The fused device-resident engine is checked two ways per scheme:
bit-for-bit fixpoint equality against the sequential drivers, the one
reference, and the device-residency accounting itself — the grounding
is computed exactly once per bin per cover (ground-call counter) and
the host dispatch count is O(bins + quiescence points), not
O(bins x rounds).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fig1, pipeline
from repro.core import pairs as pairlib
from repro.core import parallel as par
from repro.core.driver import MessagePool, run_mmp, run_nomp, run_smp
from repro.core.global_grounding import build_global_grounding
from repro.core.mln import NEG, MLNMatcher, PAPER_LEARNED, PEDAGOGICAL, _peel_and_promote
from repro.core.parallel import GroundingCache, run_parallel
from repro.core.rules import RulesMatcher
from repro.obs import get_registry


@pytest.fixture(scope="module")
def hepth_state(hepth_small):
    packed, gg, _ = pipeline.prepare(hepth_small.entities, hepth_small.relations)
    return packed, gg


def _staged_bins(packed) -> int:
    """Number of bins the round engine stages for a cover."""
    return len(par._prepare_bins(packed, par._pair_universe(packed)).bins)


def test_parallel_smp_equals_sequential_fig1(fig1_packed, mln_pedagogical):
    seq = run_smp(fig1_packed, mln_pedagogical)
    par = run_parallel(fig1_packed, mln_pedagogical, scheme="smp")
    assert seq.matches.as_set() == par.matches.as_set()


def test_parallel_mmp_equals_sequential_fig1(fig1_packed, mln_pedagogical):
    gg = build_global_grounding(
        fig1_packed.pair_levels, fig1.relations(), PEDAGOGICAL
    )
    seq = run_mmp(fig1_packed, mln_pedagogical, gg)
    par = run_parallel(fig1_packed, mln_pedagogical, gg, scheme="mmp")
    assert seq.matches.as_set() == par.matches.as_set()
    assert fig1.names_of(par.matches) == fig1.EXPECTED_MMP


@pytest.mark.parametrize(
    "scheme,capacity",
    [("nomp", None), ("smp", None), ("mmp", None), ("mmp", 1)],
)
def test_parallel_schemes_equal_sequential(hepth_state, mln_paper, scheme,
                                           capacity):
    """All three schemes: the device engine and the sequential driver
    agree bit-for-bit on the fixpoint.  ``capacity=1`` bounds the
    grounding cache below the bin count (spill mode), where every round
    is a full round: no fused greedy segment runs."""
    packed, gg = hepth_state
    if scheme == "nomp":
        seq = run_nomp(packed, mln_paper)
    elif scheme == "smp":
        seq = run_smp(packed, mln_paper)
    else:
        seq = run_mmp(packed, mln_paper, gg)
    par = run_parallel(
        packed, mln_paper, gg, scheme=scheme,
        gcache=GroundingCache(capacity=capacity),
    )
    assert par.matches.as_set() == seq.matches.as_set()
    if capacity is not None:
        assert capacity < _staged_bins(packed)
        assert par.full_rounds == par.rounds > 1


def test_parallel_rules(hepth_state):
    packed, _ = hepth_state
    m = RulesMatcher()
    seq = run_smp(packed, m)
    par = run_parallel(packed, m, scheme="smp")
    assert seq.matches.as_set() == par.matches.as_set()


def test_grounding_once_per_bin_per_cover(hepth_state, mln_paper):
    """The multi-round run grounds each bin exactly once; a second run
    over the same cover re-grounds nothing (device arrays are reused)."""
    packed, gg = hepth_state
    gcache = GroundingCache()
    res = run_parallel(packed, mln_paper, gg, scheme="mmp", gcache=gcache)
    assert res.rounds >= 1
    n_bins = _staged_bins(packed)
    assert gcache.ground_calls == n_bins
    rows_after = gcache.rows_ground
    assert rows_after > 0
    hits_before = gcache.bin_hits

    res2 = run_parallel(packed, mln_paper, gg, scheme="mmp", gcache=gcache)
    assert res2.matches.as_set() == res.matches.as_set()
    assert gcache.rows_ground == rows_after  # zero rows re-ground
    assert gcache.bin_hits == hits_before + n_bins


def _staged_rows(packed) -> int:
    """Rows over every bin the round engine stages for a cover."""
    st = par._prepare_bins(packed, par._pair_universe(packed))
    return sum(bt.entity_mask.shape[0] for bt in st.bins.values())


def _em_counter(name: str) -> int:
    return get_registry().snapshot()["counters"].get(name, 0)


def _with_level_changed(packed):
    """A copy of ``packed`` whose first k = 8 row has one candidate
    pair's similarity level changed: one staged row's content differs."""
    import dataclasses

    nb = packed.bins[8]
    lev = nb.sim_level.copy()
    slot = int(np.nonzero(nb.pair_mask[0])[0][0])
    lev[0, slot] = lev[0, slot] % 3 + 1
    bins = dict(packed.bins)
    bins[8] = dataclasses.replace(nb, sim_level=lev)
    return dataclasses.replace(packed, bins=bins)


@pytest.mark.parametrize("state", ["hepth_state", "split_state"])
def test_batch_run_hashes_no_rows(request, state, mln_paper):
    """A batch resolution with its own cache takes no row signature:
    every bin is ground once and its entry keeps the staged bin, so
    ``rows_hashed`` is 0 in the result and in ``em.rows_hashed``.  The
    fixpoint is the sequential driver's, and matches, history and
    message counts are those of a run whose cache compares every bin
    by its hashed rows."""
    packed, gg = request.getfixturevalue(state)[-2:]
    before = _em_counter("em.rows_hashed")
    res = run_parallel(packed, mln_paper, gg, scheme="mmp")
    assert res.rows_hashed == 0
    assert _em_counter("em.rows_hashed") == before

    warm = GroundingCache()
    run_parallel(packed, mln_paper, gg, scheme="mmp", gcache=warm)
    hashed = run_parallel(packed, mln_paper, gg, scheme="mmp", gcache=warm)
    assert hashed.rows_hashed == 2 * _staged_rows(packed)
    assert res.matches.as_set() == run_mmp(packed, mln_paper, gg).matches.as_set()
    assert res.matches.as_set() == hashed.matches.as_set()
    assert res.history == hashed.history
    assert (res.messages_emitted, res.messages_promoted) == (
        hashed.messages_emitted, hashed.messages_promoted
    )


@pytest.mark.parametrize("changed", [False, True])
def test_persistent_cache_hashes_only_to_compare(hepth_state, mln_paper,
                                                 changed):
    """A persistent cache across two batch calls sees new staged bin
    objects on the second: it hashes the kept rows and the new rows
    once each, serves unchanged bins whole and splices exactly the one
    row whose content changed; the spliced cache resolves as a fresh
    one does."""
    packed, gg = hepth_state
    gcache = GroundingCache()
    first = run_parallel(packed, mln_paper, gg, scheme="mmp", gcache=gcache)
    assert first.rows_hashed == 0 and gcache.rows_hashed == 0
    n_bins, n_rows = _staged_bins(packed), _staged_rows(packed)
    rows0, hits0 = gcache.rows_ground, gcache.bin_hits
    splices0, cold0 = gcache.splice_calls, gcache.cold_regrounds

    second = _with_level_changed(packed) if changed else packed
    res = run_parallel(second, mln_paper, gg, scheme="mmp", gcache=gcache)
    assert res.rows_hashed == gcache.rows_hashed == 2 * n_rows
    assert gcache.rows_ground == rows0 + int(changed)
    assert gcache.bin_hits == hits0 + n_bins - int(changed)
    assert gcache.splice_calls == splices0 + int(changed)
    assert gcache.cold_regrounds == cold0
    fresh = run_parallel(second, mln_paper, gg, scheme="mmp")
    assert res.matches.as_set() == fresh.matches.as_set()


@pytest.mark.parametrize("cap", [1, 2])
def test_bounded_cache_refetch_hashes_no_rows(hepth_state, mln_paper, cap):
    """Spill mode re-fetches a bin at every dispatch within one run: the
    same staged bin object is a hit outright, or a cold re-ground after
    eviction, with no row hashed, and the fixpoint is unchanged."""
    packed, gg = hepth_state
    ref = run_parallel(packed, mln_paper, gg, scheme="mmp")
    gcache = GroundingCache(capacity=cap)
    res = run_parallel(packed, mln_paper, gg, scheme="mmp", gcache=gcache)
    assert res.matches.as_set() == ref.matches.as_set()
    assert res.rows_hashed == 0 and gcache.rows_hashed == 0
    assert res.cold_regrounds > 0

    st = par._prepare_bins(packed, par._pair_universe(packed))
    key, bt = next(iter(st.bins.items()))
    mkey = par._matcher_cache_key(mln_paper)
    own = GroundingCache(capacity=cap)
    arrays = own.get(mkey, key, bt)
    assert own.get(mkey, key, bt) is arrays
    assert (own.bin_hits, own.ground_calls, own.rows_hashed) == (1, 1, 0)


def test_rollback_after_lazy_hash_restores_cache(hepth_state, mln_paper):
    """``journal_rollback`` snapshots entries shallowly: after a call
    that replaced kept bins by their digests, a rollback restores the
    entries (the same objects) and every counter exactly, and the next
    call hashes again as the rolled-back one did."""
    from repro.core import txn

    packed, gg = hepth_state
    gcache = GroundingCache()
    run_parallel(packed, mln_paper, gg, scheme="mmp", gcache=gcache)
    entries = dict(gcache._bins)
    assert all(isinstance(e[0], par._BinTensors) for e in entries.values())
    counters = {c: getattr(gcache, c) for c in GroundingCache._TXN_COUNTERS}

    t = txn.Transaction()
    gcache.journal_rollback(t)
    res = run_parallel(packed, mln_paper, gg, scheme="mmp", gcache=gcache)
    assert res.rows_hashed > 0
    assert all(isinstance(e[0], tuple) for e in gcache._bins.values())
    t.rollback()
    assert gcache._bins.keys() == entries.keys()
    assert all(gcache._bins[k] is e for k, e in entries.items())
    assert {c: getattr(gcache, c) for c in GroundingCache._TXN_COUNTERS} == counters
    again = run_parallel(packed, mln_paper, gg, scheme="mmp", gcache=gcache)
    assert again.rows_hashed == res.rows_hashed
    assert again.matches.as_set() == res.matches.as_set()


def test_stream_ingest_with_row_keys_hashes_no_rows():
    """The served path's covers carry row keys: ingests compare bins by
    them (hits and splices) and hash no row."""
    from repro.stream import ResolveService, ServiceConfig

    before = _em_counter("em.rows_hashed")
    svc = ResolveService(ServiceConfig(scheme="smp", parallel=True))
    covers = []
    advance = svc.engine.advance
    svc.engine.advance = lambda packed, *a, **kw: (
        covers.append(packed) or advance(packed, *a, **kw)
    )
    svc.ingest([f"alessandro brunelleschi{chr(97 + i)}" for i in range(10)])
    g = svc.engine.gcache
    compared = g.bin_hits + g.splice_calls
    svc.ingest([f"evangelina montgomery{chr(97 + i)}" for i in range(5)])
    assert len(covers) == 2 and all(c.row_keys is not None for c in covers)
    assert g.bin_hits + g.splice_calls > compared
    assert g.rows_hashed == 0
    assert _em_counter("em.rows_hashed") == before


def test_fused_dispatch_counts(hepth_state, mln_paper):
    """Dispatch accounting of the device-resident engine: a cheap
    (greedy/rules) matcher's whole multi-round closure is ONE host
    dispatch; the collective MLN pays O(bins) per quiescence point plus
    one dispatch per greedy segment — O(bins + quiescence points), not
    O(bins x rounds) — and reaches the sequential driver's fixpoint."""
    packed, gg = hepth_state
    n_bins = _staged_bins(packed)

    rules = run_parallel(packed, RulesMatcher(), scheme="smp")
    assert rules.dispatches == 1

    # collective SMP/MMP: full rounds only at the start and at greedy-
    # quiescence points; every re-activation round is inside a fused
    # greedy segment (one dispatch, however many rounds it runs) — the
    # dispatch count is O(bins x quiescence points + segments), not
    # O(bins x rounds).
    seq = {"smp": run_smp(packed, mln_paper), "mmp": run_mmp(packed, mln_paper, gg)}
    for scheme in ("smp", "mmp"):
        res = run_parallel(packed, mln_paper, gg, scheme=scheme)
        assert 0 < res.full_rounds < res.rounds
        segments = res.rounds - res.full_rounds  # each is >= 1 round
        assert res.dispatches <= n_bins * res.full_rounds + segments
        assert res.matches.as_set() == seq[scheme].matches.as_set()


def test_lru_capacity_bounds_and_fixpoint(hepth_state, mln_paper):
    """LRU-bounded GroundingCache (serving HBM budget): under capacities
    {1, 2, all} the fixpoint is bit-for-bit the unbounded cache's, the
    array-resident bin count never exceeds the capacity, and with
    capacity < bins the eviction and cold-reground paths actually fire
    (cold bins are re-ground on demand — grounding is pure, so the
    recomputed tensors are the evicted ones)."""
    packed, gg = hepth_state
    n_bins = _staged_bins(packed)
    assert n_bins > 2  # capacities {1, 2} below actually evict
    ref = {
        s: run_parallel(packed, mln_paper, gg, scheme=s).matches.as_set()
        for s in ("smp", "mmp")
    }
    for cap in (1, 2, n_bins):
        for scheme in ("smp", "mmp"):
            gcache = GroundingCache(capacity=cap)
            res = run_parallel(
                packed, mln_paper, gg, scheme=scheme, gcache=gcache
            )
            assert res.matches.as_set() == ref[scheme], (cap, scheme)
            assert gcache.peak_resident_bins <= cap
            assert res.peak_resident_bins <= cap
            if cap < n_bins:
                assert res.cache_evictions > 0, (cap, scheme)
                assert res.cold_regrounds > 0, (cap, scheme)
            else:
                assert res.cache_evictions == 0

    # spill mode must also cover the non-collective single-fused-dispatch
    # paths (rules/greedy closure, nomp): with the bound tighter than the
    # bin count they reroute through per-bin full rounds — same fixpoint,
    # residency genuinely capped (no all-bins fused materialization)
    for scheme in ("nomp", "smp"):
        ref_rules = run_parallel(packed, RulesMatcher(), scheme=scheme)
        gcache = GroundingCache(capacity=1)
        res = run_parallel(
            packed, RulesMatcher(), scheme=scheme, gcache=gcache
        )
        assert res.matches.as_set() == ref_rules.matches.as_set(), scheme
        assert gcache.peak_resident_bins <= 1
        assert res.dispatches > ref_rules.dispatches  # per-bin, not fused


def test_lru_hbm_budget_bounds_and_fixpoint(hepth_state, mln_paper):
    """The byte-budget knob: a budget below one bin's tensors degrades
    gracefully to exactly one resident bin (never zero — the hot bin
    must stay cached for the current dispatch), same fixpoint."""
    packed, gg = hepth_state
    ref = run_parallel(packed, mln_paper, gg, scheme="mmp").matches.as_set()
    gcache = GroundingCache(hbm_budget_bytes=1)
    res = run_parallel(packed, mln_paper, gg, scheme="mmp", gcache=gcache)
    assert res.matches.as_set() == ref
    assert gcache.peak_resident_bins == 1
    assert gcache.evictions > 0


def test_lru_lattice_fixpoint(mln_paper):
    """The multi-round lattice instance under bounded caches: depth
    rounds of fused greedy segments with eviction between dispatches
    still reach the unbounded fixpoint for both schemes."""
    from repro.data.synthetic import make_lattice_cover

    packed, rel, weights = make_lattice_cover(6, 2)
    gg = build_global_grounding(packed.pair_levels, rel, weights)
    m = MLNMatcher(weights)
    ref = {
        s: run_parallel(packed, m, gg, scheme=s).matches.as_set()
        for s in ("smp", "mmp")
    }
    n_bins = _staged_bins(packed)
    for cap in (1, 2, n_bins):
        for scheme in ("smp", "mmp"):
            gcache = GroundingCache(capacity=cap)
            res = run_parallel(packed, m, gg, scheme=scheme, gcache=gcache)
            assert res.matches.as_set() == ref[scheme], (cap, scheme)
            assert gcache.peak_resident_bins <= cap


def test_device_promotion_no_host_scans(hepth_state, mln_paper,
                                        fig1_packed, mln_pedagogical):
    """Step-7 promotion runs on device in the fused engine: zero host
    coupling-COO walks, same fixpoint as the host-promoting sequential
    driver (which counts its host scans)."""
    packed, gg = hepth_state
    res = run_parallel(packed, mln_paper, gg, scheme="mmp")
    assert res.promote_host_scans == 0
    seq = run_mmp(packed, mln_paper, gg)
    assert seq.promote_host_scans > 0
    assert res.matches.as_set() == seq.matches.as_set()

    # fig1 is the paper's promotion example: messages must actually be
    # promoted through the device path, not just trivially skipped
    gg1 = build_global_grounding(
        fig1_packed.pair_levels, fig1.relations(), PEDAGOGICAL
    )
    res1 = run_parallel(fig1_packed, mln_pedagogical, gg1, scheme="mmp")
    assert res1.promote_host_scans == 0
    assert res1.messages_promoted > 0
    assert fig1.names_of(res1.matches) == fig1.EXPECTED_MMP


@pytest.mark.slow
def test_parallel_8_shards_subprocess():
    """The paper's §6.3 grid experiment in miniature: 8 SPMD shards
    reach the same fixpoint as 1 (device count is locked at jax init,
    hence the subprocess)."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np
        from repro.core import fig1, pipeline
        from repro.core.mln import MLNMatcher, PAPER_LEARNED
        from repro.core.parallel import run_parallel
        from repro.data.synthetic import SynthConfig, make_dataset

        ds = make_dataset(SynthConfig.hepth(scale=0.02, seed=3))
        packed, gg, _ = pipeline.prepare(ds.entities, ds.relations)
        m = MLNMatcher(PAPER_LEARNED)
        par = run_parallel(packed, m, gg, scheme="mmp")
        print(json.dumps(sorted(int(g) for g in par.matches.gids)))
        """
    )
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    got = set(json.loads(out.stdout.strip().splitlines()[-1]))

    from repro.data.synthetic import SynthConfig, make_dataset

    ds = make_dataset(SynthConfig.hepth(scale=0.02, seed=3))
    packed, gg, _ = pipeline.prepare(ds.entities, ds.relations)
    seq = run_mmp(packed, MLNMatcher(PAPER_LEARNED), gg)
    assert got == seq.matches.as_set()


# ---------------------------------------------------------------------------
# Staging on candidate pair slots
# ---------------------------------------------------------------------------

# device-capable kinds and the schemes each runs on the parallel engine
# (parallel MMP is wired to the MLN only)
SPLIT_CASES = [("mln", "nomp"), ("mln", "smp"), ("mln", "mmp"),
               ("rules", "smp"), ("embed", "smp")]


def _split_cover():
    """A small HEPTH-like cover whose 32-entity bin holds neighborhoods
    on both sides of one slot step (7 with at most 128 candidate pairs,
    14 with more), so staging splits it into two sub-bins."""
    from repro.data.synthetic import SynthConfig, make_dataset

    ds = make_dataset(SynthConfig(n_authors=20, n_papers=100, seed=2, chain_motifs=2))
    packed, gg, _ = pipeline.prepare(ds.entities, ds.relations)
    return ds, packed, gg


def _resolve_split(ds, packed, gg, kind, scheme, full_layout):
    """Sorted matches and message groups of one ``run_parallel``, on the
    staged layout or, with ``full_layout``, on the whole upper triangle
    of every bin (a slot step no bin exceeds)."""
    from repro.core.matchers.embedding import EmbeddingMatcher

    if kind == "mln":
        m = MLNMatcher(PAPER_LEARNED)
    elif kind == "rules":
        m = RulesMatcher()
    else:
        m = EmbeddingMatcher(encoder="ngram", tau=0.8)
        m.bind_names(list(ds.entities.names))
    step = par._SLOT_STEP
    if full_layout:
        par._SLOT_STEP = 1 << 30
    try:
        pool = MessagePool()
        res = run_parallel(packed, m, gg, scheme=scheme, pool=pool)
    finally:
        par._SLOT_STEP = step
    return (sorted(int(g) for g in res.matches.gids),
            sorted(sorted(int(g) for g in grp) for grp in pool.groups()))


def _split_main() -> None:
    """Subprocess entry: every case on both layouts, as one JSON line."""
    import jax

    assert jax.device_count() == 4
    ds, packed, gg = _split_cover()
    out = {
        f"{kind}/{scheme}/{layout}": _resolve_split(
            ds, packed, gg, kind, scheme, layout == "full"
        )
        for kind, scheme in SPLIT_CASES
        for layout in ("compact", "full")
    }
    print(json.dumps(out))


@pytest.fixture(scope="module")
def split_state():
    return _split_cover()


@pytest.fixture(scope="module")
def split_sharded():
    """The split cover resolved on 4 forced CPU devices (the engine pads
    every staged bin's batch axis to a multiple of 4)."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.path.join(os.path.dirname(here), "src"),
    )
    code = (f"import sys; sys.path.insert(0, {here!r}); "
            "import test_parallel_rounds as t; t._split_main()")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("pad_mult", [1, 4])
@pytest.mark.parametrize("kind,scheme", SPLIT_CASES)
def test_compact_staging_resolves_as_full_layout(split_state, request, kind,
                                                 scheme, pad_mult):
    """Staged on candidate slots, every kind and scheme reaches the same
    matches and the same message groups as on the full upper triangle,
    on one device and sharded over four (``pad_mult`` 4)."""
    ds, packed, gg = split_state
    compact = _resolve_split(ds, packed, gg, kind, scheme, False)
    if pad_mult == 1:
        full = _resolve_split(ds, packed, gg, kind, scheme, True)
    else:
        sharded = request.getfixturevalue("split_sharded")
        full = sharded[f"{kind}/{scheme}/full"]
        assert sharded[f"{kind}/{scheme}/compact"] == list(compact)
    assert list(compact) == list(full)
    assert compact[0]  # the matcher matched something
    if scheme == "mmp":
        assert compact[1]  # and emitted maximal messages


def test_staging_keeps_slot_order_with_inert_padding(split_state):
    """Each staged row holds its neighborhood's candidate slots in their
    upper-triangle order, then inert padding; bins of at most one slot
    step stay exactly as packed; padding rows are inert too."""
    _, packed, _ = split_state
    universe = par._pair_universe(packed)
    Np = len(universe)
    st = par._prepare_bins(packed, universe, pad_mult=4)
    seen = 0
    for i, ((k, w), bt) in enumerate(st.bins.items()):
        ii, jj = pairlib.triu_indices(k)
        nb = packed.bins[k]
        assert bt.pair_mask.shape[0] % 4 == 0
        for r, n in enumerate(bt.rows.tolist()):
            if n < 0:  # batch padding
                assert not bt.pair_mask[r].any()
                assert (bt.uidx[r] == Np).all() and (bt.pair_gid[r] == -1).all()
                continue
            seen += 1
            assert (st.bin_of[n], st.row_of[n]) == (i, r)
            row = int(packed.neighborhood_row[n])
            assert int(packed.neighborhood_bin[n]) == k
            np.testing.assert_array_equal(bt.entity_ids[r], nb.entity_ids[row])
            np.testing.assert_array_equal(bt.coauthor[r], nb.coauthor[row])
            if len(ii) <= par._SLOT_STEP:  # untouched
                np.testing.assert_array_equal(bt.pair_mask[r], nb.pair_mask[row])
                np.testing.assert_array_equal(bt.pair_gid[r], nb.pair_gid[row])
                np.testing.assert_array_equal(bt.sim_level[r], nb.sim_level[row])
                np.testing.assert_array_equal(bt.slot_i[r], ii)
                np.testing.assert_array_equal(bt.slot_j[r], jj)
                continue
            cand = np.nonzero(nb.pair_mask[row])[0]
            c = len(cand)
            assert bt.pair_mask[r, :c].all() and not bt.pair_mask[r, c:].any()
            np.testing.assert_array_equal(bt.pair_gid[r, :c], nb.pair_gid[row, cand])
            np.testing.assert_array_equal(bt.sim_level[r, :c], nb.sim_level[row, cand])
            np.testing.assert_array_equal(bt.slot_i[r, :c], ii[cand])
            np.testing.assert_array_equal(bt.slot_j[r, :c], jj[cand])
            np.testing.assert_array_equal(
                universe[bt.uidx[r, :c]], nb.pair_gid[row, cand]
            )
            assert (bt.uidx[r, c:] == Np).all() and (bt.pair_gid[r, c:] == -1).all()
            assert (bt.sim_level[r, c:] == 0).all()
    assert seen == packed.num_neighborhoods


def test_staging_widths_are_the_smallest_step_multiple(split_state):
    """A row of a bin wider than one slot step lands in the sub-bin of
    the smallest multiple of the step that holds its candidates (capped
    at k(k-1)/2); a bin of at most one step keeps its width."""
    _, packed, _ = split_state
    st = par._prepare_bins(packed, par._pair_universe(packed))
    step = par._SLOT_STEP
    staged_as = {
        n: key for key, bt in st.bins.items() for n in bt.rows.tolist() if n >= 0
    }
    for n in range(packed.num_neighborhoods):
        k = int(packed.neighborhood_bin[n])
        P = pairlib.num_pairs(k)
        c = int(packed.bins[k].pair_mask[int(packed.neighborhood_row[n])].sum())
        want = P if P <= step else min(max(-(-c // step), 1) * step, P)
        assert staged_as[n] == (k, want), (n, c)
    assert {(32, 128), (32, 256), (8, 28), (16, 120)} <= set(st.bins)


def test_peel_bound_follows_k_not_the_staged_width():
    """The peel's iteration bound comes from k(k-1)/2, not from the
    staged width: a component that needs 25 peels (more than the 18 a
    128-slot width would allow, fewer than the 34 of k = 32) gives the
    same ``x`` on the full and the compact layout."""
    k, Pc = 32, 128
    P = pairlib.num_pairs(k)
    # a 5-pair clique (weight 2) with a 25-pair tail chain (weight 0.6)
    # hanging off it: every peel drops the tail's loose end, so the
    # whole tail takes 25 peels; the clique alone is then promoted
    tail, core = 25, 5
    n = tail + core
    Cm = np.zeros((n, n), np.float32)
    for a in range(tail):  # tail t0 - t1 - ... - t24 - core[0]
        Cm[a, a + 1] = Cm[a + 1, a] = 0.6
    for a in range(tail, n):
        for b in range(a + 1, n):
            Cm[a, b] = Cm[b, a] = 2.0
    slots = np.sort(np.random.default_rng(0).choice(P, n, replace=False))

    def layout(width, at):
        u = np.full(width, NEG, np.float32)
        u[at] = -1.0
        C = np.zeros((width, width), np.float32)
        C[np.ix_(at, at)] = Cm
        valid = np.zeros(width, bool)
        valid[at] = True
        lab = np.full(width, width, np.int32)
        lab[at] = at[0]
        none = np.zeros(width, bool)
        return [jnp.asarray(a) for a in (u, C, none, lab, valid, none)]

    x_full = np.asarray(_peel_and_promote(*layout(P, slots)))
    x_comp = np.asarray(_peel_and_promote(*layout(Pc, np.arange(n)), num_pairs=P))
    x_short = np.asarray(_peel_and_promote(*layout(Pc, np.arange(n))))
    want = np.r_[np.zeros(tail, bool), np.ones(core, bool)]
    np.testing.assert_array_equal(x_full[slots], want)
    assert x_full.sum() == core
    np.testing.assert_array_equal(x_comp[:n], want)
    assert not x_comp[n:].any()
    # the width's own bound would stop the peel with 7 tail pairs left
    assert x_short[:n].sum() == core + 7


def test_slot_counters_published(split_state):
    """``em.candidate_slots`` / ``em.staged_slots`` count the candidate
    pairs and the staged slots of the run's bins, and their ratio is
    higher than the full layout's."""
    _, packed, gg = split_state
    reg = get_registry()
    before = reg.snapshot()["counters"]
    res = run_parallel(packed, MLNMatcher(PAPER_LEARNED), gg, scheme="mmp")
    after = reg.snapshot()["counters"]
    st = par._prepare_bins(packed, par._pair_universe(packed))
    cand = sum(int(nb.pair_mask.sum()) for nb in packed.bins.values())
    staged = sum(bt.pair_mask.size for bt in st.bins.values())
    assert (res.candidate_slots, res.staged_slots) == (cand, staged)
    assert (st.candidate_slots, st.staged_slots) == (cand, staged)
    for name, v in (("em.candidate_slots", cand), ("em.staged_slots", staged)):
        assert after.get(name, 0) - before.get(name, 0) == v
    full = sum(nb.pair_mask.size for nb in packed.bins.values())
    assert cand / full < cand / staged <= 1.0
