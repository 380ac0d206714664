"""Device-resident round-parallel SPMD message passing (paper §6.3).

The paper parallelizes the framework in *rounds*: every active
neighborhood is evaluated in parallel (Hadoop Map), the new evidence is
collected and broadcast (Reduce), and the next round's active set is
derived.  Early versions of this module paid O(corpus) host/device
overhead *per round* — re-grounding the MLN on identical static inputs,
one jitted dispatch per size-bin per round (recompiled whenever the
active-row count changed), and Python loops over pair slots to collect
messages.  The engine is now device-resident end to end; the host/device
boundary sits exactly at the *quiescence points*:

* **Grounding cache** (:class:`GroundingCache`): the grounded structures
  (``u``/``u_raw``/``C``/``valid`` for the MLN, ``lev``/``n_shared``/
  ``link``/``valid`` for RULES) are computed once per ``(matcher, bin)``
  and kept on device across rounds.  Rows are fingerprinted by the
  packer's row keys where the cover has them, so the streaming engine
  reuses cached bins across ingests and *splices* only the dirty rows'
  freshly grounded arrays into place (``rows_ground`` counts exactly the
  recomputed rows); a batch cover's rows are hashed only when a cache
  compares them against another staging of the bin
  (``EMResult.rows_hashed``).  Serving memory is boundable: an LRU over bins
  (``capacity`` / ``hbm_budget_bytes``) drops cold bins' tensors and
  re-grounds them on demand, bit-for-bit (see the class docstring).

* **Staged layout** (:func:`_prepare_bins`): each neighborhood is
  staged on its candidate pair slots, not on all k(k-1)/2 slots of its
  size bin; a bin wider than ``_SLOT_STEP`` slots is split into sub-bins
  by the width its rows need, so no program grounds, sweeps or reads
  back the non-candidate slots (``EMResult.candidate_slots`` /
  ``staged_slots`` count both).

* **Fused multi-round closure** (:func:`build_fused_fn`): rounds that
  touch no host state — all NO-MP/SMP rounds, and MMP's ``fast_rounds``
  greedy re-activation rounds — run inside a single jitted
  ``jax.lax.while_loop``.  The loop body evaluates every bin (batched,
  ``shard_map``-sharded over the mesh's data axes), ORs the matched
  pairs into a replicated match bitset (one ``psum`` per round — the
  paper's disk shuffle), and derives the next round's active set *on
  device* from the ``uidx`` slot-incidence of the newly set bits.  The
  bitset is donated into the call and carried by the loop, so the
  multi-round closure is ONE host dispatch instead of
  O(bins x rounds).

* **Quiescence points**: only MMP's maximal-message *pool merge*
  (Algorithm 3 keeps it on the coordinator) runs on the host.  Full
  maximal-message rounds dispatch once per bin at the *full* bin shape
  with an active-row mask (no per-round recompiles), component labels
  are turned into messages by batched numpy segment ops
  (``driver._labels_to_messages``), and the step-7 promotion delta
  checks run *batched on device* (:class:`DevicePromoter`): the pool's
  group bitsets ship to device and the whole promotion fixpoint is one
  jitted ``while_loop`` — no host walk over the global coupling COO
  (``EMResult.promote_host_scans`` == 0, gated in CI).

Consistency (Thms. 2/4) guarantees the device schedule reaches the same
fixpoint as the sequential drivers: the matcher is monotone, evaluating
a non-incident neighborhood is idempotent (its evidence projection is
unchanged), and deferring step-7 promotion to quiescence points
composes monotone operators whose least fixpoint is schedule-invariant.
``tests/test_parallel_rounds.py`` asserts bit-for-bit equality for all
three schemes, ``fast_rounds`` on and off, against both the sequential
drivers and the legacy per-round host loop (kept under ``fused=False``
as the differential baseline that ``benchmarks/table1_parallel.py``
measures the speedup against).

The per-round SPMD function is exposed via :func:`build_round_fn` so the
multi-pod dry-run can ``.lower().compile()`` the EM round on the
production mesh.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import pairs as pairlib
from repro.core.cover import PackedCover
from repro.core.driver import (
    EMResult,
    MessagePool,
    _labels_to_messages,
    _promote,
    publish_em_result,
)
from repro.obs import record_transfer
from repro.obs import span as obs_span
from repro.core.global_grounding import GlobalGrounding
from repro.core.mln import (
    MLNMatcher,
    MLNWeights,
    _infer_one,
    closure_batch,
    ground,
    ground_structure,
)
from repro.core.rules import _rules_fixpoint, rules_fixpoint_batch
from repro.core.types import MatchStore, NeighborhoodBatch
from repro.kernels import common as kcommon

_HISTORY_CAP = 256  # fused-loop per-round active-count log capacity


def make_em_mesh(n_shards: int | None = None, axis: str = "data") -> Mesh:
    devs = jax.devices()
    n = n_shards or len(devs)
    return jax.make_mesh((n,), (axis,), devices=devs[:n])


# ---------------------------------------------------------------------------
# Device-resident grounding cache
# ---------------------------------------------------------------------------


def _matcher_cache_key(matcher) -> tuple[str, object]:
    """Capability dispatch: a device-capable family declares
    ``parallel_backend() -> (kind, cfg)``, the grounding-cache key that
    selects its registered ground/eval functions below."""
    pb = getattr(matcher, "parallel_backend", None)
    if pb is not None:
        return pb()
    raise TypeError(
        f"matcher {type(matcher).__name__} has no parallel backend "
        f"(registered grounding kinds: {sorted(_GROUND_BUILDERS)}); "
        "host-only families run through the sequential drivers "
        "(run_nomp / run_smp / run_mmp)"
    )


# kind -> builder(cfg) -> fn(entity_ids, entity_mask, coauthor,
# sim_level, pair_mask, slot_i, slot_j) -> 4-tuple of (B, ...) device
# arrays with ``valid`` last, on the staged pair layout (``slot_i`` /
# ``slot_j`` give each pair slot's entity slots, see _prepare_bins).
# Plug-in families register here (and an eval branch in _eval_bin_x) to
# run on the fused device engine.
_GROUND_BUILDERS: dict[str, object] = {}


def register_ground_builder(kind: str, builder) -> None:
    _GROUND_BUILDERS[kind] = builder


def _mln_ground_builder(weights: MLNWeights):
    def _ground_mln(entity_ids, entity_mask, coauthor, sim_level, pair_mask,
                    slot_i, slot_j):
        batch = NeighborhoodBatch(
            entity_ids=entity_ids,
            entity_mask=entity_mask,
            coauthor=coauthor,
            sim_level=sim_level,
            pair_gid=pair_mask,
            pair_mask=pair_mask,
        )
        g = ground(batch, weights, slot_i, slot_j)
        return g.u, g.u_raw, g.C, g.valid

    return jax.jit(_ground_mln)


def _rules_ground_builder(_cfg):
    def _ground_rules(entity_ids, entity_mask, coauthor, sim_level, pair_mask,
                      slot_i, slot_j):
        batch = NeighborhoodBatch(
            entity_ids=entity_ids,
            entity_mask=entity_mask,
            coauthor=coauthor,
            sim_level=sim_level,
            pair_gid=pair_mask,
            pair_mask=pair_mask,
        )
        lev, valid, n_shared, link = ground_structure(batch, slot_i, slot_j)
        return lev, n_shared, link, valid

    return jax.jit(_ground_rules)


def _embed_ground_builder(matcher):
    """Host grounding for the embedding family: pairwise cosine from the
    matcher's append-only per-id embedding memo.  Pure in the entity
    ids (embeddings are deterministic per id and never mutated), so the
    grounding-cache splice/LRU contract holds exactly as for the jitted
    kinds; only dirty rows' ids are ever (re-)encoded.  It grounds on
    the upper triangle and gathers the staged slots from it."""

    def f(entity_ids, entity_mask, coauthor, sim_level, pair_mask,
          slot_i, slot_j):
        ids = np.asarray(entity_ids)
        pm = np.asarray(pair_mask, dtype=bool)
        B, k = ids.shape
        # triangle slot of each staged slot; inert padding reads slot 0
        tri = np.maximum(
            pairlib.pair_slot_table(k)[np.asarray(slot_i), np.asarray(slot_j)], 0
        )
        full_pm = np.zeros((B, pairlib.num_pairs(k)), dtype=bool)
        rows, cols = np.nonzero(pm)
        full_pm[rows, tri[rows, cols]] = True
        base, _ = matcher.ground_rows(ids, full_pm)
        base = np.take_along_axis(base, tri, axis=1) & pm
        return (
            jnp.asarray(base),
            jnp.asarray(pm),
            jnp.zeros((B, 1, 1), jnp.float32),
            jnp.zeros((B, 1), jnp.float32),
        )

    return f


register_ground_builder("mln", _mln_ground_builder)
register_ground_builder("rules", _rules_ground_builder)
register_ground_builder("embed", _embed_ground_builder)


@functools.lru_cache(maxsize=None)
def _ground_bin_fn(kind: str, cfg):
    """Bin grounding for one ``(kind, cfg)`` key: raw row tensors ->
    device-resident arrays.

    Returns a uniform 4-tuple with ``valid`` last: MLN bins get
    ``(u, u_raw, C, valid)``, RULES bins ``(lev, n_shared, link,
    valid)``, embedding bins ``(base, valid, 0, 0)``.  ``cfg`` must be
    hashable (weights dataclass, matcher instance, or None).
    """
    if kind not in _GROUND_BUILDERS:
        raise TypeError(
            f"no grounding builder registered for kind {kind!r} "
            f"(registered: {sorted(_GROUND_BUILDERS)})"
        )
    return _GROUND_BUILDERS[kind](cfg)


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n else 1


class GroundingCache:
    """Per-bin device-resident grounded structures with splice updates
    and an optional LRU bound on resident device memory.

    Rows are fingerprinted by the packer's row key when the cover came
    from the CoverDelta splice (``PackedCover.row_keys`` — the ``(k,
    members, intra-edges)`` tuple that by contract changes whenever
    anything feeding the row tensors changes; the streaming path always
    has these, so its per-ingest signature sweep is a tuple gather, not
    a serialization pass).  A cover packed from scratch has no row keys:
    its entry keeps a reference to the staged bin (``_BinTensors``) it
    was grounded from instead of signatures, and a ``get`` of that same
    bin object is a hit outright (the per-dispatch re-fetch of a bounded
    cache within one run).  Rows are hashed only when a ``get`` has to
    compare a *different* bin object with an entry and a side has no row
    keys: a fixed-size blake2b digest of each row's raw bytes, taken
    once for the kept bin and once for the new one, after which the
    entry holds the digests and drops the reference.  Identity stands for equal content because no
    path writes into a staged bin's arrays once it is built:
    ``_prepare_bins`` makes them fresh or shares the arrays of a batch
    ``PackedCover``, which never changes after it is packed (the
    CoverDelta's covers, whose rows are views of append buffers, carry
    row keys, and an entry with row keys keeps no reference).

    An unchanged bin is served from cache outright; a bin whose rows
    moved/changed is *spliced* — unchanged rows are gathered from the
    cached device arrays, only fresh rows are re-grounded (the O(B * P^2
    * k) einsums), padded to a power of two to bound compile variants.
    The streaming engine holds one cache per service so ingests that
    leave a bin untouched never re-ground it; call :meth:`invalidate` to
    drop everything (e.g. after changing matcher weights in place).

    **Serving-memory bound** (``capacity`` / ``hbm_budget_bytes``): the
    cached ``(B, P, P)`` coupling tensors dominate device memory, so a
    long-lived service can cap how many bins stay resident.  Entries
    are LRU-ordered by :meth:`get`; inserting past the bound drops the
    coldest bins' device arrays (their row signatures or bin reference
    are kept — host objects, not HBM, and nothing is computed).  A later
    ``get`` of an evicted bin *cold re-grounds* it from the raw row
    tensors — grounding is a pure function of those tensors, so the
    recomputed arrays are bit-for-bit the evicted ones and every
    fixpoint is unchanged (tested under capacities {1, 2, all}).
    Eviction trades compute for memory only.

    Counters (read by tests, ``EMResult`` and ``IngestReport``):
      ``ground_calls``        grounding dispatches issued
      ``rows_ground``         rows whose grounding was actually recomputed
      ``rows_hashed``         rows whose signature was taken by hashing
                              (row-key gathers do not count)
      ``bin_hits``            bins served without re-grounding any row
      ``splice_calls``        bins updated via :meth:`splice` (device scatter)
      ``evictions``           bins whose device arrays were LRU-dropped
      ``cold_regrounds``      gets that re-ground an evicted (unchanged) bin
      ``peak_resident_bins``  high-water mark of array-resident bins
      ``peak_resident_bytes`` high-water mark of tracked device bytes
    """

    def __init__(self, capacity: int | None = None,
                 hbm_budget_bytes: int | None = None):
        if capacity is not None and capacity < 1:
            raise ValueError(f"GroundingCache capacity must be >= 1: {capacity}")
        if hbm_budget_bytes is not None and hbm_budget_bytes <= 0:
            raise ValueError(
                f"GroundingCache hbm_budget_bytes must be > 0: {hbm_budget_bytes}"
            )
        self.capacity = capacity
        self.hbm_budget_bytes = hbm_budget_bytes
        # key -> (src, arrays | None, nbytes); src is the row signatures
        # or, without row keys, the _BinTensors the arrays were grounded
        # from; dict order == LRU order (oldest first), arrays None for
        # entries evicted but remembered
        self._bins: dict[tuple, tuple[tuple | _BinTensors, tuple | None, int]] = {}
        self.ground_calls = 0
        self.rows_ground = 0
        self.rows_hashed = 0
        self.bin_hits = 0
        self.splice_calls = 0
        self.evictions = 0
        self.cold_regrounds = 0
        self.peak_resident_bins = 0
        self.peak_resident_bytes = 0
        # per-run window peak: run_parallel resets it at run start so
        # EMResult can report the residency high-water of THAT run,
        # while peak_resident_bins stays the cache-lifetime mark
        self.window_peak_bins = 0

    @property
    def bounded(self) -> bool:
        return self.capacity is not None or self.hbm_budget_bytes is not None

    @property
    def resident_bins(self) -> int:
        return sum(1 for _, arrays, _ in self._bins.values() if arrays is not None)

    @property
    def resident_bytes(self) -> int:
        return sum(n for _, arrays, n in self._bins.values() if arrays is not None)

    def invalidate(self) -> None:
        self._bins.clear()

    _TXN_COUNTERS = (
        "ground_calls", "rows_ground", "rows_hashed", "bin_hits", "splice_calls",
        "evictions", "cold_regrounds", "peak_resident_bins",
        "peak_resident_bytes", "window_peak_bins",
    )

    def journal_rollback(self, t) -> None:
        """Register restoration of this cache into an ingest transaction.

        The entry tuples are immutable, so a shallow copy of the LRU
        dict plus the counter values is an exact pre-ingest snapshot —
        O(bins), not O(rows) (bin count is bounded by
        ``len(k_bins) x matchers``).
        """
        prev_bins = dict(self._bins)
        prev_counters = tuple(getattr(self, c) for c in self._TXN_COUNTERS)

        def undo() -> None:
            self._bins = prev_bins
            for c, v in zip(self._TXN_COUNTERS, prev_counters):
                setattr(self, c, v)

        t.on_rollback(undo)

    def begin_peak_window(self) -> None:
        """Start a fresh residency-peak window (bins already resident
        count toward it — they occupy HBM whether or not this run
        touches them)."""
        self.window_peak_bins = self.resident_bins

    @staticmethod
    def _nbytes(arrays: tuple) -> int:
        return sum(int(a.nbytes) for a in arrays)

    def _touch(self, key: tuple) -> None:
        self._bins[key] = self._bins.pop(key)

    def _store(self, key: tuple, src: tuple | _BinTensors, arrays: tuple) -> None:
        """Insert/refresh an entry as most-recent, then evict the coldest
        array-resident entries (never the one just stored) until the
        configured bin-count capacity and byte budget both hold."""
        self._bins.pop(key, None)
        self._bins[key] = (src, arrays, self._nbytes(arrays))

        def over() -> bool:
            if self.capacity is not None and self.resident_bins > self.capacity:
                return True
            return (
                self.hbm_budget_bytes is not None
                and self.resident_bins > 1
                and self.resident_bytes > self.hbm_budget_bytes
            )

        while over():
            victim = next(
                k for k, (_, arrays, _) in self._bins.items()
                if arrays is not None and k != key
            )
            vsrc, _, _ = self._bins[victim]
            self._bins[victim] = (vsrc, None, 0)
            # keep LRU position: an evicted entry stays coldest until re-used
            self.evictions += 1
        resident = self.resident_bins
        self.peak_resident_bins = max(self.peak_resident_bins, resident)
        self.window_peak_bins = max(self.window_peak_bins, resident)
        self.peak_resident_bytes = max(
            self.peak_resident_bytes, self.resident_bytes
        )

    def _row_sigs(self, bt: _BinTensors, row_keys: tuple | None = None) -> tuple:
        if row_keys is not None:
            return row_keys
        self.rows_hashed += bt.entity_mask.shape[0]
        return tuple(
            hashlib.blake2b(
                bt.entity_ids[r].tobytes()
                + bt.entity_mask[r].tobytes()
                + bt.coauthor[r].tobytes()
                + bt.sim_level[r].tobytes()
                + bt.pair_mask[r].tobytes()
                + bt.slot_i[r].tobytes()
                + bt.slot_j[r].tobytes(),
                digest_size=16,
            ).digest()
            for r in range(bt.entity_mask.shape[0])
        )

    def _ground_rows(self, fn, bt: _BinTensors, rows: np.ndarray):
        """Ground a row subset, padded to a power of two (inert rows)."""
        n = len(rows)
        pad = _pow2(n) - n
        ids = bt.entity_ids[rows]
        em = bt.entity_mask[rows]
        co = bt.coauthor[rows]
        lv = bt.sim_level[rows]
        pm = bt.pair_mask[rows]
        si = bt.slot_i[rows]
        sj = bt.slot_j[rows]
        if pad:
            ids = np.concatenate(
                [ids, np.full((pad,) + ids.shape[1:], -1, ids.dtype)]
            )
            em, co, lv, pm, si, sj = (
                np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
                for a in (em, co, lv, pm, si, sj)
            )
        with obs_span("rounds.ground", rows=n):
            record_transfer("gcache", ids, em, co, lv, pm, si, sj)
            out = fn(ids, em, co, lv, pm, si, sj)
        self.ground_calls += 1
        self.rows_ground += n
        return tuple(a[:n] for a in out) if pad else out

    def splice(self, matcher_key, bt: _BinTensors, sigs: tuple,
               cached: tuple[tuple, tuple]) -> tuple:
        """Update a cached bin in place on device: gather unchanged rows
        from the cached arrays (by row signature), re-ground *only* the
        fresh rows, and scatter them at their new positions.

        This is the device-side leg of the O(dirty) ingest path: the
        streaming engine's covers arrive with ``PackedCover.row_keys``
        from the :class:`~repro.core.cover.CoverDelta` splice, so the
        signature diff here sees exactly the spliced rows and the
        ``(B, P, P)`` grounded tensors are never rebuilt host-side.
        Returns the updated device arrays (also usable standalone by
        callers that track their own bin cache).
        """
        old_sigs, old_arrays = cached
        fn = _ground_bin_fn(*matcher_key)
        pos_of = {s: i for i, s in enumerate(old_sigs)}
        src = np.asarray([pos_of.get(s, -1) for s in sigs], dtype=np.int64)
        fresh = np.where(src < 0)[0]
        gather = jnp.asarray(np.where(src >= 0, src, 0))
        arrays = tuple(a[gather] for a in old_arrays)
        if len(fresh):
            sub = self._ground_rows(fn, bt, fresh)
            at = jnp.asarray(fresh)
            arrays = tuple(
                a.at[at].set(s) for a, s in zip(arrays, sub)
            )
            self.splice_calls += 1
        else:
            self.bin_hits += 1
        return arrays

    def get(self, matcher_key, bin_key: tuple, bt: _BinTensors,
            row_keys: tuple | None = None) -> tuple:
        key = (matcher_key, bin_key)
        cached = self._bins.get(key)
        if cached is None or cached[1] is None:
            # miss, or LRU-evicted arrays: (cold) re-ground every row —
            # grounding is pure in the row tensors, so this reproduces
            # the dropped arrays bit-for-bit.  Nothing is compared, so
            # nothing is hashed: without row keys the entry keeps ``bt``.
            if cached is not None:
                self.cold_regrounds += 1
            fn = _ground_bin_fn(*matcher_key)
            arrays = self._ground_rows(fn, bt, np.arange(bt.entity_mask.shape[0]))
            self._store(key, bt if row_keys is None else row_keys, arrays)
            return arrays
        src, arrays, nbytes = cached
        if src is not bt:
            sigs = self._row_sigs(bt, row_keys)
            if isinstance(src, _BinTensors):
                # first comparison against the kept bin: its digests
                # replace the reference
                src = self._row_sigs(src)
                self._bins[key] = (src, arrays, nbytes)
            if src != sigs:
                arrays = self.splice(matcher_key, bt, sigs, (src, arrays))
                self._store(key, sigs, arrays)
                return arrays
        self.bin_hits += 1
        self._touch(key)
        return arrays


# ---------------------------------------------------------------------------
# Bin preparation (host side, once per cover)
# ---------------------------------------------------------------------------


# Pair-slot width step of a staged bin.  A bin whose k(k-1)/2 slots
# exceed it is split into sub-bins of the smallest multiple of it that
# holds each neighborhood's candidate pairs (capped at k(k-1)/2).
_SLOT_STEP = 128


@dataclasses.dataclass
class _BinTensors:
    """Per-bin device-ready tensors (host copies) on the staged pair
    layout: ``Pc`` slots per row, its candidate pairs first in their
    upper-triangle order, then inert padding (``pair_mask`` False,
    ``uidx`` == Np, ``pair_gid`` == -1)."""

    entity_ids: np.ndarray  # (B, k) int, -1 padding
    entity_mask: np.ndarray
    coauthor: np.ndarray
    sim_level: np.ndarray  # (B, Pc)
    pair_mask: np.ndarray  # (B, Pc)
    uidx: np.ndarray  # (B, Pc) int32 universe index, Np where invalid
    pair_gid: np.ndarray  # (B, Pc)
    slot_i: np.ndarray  # (B, Pc) int16 entity slot of each pair's first end
    slot_j: np.ndarray  # (B, Pc) int16 ... and of its second end
    rows: np.ndarray  # (B,) neighborhood of each row, -1 for padding


@dataclasses.dataclass
class _Staging:
    """The staged bins of one cover and where each neighborhood sits."""

    bins: dict[tuple[int, int], _BinTensors]  # (k, Pc) -> bin, sorted
    bin_of: np.ndarray  # (N,) index into ``bins`` of each neighborhood
    row_of: np.ndarray  # (N,) row within that bin
    candidate_slots: int  # candidate pairs over every row
    staged_slots: int  # B x Pc over every staged bin, padding included


def _universe_index(universe: np.ndarray, pair_gid: np.ndarray) -> np.ndarray:
    """Universe index of each pair gid; ``len(universe)`` where absent."""
    Np = len(universe)
    idx = np.clip(np.searchsorted(universe, pair_gid), 0, max(Np - 1, 0))
    ok = (pair_gid >= 0) & (
        universe[idx] == pair_gid if Np else np.zeros(pair_gid.shape, bool)
    )
    return np.where(ok, idx, Np).astype(np.int32)


def _prepare_bins(
    packed: PackedCover, universe: np.ndarray, pad_mult: int = 1
) -> _Staging:
    """Stage every neighborhood on its candidate pair slots.

    A bin of k entities keeps its upper-triangle layout when k(k-1)/2 is
    at most ``_SLOT_STEP``.  A wider bin is split into sub-bins keyed
    ``(k, Pc)``: each row goes to the smallest multiple of the step that
    holds its candidate pairs (capped at k(k-1)/2) and holds its
    candidate slots in their upper-triangle order, inert padding after.
    The order keeps every tie-break of the matcher (``argmin`` of the
    peel, min-labels of the components) the same as on the full layout.
    ``pad_mult`` pads each sub-bin's batch axis up front (padding rows
    are inert too) so every later dispatch is full-bin shaped.
    """
    out: dict[tuple[int, int], _BinTensors] = {}
    Np = len(universe)
    candidate = 0

    def pad_rows(a, fill, target):
        if target == a.shape[0]:
            return a
        extra = np.full((target - a.shape[0],) + a.shape[1:], fill, dtype=a.dtype)
        return np.concatenate([a, extra], axis=0)

    for k, nb in sorted(packed.bins.items()):
        ii, jj = pairlib.triu_indices(k)
        B, P = nb.pair_mask.shape
        # candidate slots in row-major order: per row, ascending slot
        cr, cc = np.nonzero(nb.pair_mask)
        candidate += len(cr)
        if P <= _SLOT_STEP:
            layouts = [(P, np.arange(B), None)]
        else:
            n_cand = np.bincount(cr, minlength=B)
            steps = np.maximum(-(-n_cand // _SLOT_STEP), 1)
            widths = np.minimum(steps * _SLOT_STEP, P)
            pos = np.arange(len(cr)) - (np.cumsum(n_cand) - n_cand)[cr]
            layouts = [(int(w), np.nonzero(widths == w)[0], pos)
                       for w in np.unique(widths)]
        for w, sel, pos in layouts:
            target = max(-(-len(sel) // pad_mult) * pad_mult, pad_mult)
            if pos is None:  # the upper-triangle layout, as packed
                lev = nb.sim_level.astype(np.int8)
                pm = nb.pair_mask
                gid = nb.pair_gid
                uidx = _universe_index(universe, gid)
                si = np.broadcast_to(ii.astype(np.int16), (B, P))
                sj = np.broadcast_to(jj.astype(np.int16), (B, P))
            else:
                sub = np.full(B, -1)
                sub[sel] = np.arange(len(sel))
                on = widths[cr] == w
                r, c, p = sub[cr[on]], cc[on], pos[on]
                src = (cr[on], c)

                def place(vals, fill, dtype):
                    a = np.full((len(sel), w), fill, dtype=dtype)
                    a[r, p] = vals
                    return a

                gid = place(nb.pair_gid[src], -1, nb.pair_gid.dtype)
                lev = place(nb.sim_level[src], 0, np.int8)
                pm = place(True, False, bool)
                uidx = place(_universe_index(universe, gid[r, p]), Np, np.int32)
                si = place(ii[c], 0, np.int16)
                sj = place(jj[c], 0, np.int16)
            bt = _BinTensors(
                entity_ids=pad_rows(nb.entity_ids[sel], -1, target),
                entity_mask=pad_rows(nb.entity_mask[sel], False, target),
                coauthor=pad_rows(nb.coauthor[sel], False, target),
                sim_level=pad_rows(lev, 0, target),
                pair_mask=pad_rows(pm, False, target),
                uidx=pad_rows(uidx, Np, target),
                pair_gid=pad_rows(gid, -1, target),
                slot_i=pad_rows(si, 0, target),
                slot_j=pad_rows(sj, 0, target),
                rows=pad_rows(packed.bin_rows[k][sel], -1, target),
            )
            record_transfer(
                "prepare", bt.entity_mask, bt.coauthor, bt.sim_level,
                bt.pair_mask, bt.uidx, bt.pair_gid,
            )
            out[(k, w)] = bt
    n = packed.num_neighborhoods
    bin_of = np.zeros(n, dtype=np.int64)
    row_of = np.zeros(n, dtype=np.int64)
    for i, bt in enumerate(out.values()):
        real = np.nonzero(bt.rows >= 0)[0]
        bin_of[bt.rows[real]] = i
        row_of[bt.rows[real]] = real
    return _Staging(
        bins=out, bin_of=bin_of, row_of=row_of, candidate_slots=candidate,
        staged_slots=sum(bt.pair_mask.size for bt in out.values()),
    )


# ---------------------------------------------------------------------------
# Fused multi-round closure (one dispatch for a whole round sequence)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FusedSpec:
    """Static shape/kind description of a fused multi-round program."""

    kinds: tuple[str, ...]  # per-bin matcher kind
    ks: tuple[int, ...]  # per-bin entity slots k
    batch: tuple[int, ...]  # per-bin padded batch size
    num_pairs: tuple[int, ...]  # per-bin staged pair slots Pc
    universe_size: int
    history_cap: int = _HISTORY_CAP  # >= the largest budget ever passed


def _eval_bin_x(kind: str, g, ev_pos, ev_neg):
    """Batched evaluation of the matchers that emit no messages, from
    cached grounding arrays (the collective MLN runs in
    :func:`_bin_full_round`)."""
    if kind == "rules":
        lev, n_shared, link, valid = g
        return rules_fixpoint_batch(lev, n_shared, link, ev_pos, ev_neg, valid)
    if kind == "mln_greedy":
        u, _, C, valid = g
        return closure_batch(u, C, ev_pos, ev_neg, valid)
    if kind == "embed":
        base, valid, _z0, _z1 = g
        return (base | ev_pos) & valid & ~ev_neg
    raise ValueError(f"no batched evaluation for kind {kind!r}")


def _fused_rounds(spec: FusedSpec, axes: tuple[str, ...], *args):
    """Multi-round closure body (runs inside shard_map).

    ``args`` is, per bin, ``(g0, g1, g2, g3, uidx, pair_mask, active0)``
    followed by ``(m_bits, budget)``.  Carries the match bitset, the
    per-bin active-row masks, and the round/eval counters through a
    single ``lax.while_loop``; the next active set is derived on device
    from the ``uidx`` slot incidence of the newly set bits.
    """
    nb = len(spec.kinds)
    per = [args[i * 7 : (i + 1) * 7] for i in range(nb)]
    m_bits = args[7 * nb]
    budget = args[7 * nb + 1]
    Np = spec.universe_size

    def _psum(v):
        for ax in axes:
            v = jax.lax.psum(v, ax)
        return v

    uidxs = [p[4] for p in per]
    safe = [jnp.minimum(u, Np - 1) for u in uidxs]
    inuniv = [(p[4] < Np) & p[5] for p in per]
    actives0 = tuple(p[6] for p in per)

    n0 = _psum(
        functools.reduce(
            jnp.add, [jnp.sum(a.astype(jnp.int32)) for a in actives0]
        )
    )

    def cond(state):
        _, _, rounds, _, n_active, _ = state
        return (n_active > 0) & (rounds < budget)

    def body(state):
        bits, actives, rounds, evals, n_active, hist = state
        hist = hist.at[jnp.minimum(rounds, spec.history_cap - 1)].set(n_active)
        local = jnp.zeros((Np,), jnp.int32)
        for i in range(nb):
            ev_pos = bits[safe[i]] & inuniv[i]
            x = _eval_bin_x(spec.kinds[i], per[i][:4], ev_pos,
                            jnp.zeros_like(ev_pos))
            x = x & inuniv[i] & actives[i][:, None]
            local = local.at[uidxs[i].reshape(-1)].max(
                x.reshape(-1).astype(jnp.int32), mode="drop"
            )
        new_bits = (_psum(local) > 0) | bits
        changed = new_bits & ~bits
        nxt = []
        n_local = jnp.int32(0)
        for i in range(nb):
            act = jnp.any(changed[safe[i]] & inuniv[i], axis=1)
            nxt.append(act)
            n_local = n_local + jnp.sum(act.astype(jnp.int32))
        return (new_bits, tuple(nxt), rounds + 1, evals + n_active,
                _psum(n_local), hist)

    state0 = (
        m_bits,
        actives0,
        jnp.int32(0),
        jnp.int32(0),
        n0,
        jnp.zeros((spec.history_cap,), jnp.int32),
    )
    bits, _, rounds, evals, _, hist = jax.lax.while_loop(cond, body, state0)
    return bits, rounds, evals, hist


@functools.lru_cache(maxsize=64)  # bounded: streaming ingests grow the
# universe/batch shapes, so specs (and their compiled executables) churn
def build_fused_fn(spec: FusedSpec, mesh: Mesh, axes: tuple[str, ...]):
    """Jitted fused multi-round program for one (cover, mesh) shape.

    The match bitset argument is donated: across calls its buffer is
    reused, and inside the call the ``while_loop`` aliases it between
    rounds — the bitset never round-trips to the host mid-closure.
    """
    nbins = len(spec.kinds)
    batch_spec = P(axes)
    rep = P()
    in_specs = tuple([batch_spec] * 7 * nbins) + (rep, rep)
    fn = functools.partial(_fused_rounds, spec, axes)
    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=(rep, rep, rep, rep),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(7 * nbins,))


# ---------------------------------------------------------------------------
# Device-resident step-7 promotion (quiescence points without host scans)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _promote_loop_fn(num_gids: int, num_coup: int, m_pad: int, k_pad: int):
    """Jitted promotion fixpoint for one (grounding, pool) shape.

    One dispatch runs the whole ``while changed`` sweep of Algorithm 3
    step 7 on device: every sweep evaluates ALL groups' global deltas
    against the current base bitset in a single batched computation
    (``lin + w_co * quad`` over the coupling COO) and promotes every
    group with new pairs and a non-negative delta at once.  Batching
    the sweep is sound because ``w_co >= 0`` makes ``P_E`` supermodular:
    a group's delta is non-decreasing in the base, so a group promotable
    against the sweep-start base is still promotable after any other
    promotion of that sweep — the closure reached is the same least
    fixpoint the sequential group walk reaches (``driver._promote``,
    kept as the host baseline).
    """

    def _promote_loop(u, coup_p, coup_q, w_co, gidx, gseg, gvalid, base):
        # (K, Np) membership bitsets of the pool groups, scattered once;
        # padded members carry gseg == k_pad and land in a dropped row.
        add = (
            jnp.zeros((k_pad + 1, num_gids), jnp.bool_)
            .at[gseg, gidx].set(True)[:k_pad]
        )

        def cond(state):
            return state[2]

        def body(state):
            bits, promoted, _ = state
            new = add & ~bits[None, :]
            has_new = jnp.any(new, axis=1) & gvalid
            lin = jnp.sum(jnp.where(new, u[None, :], jnp.float32(0)), axis=1)
            both = bits[None, :] | add
            quad_base = jnp.sum(bits[coup_p] & bits[coup_q])
            quad_both = jnp.sum(both[:, coup_p] & both[:, coup_q], axis=1)
            delta = lin + w_co * (quad_both - quad_base).astype(jnp.float32)
            mask = has_new & (delta >= -1e-6)
            bits = bits | jnp.any(add & mask[:, None], axis=0)
            return (bits, promoted + jnp.sum(mask.astype(jnp.int32)),
                    jnp.any(mask))

        bits, promoted, _ = jax.lax.while_loop(
            cond, body, (base, jnp.int32(0), jnp.bool_(True))
        )
        return bits, promoted

    return jax.jit(_promote_loop)


class DevicePromoter:
    """Step-7 promotion with the delta checks batched on device.

    The host ``driver._promote`` walks the global coupling COO with
    numpy once per group per sweep — an O(groups x couplings) host scan
    at every quiescence point.  This class keeps the grounding's unary
    and coupling arrays on device (uploaded once per grounding) and
    ships the pool's group bitsets alongside, so a quiescence point is
    ONE jitted dispatch running the whole promotion fixpoint
    (:func:`_promote_loop_fn`); the host only assembles the group
    member indices (O(pool), memoized per ``MessagePool.groups()``
    snapshot) and reads back the (Np,) bitset.  ``host_scans`` counts
    fallbacks to the host walk (only taken for ``w_co < 0``, where the
    supermodularity argument for batched sweeps fails) — the quantity
    ``benchmarks/check_bench.py`` gates at zero.
    """

    def __init__(self, gg: GlobalGrounding):
        self.gg = gg
        self.batched_ok = float(gg.w_co) >= 0.0 and len(gg.gids) > 0
        self.dispatches = 0
        self.host_scans = 0
        # (groups list, device arrays): keeps a strong ref to the groups
        # snapshot so identity comparison can never hit a recycled id
        self._groups_memo: tuple[list, tuple | None] | None = None

    def _device_grounding(self) -> tuple:
        # cached ON the grounding object: the streaming maintainer hands
        # out the same GlobalGrounding while no delta is pending, so the
        # upload happens once per grounding *version*, not once per run
        gg = self.gg
        if gg._device is None:
            cp = gg.coup_p.astype(np.int32)
            cq = gg.coup_q.astype(np.int32)
            record_transfer("promoter", gg.u, cp, cq)
            gg._device = (
                jnp.asarray(gg.u),
                jnp.asarray(cp),
                jnp.asarray(cq),
                jnp.float32(gg.w_co),
            )
        return gg._device

    def _group_arrays(self, groups: list[np.ndarray]) -> tuple | None:
        """Flat member-index CSR of the pool groups (pow2-padded), memoized
        on the identity of the ``MessagePool.groups()`` snapshot (the pool
        invalidates it on every mutation)."""
        if self._groups_memo is not None and self._groups_memo[0] is groups:
            return self._groups_memo[1]
        gg = self.gg
        idx_parts: list[np.ndarray] = []
        seg_parts: list[np.ndarray] = []
        n_groups = 0
        for grp in groups:
            idx = gg.index_of(grp)
            idx = idx[idx >= 0]
            if len(idx) < 2:  # retracted below pair size: never promotable
                continue
            idx_parts.append(idx.astype(np.int32))
            seg_parts.append(np.full(len(idx), n_groups, dtype=np.int32))
            n_groups += 1
        if not n_groups:
            out = None
        else:
            gidx = np.concatenate(idx_parts)
            gseg = np.concatenate(seg_parts)
            m_pad = _pow2(len(gidx))
            k_pad = _pow2(n_groups)
            if m_pad > len(gidx):
                pad = m_pad - len(gidx)
                gidx = np.concatenate([gidx, np.zeros(pad, np.int32)])
                gseg = np.concatenate([gseg, np.full(pad, k_pad, np.int32)])
            gvalid = np.zeros(k_pad, dtype=bool)
            gvalid[:n_groups] = True
            record_transfer("promoter", gidx, gseg, gvalid)
            out = (
                jnp.asarray(gidx), jnp.asarray(gseg), jnp.asarray(gvalid),
                m_pad, k_pad,
            )
        self._groups_memo = (groups, out)
        return out

    def promote(self, pool: MessagePool, m_plus: MatchStore):
        """Drop-in for ``driver._promote``: same (matches, promoted) pair.

        ``promoted`` counts group-promotion events; the batched sweep may
        count a group the sequential walk skipped as already-subsumed
        within the same sweep, so only the *match set* (identical by
        supermodularity) is bit-for-bit comparable across engines.
        """
        with obs_span("rounds.promote") as sp:
            groups = pool.groups()
            if not groups:
                return m_plus, 0
            if not self.batched_ok:
                self.host_scans += 1
                sp.set(host=True)
                return _promote(pool, self.gg, m_plus)
            garrs = self._group_arrays(groups)
            if garrs is None:
                return m_plus, 0
            gg = self.gg
            gidx, gseg, gvalid, m_pad, k_pad = garrs
            base0 = gg.bool_of(m_plus)
            fn = _promote_loop_fn(len(gg.gids), len(gg.coup_p), m_pad, k_pad)
            record_transfer("promoter", base0)
            bits, promoted = fn(
                *self._device_grounding(), gidx, gseg, gvalid,
                jnp.asarray(base0)
            )
            # int() blocks on the dispatch, so the span bills the device
            # work it launched, not the next host sync
            promoted = int(promoted)
            self.dispatches += 1
            if promoted:
                extra = gg.gids[np.asarray(bits) & ~base0]
                if len(extra):
                    m_plus = m_plus.union(extra)
            return m_plus, promoted


# ---------------------------------------------------------------------------
# Full (maximal-message) rounds: one full-bin-shaped dispatch per bin
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BinRoundSpec:
    """Static description of one bin's host-visible full round."""

    kind: str
    k: int  # entity slots: k(k-1)/2 bounds the MLN's peel
    batch: int
    num_pairs: int  # staged pair slots Pc
    universe_size: int


def _bin_full_round(spec: BinRoundSpec, axes, gather, g0, g1, g2, g3, uidx,
                    pmask, active, m_bits):
    """One full round of one bin (inside shard_map): evaluate every
    active row from cached grounding arrays, return per-slot matches,
    component labels, and the updated replicated bitset.

    With ``gather=True`` (multi-process meshes) the per-row ``x``/``lab``
    outputs are ``all_gather``-ed back to replicated inside the body:
    the host coordinator reads them into numpy for the maximal-message
    pool merge, and a batch-sharded global array is not addressable as a
    whole on any single host.
    """
    Np = spec.universe_size
    safe = jnp.minimum(uidx, Np - 1)
    inuniv = (uidx < Np) & pmask
    ev_pos = m_bits[safe] & inuniv
    ev_neg = jnp.zeros_like(ev_pos)
    g = (g0, g1, g2, g3)
    if spec.kind == "mln":
        infer = functools.partial(_infer_one, num_pairs=pairlib.num_pairs(spec.k))
        x, lab = jax.vmap(infer)(g0, g1, g2, ev_pos, ev_neg, g3)
    else:
        x = _eval_bin_x(spec.kind, g, ev_pos, ev_neg)
        lab = jnp.full(x.shape, spec.num_pairs, dtype=jnp.int32)
    xm = x & inuniv & active[:, None]
    local = jnp.zeros((Np,), jnp.int32).at[uidx.reshape(-1)].max(
        xm.reshape(-1).astype(jnp.int32), mode="drop"
    )
    bits = local
    for ax in axes:
        bits = jax.lax.psum(bits, ax)
    if gather:
        for ax in axes:
            x = jax.lax.all_gather(x, ax, axis=0, tiled=True)
            lab = jax.lax.all_gather(lab, ax, axis=0, tiled=True)
    return x, lab, (bits > 0) | m_bits


@functools.lru_cache(maxsize=64)  # bounded, same churn as build_fused_fn
def build_bin_round_fn(spec: BinRoundSpec, mesh: Mesh, axes: tuple[str, ...]):
    """Jitted full round for one bin, always dispatched at the full bin
    shape (an active-row mask replaces host-side row gathering, so the
    program compiles once per cover instead of once per active-set
    shape per round).  On a multi-process mesh the row outputs come back
    replicated (gathered in-body) so the coordinator can read them."""
    batch_spec = P(axes)
    rep = P()
    gather = kcommon.mesh_spans_processes(mesh)
    fn = functools.partial(_bin_full_round, spec, axes, gather)
    row_spec = rep if gather else batch_spec
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(batch_spec,) * 7 + (rep,),
        out_specs=(row_spec, row_spec, rep),
        check_vma=False,
    )
    return jax.jit(mapped)


# ---------------------------------------------------------------------------
# Legacy per-round host loop (build_round_fn stays for the mesh dry-run)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RoundSpec:
    """Static description of one bin's round function."""

    k: int
    num_pairs: int
    universe_size: int
    matcher_kind: str  # 'mln' | 'mln_greedy' | 'rules'
    weights: MLNWeights | None


def _device_round(spec: RoundSpec, axes: tuple[str, ...], entity_mask, coauthor,
                  sim_level, pair_mask, uidx, m_bits):
    """One shard's work for one legacy round: re-grounds from the raw
    tensors on every call (the per-round overhead the grounding cache
    and fused engine remove — kept as the differential baseline)."""
    Np = spec.universe_size
    safe = jnp.minimum(uidx, Np - 1)
    ev_pos = m_bits[safe] & (uidx < Np) & pair_mask
    ev_neg = jnp.zeros_like(ev_pos)

    batch = NeighborhoodBatch(
        entity_ids=entity_mask,  # only shapes/masks are used by grounding
        entity_mask=entity_mask,
        coauthor=coauthor,
        sim_level=sim_level,
        pair_gid=uidx,
        pair_mask=pair_mask,
    )
    if spec.matcher_kind == "rules":
        lev, valid, n_shared, link = ground_structure(batch)
        x = jax.vmap(_rules_fixpoint)(lev, n_shared, link, ev_pos, ev_neg, valid)
        lab = jnp.full(x.shape, spec.num_pairs, dtype=jnp.int32)
    else:
        g = ground(batch, spec.weights)
        if spec.matcher_kind == "mln_greedy":
            x = closure_batch(g.u, g.C, ev_pos, ev_neg, g.valid)
            lab = jnp.full(x.shape, spec.num_pairs, dtype=jnp.int32)
        else:
            x, lab = jax.vmap(_infer_one)(g.u, g.u_raw, g.C, ev_pos, ev_neg,
                                          g.valid)

    flat_idx = uidx.reshape(-1)
    flat_val = (x & pair_mask).reshape(-1)
    local_bits = jnp.zeros((Np,), jnp.int32).at[flat_idx].max(
        flat_val.astype(jnp.int32), mode="drop"
    )
    bits = local_bits
    for ax in axes:
        bits = jax.lax.psum(bits, ax)
    return x, lab, (bits > 0) | m_bits


@functools.lru_cache(maxsize=None)
def build_round_fn(spec: RoundSpec, mesh: Mesh, axes: tuple[str, ...]):
    """Jitted SPMD round function for one (bin, mesh) combination."""
    batch_spec = P(axes)
    rep = P()
    fn = functools.partial(_device_round, spec, axes)
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(batch_spec, batch_spec, batch_spec, batch_spec, batch_spec, rep),
        out_specs=(batch_spec, batch_spec, rep),
        check_vma=False,
    )
    return jax.jit(mapped)


def _matcher_spec(matcher, k: int, Np: int) -> RoundSpec:
    kind, weights = _matcher_cache_key(matcher)
    if kind not in ("mln", "rules"):
        raise TypeError(
            f"legacy per-round loop supports only the jit-groundable "
            f"'mln'/'rules' kinds, got {kind!r}; use the fused engine"
        )
    if kind == "mln" and not getattr(matcher, "collective", True):
        kind = "mln_greedy"
    return RoundSpec(
        k=k,
        num_pairs=pairlib.num_pairs(k),
        universe_size=Np,
        matcher_kind=kind,
        weights=weights,
    )


def _pad_rows(arrs: list[np.ndarray], mult: int) -> list[np.ndarray]:
    """Pad the batch axis to a multiple of the shard count.

    Padding rows are all-zero: ``pair_mask`` False everywhere makes them
    inert (no candidate pairs, no scatters — `x & pair_mask` is False).
    """
    b = arrs[0].shape[0]
    target = max(((b + mult - 1) // mult) * mult, mult)
    if target == b:
        return arrs
    out = []
    for a in arrs:
        pad = np.zeros((target - b,) + a.shape[1:], dtype=a.dtype)
        out.append(np.concatenate([a, pad], axis=0))
    return out


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------


def _pair_universe(packed: PackedCover) -> np.ndarray:
    """Every candidate pair gid of the cover, sorted."""
    return np.sort(np.asarray(sorted(packed.pair_levels.keys()), dtype=np.int64))


def _no_pairs_result(init_matches: MatchStore | None, t0: float) -> EMResult:
    """No candidate pairs anywhere: nothing to resolve."""
    return EMResult(
        init_matches if init_matches is not None else MatchStore(),
        0, 0, 0, 0, time.perf_counter() - t0,
    )


def _seed_bits(universe: np.ndarray, m_plus: MatchStore) -> np.ndarray:
    Np = len(universe)
    bits = np.zeros(Np, dtype=bool)
    if len(m_plus):
        idx = np.searchsorted(universe, m_plus.gids)
        idx = np.clip(idx, 0, Np - 1)
        bits[idx[universe[idx] == m_plus.gids]] = True
    return bits


def _set_bits(bits: np.ndarray, universe: np.ndarray, gids: np.ndarray) -> None:
    if not len(gids):
        return
    idx = np.searchsorted(universe, gids)
    idx = np.clip(idx, 0, max(len(universe) - 1, 0))
    bits[idx[universe[idx] == gids]] = True


def run_parallel(
    packed: PackedCover,
    matcher,
    gg: GlobalGrounding | None = None,
    *,
    scheme: str = "smp",
    mesh: Mesh | None = None,
    max_rounds: int = 256,
    fast_rounds: bool = True,
    active: list[int] | None = None,
    init_matches: MatchStore | None = None,
    pool: MessagePool | None = None,
    gcache: GroundingCache | None = None,
    fused: bool = True,
) -> EMResult:
    """Round-parallel NO-MP / SMP / MMP over the mesh's data axes.

    See :func:`_run_parallel_impl` for the engine semantics; this entry
    point additionally opens the ``em.run`` span over the whole call and
    publishes the :class:`EMResult` counters into the runtime metrics
    registry (``em.*`` family).
    """
    with obs_span("em.run", scheme=scheme):
        res = _run_parallel_impl(
            packed, matcher, gg, scheme=scheme, mesh=mesh,
            max_rounds=max_rounds, fast_rounds=fast_rounds, active=active,
            init_matches=init_matches, pool=pool, gcache=gcache,
            fused=fused,
        )
        return publish_em_result(res)


def _run_parallel_impl(
    packed: PackedCover,
    matcher,
    gg: GlobalGrounding | None = None,
    *,
    scheme: str = "smp",
    mesh: Mesh | None = None,
    max_rounds: int = 256,
    fast_rounds: bool = True,
    active: list[int] | None = None,
    init_matches: MatchStore | None = None,
    pool: MessagePool | None = None,
    gcache: GroundingCache | None = None,
    fused: bool = True,
) -> EMResult:
    """Round-parallel NO-MP / SMP / MMP over the mesh's data axes.

    scheme='nomp' runs one round with no evidence exchange;
    scheme='smp' exchanges match bitsets per round (Alg. 1 in rounds);
    scheme='mmp' additionally maintains the maximal-message pool and the
    step-7 promotion on the host (needs a Type-II matcher and ``gg``).

    ``active``/``init_matches``/``pool`` are the streaming hooks
    (mirroring the sequential drivers): seed round 1 with only the
    dirty neighborhoods and continue the closure from a previous
    fixpoint / maximal-message pool.

    ``gcache`` is the persistent grounding cache: the streaming engine
    passes one per service so clean bins are never re-ground across
    ingests; batch callers get a per-run cache (grounding still happens
    exactly once per bin per cover, across all rounds).  A *bounded*
    cache (``GroundingCache(capacity=...)`` or ``hbm_budget_bytes=...``)
    is honored per dispatch: bin arrays are fetched just-in-time, so at
    most ``capacity`` bins stay array-resident between dispatches and
    cold bins re-ground on demand — same fixpoint bit-for-bit, compute
    traded for bounded HBM.

    ``fast_rounds`` (SMP and MMP with the collective MLN): re-activation
    rounds run the *greedy closure* variant — evidence-driven
    propagation needs no entailment matrix, which is the entire O(P^3)
    cost of a full round (measured 3376x cheaper per round on the
    production-mesh dry-run).  With the fused engine those greedy
    rounds run inside a single on-device ``while_loop``; a full round
    (maximal-message inference for MMP, full collective MAP for SMP)
    runs first and again at every quiescence point, so the final
    fixpoint is closed under the full matcher on every neighborhood:
    greedy closure under evidence is sound (Prop. 6), and termination
    still requires a full round to have produced nothing new (Thm. 2/4).

    ``fused=False`` selects the legacy per-round host loop (one dispatch
    per bin per round, re-grounding every time) — the differential
    baseline for tests and ``benchmarks/table1_parallel.py``.
    """
    t0 = time.perf_counter()
    if scheme == "mmp":
        assert gg is not None and getattr(matcher, "score", None) is not None
    mesh = mesh or make_em_mesh()
    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod(mesh.devices.shape))

    if not fused:
        return _run_parallel_legacy(
            packed, matcher, gg, scheme=scheme, mesh=mesh,
            max_rounds=max_rounds, fast_rounds=fast_rounds, active=active,
            init_matches=init_matches, pool=pool, t0=t0, n_shards=n_shards,
        )

    with obs_span("rounds.stage"):
        universe = _pair_universe(packed)
        Np = len(universe)
        if Np == 0:  # no candidate pairs anywhere: nothing to resolve
            return _no_pairs_result(init_matches, t0)
        mkey = _matcher_cache_key(matcher)
        base_kind = mkey[0]
        if base_kind == "mln" and not getattr(matcher, "collective", True):
            base_kind = "mln_greedy"
        if scheme == "mmp" and base_kind not in ("mln", "mln_greedy"):
            raise TypeError(
                f"parallel MMP is wired to the MLN device promoter; kind "
                f"{base_kind!r} emits no multi-pair messages, so run_mmp "
                "(sequential) or scheme='smp' reach the identical fixpoint"
            )
        staging = _prepare_bins(packed, universe, pad_mult=n_shards)
        bins = staging.bins
        bin_ks = list(bins)  # (k, Pc) keys, sorted
        dev_uidx = {
            k: kcommon.put_sharded(bins[k].uidx, mesh, axes) for k in bin_ks
        }
        dev_pmask = {
            k: kcommon.put_sharded(bins[k].pair_mask, mesh, axes)
            for k in bin_ks
        }
        gcache = gcache if gcache is not None else GroundingCache()
        # step-7 promotion runs on device (batched delta checks, zero
        # host coupling-COO scans); the promoter counts any host fallback.
        promoter = DevicePromoter(gg) if scheme == "mmp" else None
        m_plus = init_matches if init_matches is not None else MatchStore()
        m_bits = _seed_bits(universe, m_plus)

    _rk_memo: dict[tuple, tuple | None] = {}

    def bin_row_keys(k):
        # packer row keys (streaming path) double as grounding
        # fingerprints (a row's staged layout is a function of its
        # content, and the bin key fixes its width); padding rows get a
        # stable sentinel
        if packed.row_keys is None:
            return None
        if k not in _rk_memo:
            _rk_memo[k] = tuple(
                packed.row_keys[n] if n >= 0 else ("__pad__", k)
                for n in bins[k].rows.tolist()
            )
        return _rk_memo[k]

    run_grounds: dict[tuple, tuple] = {}

    def ground_of(k):
        """Fetch one bin's grounded device arrays.

        Unbounded cache: memoized per run — exactly one ``get`` per bin
        per cover (the historical counter contract).  Bounded cache:
        fetched per dispatch, so between dispatches only the LRU's
        ``capacity`` bins stay array-resident and a cold bin re-grounds
        on demand — the run never pins every bin's ``(B, P, P)`` tensors
        for its whole lifetime.
        """
        if gcache.bounded:
            return gcache.get(mkey, k, bins[k], bin_row_keys(k))
        g = run_grounds.get(k)
        if g is None:
            g = run_grounds[k] = gcache.get(mkey, k, bins[k], bin_row_keys(k))
        return g

    # Multi-device meshes: every grounding tensor is placed split over
    # the mesh, once per (run, bin) — the cache's arrays live on one
    # device, and a shard_map over several would otherwise receive a
    # whole copy on every device at every dispatch (across processes
    # they are not even addressable).  Within a run the grounds never
    # change, and grounding is deterministic, so the bounded-cache
    # re-fetch would be bit-identical anyway.
    spread = mesh.devices.size > 1
    _global_grounds: dict[tuple, tuple] = {}

    def dispatch_grounds(k):
        if not spread:
            return ground_of(k)
        g = _global_grounds.get(k)
        if g is None:
            g = _global_grounds[k] = tuple(
                kcommon.put_sharded(a, mesh, axes) for a in ground_of(k)
            )
        return g

    evictions0 = gcache.evictions
    cold0 = gcache.cold_regrounds
    hashed0 = gcache.rows_hashed
    gcache.begin_peak_window()

    # A fused dispatch passes EVERY bin's grounded tensors to one jitted
    # program — transiently full residency, which would defeat a memory
    # bound tighter than the bin count.  In *spill mode* the run instead
    # routes everything through the per-bin full-round loop: each
    # dispatch stages one bin's arrays and releases them, so peak device
    # residency really is capacity (+ the one bin in flight) — memory
    # bought with extra dispatches and cold re-grounds, never with a
    # different fixpoint.
    spill_mode = gcache.hbm_budget_bytes is not None or (
        gcache.capacity is not None and gcache.capacity < len(bin_ks)
    )

    if pool is None:
        pool = MessagePool()
    active = (
        list(active) if active is not None else list(range(packed.num_neighborhoods))
    )
    evals = 0
    emitted = 0
    promoted_total = 0
    rounds = 0
    full_rounds = 0
    dispatches = 0
    history: list[int] = []

    def rows_for(act_list):
        """Staged bin index and row of each listed neighborhood."""
        a = np.asarray(act_list, dtype=np.int64)
        return staging.bin_of[a], staging.row_of[a]

    def masks_for(act_list):
        bi, ri = rows_for(act_list)
        masks = {}
        for i, k in enumerate(bin_ks):
            masks[k] = np.zeros(bins[k].entity_mask.shape[0], dtype=bool)
            masks[k][ri[bi == i]] = True
        return masks

    def live_rows(act_list):
        """Drop provably inert rows: a neighborhood whose every candidate
        slot is already matched can add no matches (output is a subset of
        its valid slots) and can emit no maximal messages (messages range
        over *undecided* pairs) — evaluating it in a full round is a
        no-op in every driver.  Cost is O(|act_list| slots): only the
        requested rows are inspected, so a small dirty seed set stays
        cheap on a large corpus."""
        bi, ri = rows_for(act_list)
        keep = []
        for i, k in enumerate(bin_ks):
            rows = ri[bi == i]
            bt = bins[k]
            uidx = bt.uidx[rows]
            un = bt.pair_mask[rows] & (uidx < Np) & ~m_bits[
                np.minimum(uidx, Np - 1)
            ]
            keep.append(bt.rows[rows[un.any(axis=1)]])
        return sorted(np.concatenate(keep).tolist())

    # round history buffer: one slot per possible round so EMResult
    # always has len(history) == rounds, whatever max_rounds the caller
    # picked (rounded up so the compiled shape is stable across calls)
    hist_cap = ((max_rounds + _HISTORY_CAP - 1) // _HISTORY_CAP) * _HISTORY_CAP

    def fused_call(kind, act_list, budget):
        nonlocal dispatches
        with obs_span("rounds.schedule"):
            act_masks = masks_for(act_list)
            spec = FusedSpec(
                kinds=tuple(kind for _ in bin_ks),
                ks=tuple(k for k, _ in bin_ks),
                batch=tuple(bins[k].entity_mask.shape[0] for k in bin_ks),
                num_pairs=tuple(bins[k].pair_mask.shape[1] for k in bin_ks),
                universe_size=Np,
                history_cap=hist_cap,
            )
            fn = build_fused_fn(spec, mesh, axes)
            args = []
            for k in bin_ks:
                args += list(dispatch_grounds(k))
                args += [
                    dev_uidx[k], dev_pmask[k],
                    kcommon.put_sharded(act_masks[k], mesh, axes),
                ]
            args += [
                kcommon.put_replicated(m_bits, mesh),
                kcommon.put_replicated(np.asarray(budget, np.int32), mesh),
            ]
        with obs_span("rounds.fused", kind=kind):
            bits, r, ev, hist = fn(*args)
            # int() blocks on the while_loop, so the span owns its time
            r = int(r)
            dispatches += 1
            # np.array (not asarray): callers assign this to m_bits and
            # mutate it in place, and asarray of a jax buffer is read-only
            return (np.array(bits), r, int(ev),
                    [int(h) for h in np.asarray(hist)[:r]])

    def finish():
        return EMResult(
            matches=m_plus,
            neighborhood_evals=evals,
            rounds=rounds,
            messages_emitted=emitted,
            messages_promoted=promoted_total,
            wall_time_s=time.perf_counter() - t0,
            history=history,
            dispatches=dispatches,
            full_rounds=full_rounds,
            peak_resident_bins=gcache.window_peak_bins,
            cache_evictions=gcache.evictions - evictions0,
            cold_regrounds=gcache.cold_regrounds - cold0,
            rows_hashed=gcache.rows_hashed - hashed0,
            promote_host_scans=promoter.host_scans if promoter else 0,
            candidate_slots=staging.candidate_slots,
            staged_slots=staging.staged_slots,
        )

    collective = base_kind == "mln"

    def full_round_over(act_list):
        """One host-visible full round: per-bin full-shape dispatches.
        Returns (newly matched gids, messages).  Mutates m_bits/m_plus."""
        nonlocal dispatches, evals, rounds, full_rounds, m_bits, m_plus
        with obs_span("rounds.schedule"):
            act_masks = masks_for(act_list)
            history.append(len(act_list))
            rounds += 1
            full_rounds += 1
            new_bits = m_bits.copy()
            round_msgs: list[list[int]] = []
            m_bits_dev = kcommon.put_replicated(m_bits, mesh)
        with obs_span("rounds.full", active=len(act_list)):
            for k in bin_ks:
                am = act_masks[k]
                if not am.any():
                    continue
                spec = BinRoundSpec(
                    kind=base_kind,
                    k=k[0],
                    batch=bins[k].entity_mask.shape[0],
                    num_pairs=bins[k].pair_mask.shape[1],
                    universe_size=Np,
                )
                fn = build_bin_round_fn(spec, mesh, axes)
                x, lab, bits = fn(
                    *dispatch_grounds(k), dev_uidx[k], dev_pmask[k],
                    kcommon.put_sharded(am, mesh, axes), m_bits_dev,
                )
                dispatches += 1
                evals += int(am.sum())
                new_bits |= np.asarray(bits)
                if scheme == "mmp" and collective:
                    lab = np.asarray(lab)
                    with obs_span("rounds.messages"):
                        round_msgs += _labels_to_messages(
                            bins[k].pair_gid, lab, m_plus, row_mask=am
                        )
        with obs_span("rounds.schedule"):
            newly = universe[new_bits & ~m_bits]
            m_bits = new_bits
            m_plus = m_plus.union(newly)
        return newly, round_msgs

    def promote():
        """Step 7 over the pool; folds what it promotes into m_plus and
        m_bits and returns those gids (None when nothing was promoted)."""
        nonlocal m_plus, promoted_total
        m_plus2, promoted = promoter.promote(pool, m_plus)
        promoted_total += promoted
        if not promoted:
            return None
        with obs_span("rounds.schedule"):
            extra = m_plus2.difference(m_plus)
            m_plus = m_plus2
            _set_bits(m_bits, universe, extra)
        return extra

    if scheme == "nomp":
        # one round, no exchange: a single fused dispatch for cheap
        # matchers, one full-shape dispatch per bin for the collective
        # MLN (shares the compiled full-round programs with SMP/MMP) —
        # and per bin in spill mode, where an all-bins fused dispatch
        # would transiently materialize every bin's tensors.
        if active:
            if collective or spill_mode:
                full_round_over(active)
            else:
                bits, rounds, evals, history = fused_call(base_kind, active, 1)
                with obs_span("rounds.schedule"):
                    m_plus = m_plus.union(universe[bits & ~m_bits])
        return finish()

    if scheme == "smp" and not collective and not spill_mode:
        # greedy/rules matchers: the whole multi-round closure is ONE
        # fused dispatch — every round body is a cheap batched fixpoint.
        # (In spill mode this falls through to the per-bin round loop
        # below, which stages one bin's tensors at a time.)
        if active:
            bits, rounds, evals, history = fused_call(
                base_kind, active, max_rounds
            )
            with obs_span("rounds.schedule"):
                m_plus = m_plus.union(universe[bits & ~m_bits])
        return finish()

    # -- SMP and MMP: host-visible full rounds + fused greedy segments. ---
    # Re-activation rounds only propagate evidence, so they run as
    # greedy closure inside the fused device loop; a full round over
    # every neighborhood runs at each quiescence point (and first), so
    # the fixpoint is closed under the full matcher — the same soundness
    # argument as MMP's fast_rounds (Prop. 6 + Thm. 2/4), now shared by
    # SMP.  Spill mode disables the fused segments outright (they stage
    # every bin at once): each round is per-bin full dispatches, the
    # memory-for-dispatches trade of a bounded cache.
    greedy_ok = fast_rounds and collective and not spill_mode
    full_round = True
    seeds = list(active)
    bits0 = m_bits.copy()

    def certify_rows():
        """Neighborhoods a quiescence full round must re-check: the
        seeds plus every neighborhood slot-incident to a bit set during
        this run.  Any other neighborhood was at the carried fixpoint
        with unchanged evidence projection, so the full matcher can add
        nothing there — on the streaming path this keeps quiescence
        checks O(dirty + affected), not O(unresolved corpus)."""
        cand = set(seeds)
        changed = universe[m_bits & ~bits0]
        if len(changed):
            cand.update(packed.neighborhoods_of_slot_pairs(changed))
        return sorted(cand)

    with obs_span("rounds.schedule"):
        active = live_rows(active)
    if scheme == "mmp" and seeds and not active:
        # every seed is inert, but the (streaming-persistent) pool must
        # still be replayed against the current grounding — exactly what
        # run_mmp's step 7 does after evaluating those seeds
        extra = promote()
        if extra is not None:
            with obs_span("rounds.schedule"):
                active = packed.neighborhoods_of_slot_pairs(extra)
    while active and rounds < max_rounds:
        if greedy_ok and not full_round:
            bits, r, ev, hist = fused_call(
                "mln_greedy", active, max_rounds - rounds
            )
            with obs_span("rounds.schedule"):
                rounds += r
                evals += ev
                history += hist
                newly = universe[bits & ~m_bits]
                m_bits = bits
                m_plus = m_plus.union(newly)
            if scheme == "mmp":
                extra = promote()
                if extra is not None:
                    with obs_span("rounds.schedule"):
                        active = packed.neighborhoods_of_slot_pairs(extra)
                    if active:
                        continue
            # greedy closure quiescent: one full round over every
            # certifiable neighborhood that still has an undecided
            # candidate slot (fresh maximal messages / collective
            # promotions) before declaring the fixpoint
            full_round = True
            with obs_span("rounds.schedule"):
                active = live_rows(certify_rows())
            continue

        newly, round_msgs = full_round_over(active)
        if scheme == "mmp":
            with obs_span("rounds.schedule"):
                for msg in round_msgs:
                    pool.add_message(msg)
                    emitted += 1
            extra = promote()
            if extra is not None:
                newly = np.unique(np.concatenate([newly, extra]))
        with obs_span("rounds.schedule"):
            active = (
                packed.neighborhoods_of_slot_pairs(newly) if len(newly) else []
            )
        if greedy_ok and active:
            full_round = False
    return finish()


def _run_parallel_legacy(
    packed: PackedCover,
    matcher,
    gg: GlobalGrounding | None,
    *,
    scheme: str,
    mesh: Mesh,
    max_rounds: int,
    fast_rounds: bool,
    active: list[int] | None,
    init_matches: MatchStore | None,
    pool: MessagePool | None,
    t0: float,
    n_shards: int,
) -> EMResult:
    """The pre-fusion host round loop: one dispatch per bin per round,
    re-grounding from raw tensors every time, per-row message walks.
    Kept as the differential baseline (tests assert bit-for-bit equality
    with the fused engine; ``table1_parallel`` reports the speedup)."""
    axes = tuple(mesh.axis_names)
    universe = _pair_universe(packed)
    Np = len(universe)
    if Np == 0:
        return _no_pairs_result(init_matches, t0)
    # the full upper-triangle layout, as the cover packed it
    uidx = {k: _universe_index(universe, nb.pair_gid)
            for k, nb in packed.bins.items()}

    m_plus = init_matches if init_matches is not None else MatchStore()
    m_bits = _seed_bits(universe, m_plus)
    if pool is None:
        pool = MessagePool()
    active = (
        list(active) if active is not None else list(range(packed.num_neighborhoods))
    )
    evals = 0
    emitted = 0
    promoted_total = 0
    rounds = 0
    dispatches = 0
    host_scans = 0
    history: list[int] = []

    # MMP fast rounds: greedy closure for re-activations, full maximal-
    # message inference on the first round and at each quiescence point.
    full_round = True

    while active and rounds < max_rounds:
        history.append(len(active))
        rounds += 1
        new_bits = m_bits.copy()
        round_msgs: list[list[int]] = []
        use_greedy = (
            scheme == "mmp" and fast_rounds and not full_round
            and isinstance(matcher, MLNMatcher) and matcher.collective
        )
        for k, rows in sorted(packed.rows_for(active).items()):
            nb = packed.bins[k]
            sel = (
                nb.entity_mask[rows],
                nb.coauthor[rows],
                nb.sim_level[rows].astype(np.int8),
                nb.pair_mask[rows],
                uidx[k][rows],
            )
            gid_rows = nb.pair_gid[rows]
            n_rows = len(rows)
            padded = _pad_rows(list(sel), n_shards)
            spec = _matcher_spec(matcher, k, Np)
            if use_greedy:
                spec = dataclasses.replace(spec, matcher_kind="mln_greedy")
            fn = build_round_fn(spec, mesh, axes)
            x, lab, bits = fn(*padded, jnp.asarray(m_bits))
            dispatches += 1
            x = np.asarray(x)[:n_rows]
            lab = np.asarray(lab)[:n_rows]
            new_bits |= np.asarray(bits)
            evals += n_rows
            if scheme == "mmp":
                round_msgs.extend(_labels_to_messages(gid_rows, lab, m_plus))
            if scheme == "nomp":
                # no exchange: collect matches directly, never re-activate
                for r in range(n_rows):
                    sel_gids = gid_rows[r][x[r] & (gid_rows[r] >= 0)]
                    m_plus = m_plus.union(sel_gids)

        if scheme == "nomp":
            break

        newly = universe[new_bits & ~m_bits]
        m_bits = new_bits
        m_plus = m_plus.union(newly)

        if scheme == "mmp":
            for msg in round_msgs:
                pool.add_message(msg)
                emitted += 1
            m_plus2, promoted = _promote(pool, gg, m_plus)
            host_scans += 1
            promoted_total += promoted
            if promoted:
                extra = m_plus2.difference(m_plus)
                newly = np.unique(np.concatenate([newly, extra]))
                m_plus = m_plus2
                _set_bits(m_bits, universe, extra)

        active = packed.neighborhoods_of_pairs(newly) if len(newly) else []

        if scheme == "mmp" and fast_rounds:
            if active:
                full_round = False  # evidence to propagate: greedy rounds
            elif use_greedy or not full_round:
                # quiescent after greedy rounds: one full round to emit
                # fresh maximal messages before declaring the fixpoint
                full_round = True
                active = list(range(packed.num_neighborhoods))

    return EMResult(
        matches=m_plus,
        neighborhood_evals=evals,
        rounds=rounds,
        messages_emitted=emitted,
        messages_promoted=promoted_total,
        wall_time_s=time.perf_counter() - t0,
        history=history,
        dispatches=dispatches,
        promote_host_scans=host_scans,
    )
