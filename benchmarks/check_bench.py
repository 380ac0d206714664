"""CI gate: fail if scale-robust perf invariants regress.

``python -m benchmarks.check_bench [--gate=NAME] BASELINE.json FRESH.json``

Three baselines are gated, dispatched on the JSON's ``benchmark`` field.
Each invariant family is a :class:`Gate` in a registry keyed by name and
baseline family; ``--gate=all`` (the default, and what CI runs — one
invocation per baseline file) runs every gate that applies to the file,
``--gate=NAME`` restricts to one for local triage:

* ``BENCH_parallel.json``
  - ``dispatch``: the ``dispatches_per_round`` of every scheme, bounded
    by O(bins + quiescence points) per round with the bin count capped
    by ``DEFAULT_BINS``, so a smoke-scale run is comparable to the
    committed default-scale baseline.  A regression to an
    O(bins x rounds) dispatch pattern blows well past the slack.
  - ``promotion``: ``promote_host_scans`` of the fused engine must be
    exactly 0 — step-7 delta checks run batched on device
    (``repro.core.parallel.DevicePromoter``); any host coupling-COO
    walk is a regression, no slack.
  - ``matchers``: the ``fig4_matchers`` block — every matcher family in
    the baseline must still be measured, the optimal assignment must
    keep its quality edge over the greedy ablation, and no family's F1
    may regress below the baseline minus slack (quality is a model
    property of the corpus, so it is stable across machine speeds).
* ``BENCH_stream.json``
  - ``stream``: the O(dirty) ingest-path ratios — ``splice_per_dirty``
    (cover rows staged per dirty neighborhood), ``splice_per_visit``
    (grounding array rows spliced per pair visited) and
    ``growth_copy_per_row`` (backing-buffer rows memcpy'd per row
    placed; amortized O(1) under capacity doubling, O(bin) per append
    under the old per-ingest ``np.concatenate``).  All ~O(1) by
    construction and gated as max-over-entries so smoke batch sizes
    need not match the committed grid.
  - ``lru``: the bounded-serving-memory block — peak array-resident
    bins must not exceed the configured LRU capacity (exact, no slack),
    the eviction path must have actually fired, and promotion must have
    done zero host scans.
  - ``transfer``: the device-transfer block — host->device upload bytes
    per unit of per-site work (``gcache_upload_per_reground_row``,
    ``promoter_upload_per_pair_ingest``,
    ``prepare_upload_per_row_ingest``, from the
    ``transfer.{gcache,promoter,prepare}_bytes`` registry counters).
    Per-unit byte cost is bounded by the bin shapes, so the ratios are
    comparable across corpus scales; a regression to O(corpus)
    re-uploads per ingest multiplies them far past the slack.
  - ``recovery``: the durability block — the WAL append overhead must
    stay under 10% of the ingest p50 (fsync-per-append riding on a
    much larger delta+fixpoint cost), and recovery (snapshot restore +
    WAL tail replay) must have reached the uninterrupted run's state
    digest bit-for-bit (``fixpoint_equal``).  Absolute bounds, not
    baseline-relative: both hold at every corpus scale.
  - ``tails``: the serving block — coalesced ingest throughput must
    beat the per-arrival synchronous baseline by the speedup floor
    (5x at full scale; a lower absolute floor at smoke scale, where
    tiny corpora shrink the fixed per-ingest cost being amortized and
    the fresh JSON's ``smoke`` flag says which regime applies), the
    readers must actually have sampled latency, and the resolve p99
    under concurrent load is gated baseline-relative with generous
    slack (CI boxes are noisy; losing the lock-free read path
    multiplies p99 by ingest wall time, far past it).
* ``BENCH_shard.json``
  - ``shard``: the sharded-serving block — the state digest must be
    identical across shard counts {1, 2, 4} (bit-for-bit the
    single-host fixpoint; absolute, no slack), every replica set must
    agree among itself, ingest throughput and aggregate resolve QPS
    must be present and positive, and the 2-shard QPS scaling
    efficiency must clear an absolute floor wherever the measuring
    host has >= 2 cores (reads are replica-local, so read capacity is
    the axis that scales with the shard count).  A missing
    ``BENCH_shard.json`` fails the step loudly rather than reading as
    "gate does not apply".

Wall times are recorded in the JSON for the trajectory but never gated
(CI machines are noisy).
"""

from __future__ import annotations

import dataclasses
import json
import sys
from typing import Callable

# Multiplicative + additive slack: quiescence-point counts can shift by
# a round or two between corpus scales; a true regression to an
# O(bins x rounds) dispatch pattern blows well past this.
REL_SLACK = 1.5
ABS_SLACK = 2.0

# Stream splice ratios are ~O(1); corpus-scale effects (totality-group /
# leftover-chunk churn) shift them by fractions, a full-restage
# regression multiplies them by the cover/pair count.
STREAM_REL_SLACK = 2.0
STREAM_ABS_SLACK = 1.0

# Matcher quality: F1 on the synthetic corpora is deterministic up to
# tiny tie-break drift between numpy versions; a family losing its
# separation (or dropping out of the benchmark) moves far past this.
MATCHERS_F1_ABS_SLACK = 0.02


@dataclasses.dataclass(frozen=True)
class Gate:
    """One named invariant family over (baseline, fresh) JSON blobs.

    ``family`` names the baseline file the gate reads (``parallel`` /
    ``stream`` / ``shard``, dispatched on the fresh JSON's ``benchmark``
    field), so ``--gate=all`` runs exactly the gates that apply."""

    name: str
    family: str
    fn: Callable[[dict, dict, list], None]


_GATES: dict[str, Gate] = {}


def register_gate(name: str, family: str):
    """Register ``fn(base, fresh, failures)`` as the gate ``name``."""

    def deco(fn):
        _GATES[name] = Gate(name=name, family=family, fn=fn)
        return fn

    return deco


def gate_names() -> tuple[str, ...]:
    return tuple(_GATES)

# Durability: fsync-per-append rides on a much larger delta+fixpoint
# ingest; a WAL that costs a tenth of the ingest p50 means the append
# path regressed (e.g. re-pickling state instead of the batch).
RECOVERY_MAX_WAL_OVERHEAD_FRAC = 0.10

# Serving coalescing: the full-scale speedup floor is the acceptance
# bar (>= 5x over per-arrival ingest); smoke corpora amortize a much
# smaller fixed cost, so CI gates a lower absolute floor there.
TAILS_MIN_SPEEDUP = 5.0
TAILS_SMOKE_MIN_SPEEDUP = 1.5
# p99 resolve latency under concurrent load, baseline-relative: the
# lock-free read path is ~fixed cost; regressing to reads that wait on
# an in-flight ingest multiplies p99 by ingest wall time.
TAILS_P99_REL_SLACK = 3.0
TAILS_P99_ABS_SLACK = 1.0  # ms

# Transfer ratios: per-unit byte costs shift with bin-shape mix between
# corpus scales; an O(corpus)-re-upload regression scales them with the
# corpus, far past this.
TRANSFER_REL_SLACK = 2.0
TRANSFER_ABS_SLACK = 64.0  # bytes per unit

# Sharded serving: aggregate resolve QPS at 2 shards must retain this
# fraction of perfect 2x scaling.  Reads are replica-local (no
# collectives), so losing the floor means reads started waiting on
# cross-shard state.  Only enforced where two shards can actually run
# in parallel (cpu_count >= 2, recorded in the fresh JSON) — N
# co-scheduled replicas on one core timeshare it.
SHARD_MIN_QPS_EFF_2 = 0.35


@register_gate("dispatch", "parallel")
def _check_dispatch(base: dict, fresh: dict, failures: list[str]) -> None:
    for inst, iblock in base.get("instances", {}).items():
        fblock = fresh.get("instances", {}).get(inst, {})
        for scheme, b in iblock.get("schemes", {}).items():
            tag = f"{inst}/{scheme}"
            got = fblock.get("schemes", {}).get(scheme)
            if got is None:
                failures.append(f"{tag}: missing from fresh results")
                continue
            limit = b["dispatches_per_round"] * REL_SLACK + ABS_SLACK
            if got["dispatches_per_round"] > limit:
                failures.append(
                    f"{tag}: dispatches_per_round "
                    f"{got['dispatches_per_round']} > limit {limit:.2f} "
                    f"(baseline {b['dispatches_per_round']})"
                )
            else:
                print(
                    f"ok {tag}: dispatches_per_round "
                    f"{got['dispatches_per_round']} <= {limit:.2f}"
                )


@register_gate("promotion", "parallel")
def _check_promotion_parallel(
    _base: dict, fresh: dict, failures: list[str]
) -> None:
    """Fused engine: zero host promotion scans, exact (no slack)."""
    checked = 0
    for inst, iblock in fresh.get("instances", {}).items():
        for scheme, got in iblock.get("schemes", {}).items():
            tag = f"{inst}/{scheme}"
            scans = got.get("promote_host_scans")
            if scans is None:
                failures.append(f"{tag}: promote_host_scans missing")
                continue
            checked += 1
            if scans != 0:
                failures.append(
                    f"{tag}: promote_host_scans {scans} != 0 — the fused "
                    "engine fell back to the host coupling-COO walk"
                )
            else:
                print(f"ok {tag}: promote_host_scans == 0")
    if not checked:
        failures.append("promotion: no schemes found in fresh results")


def _max_ratio(entries: list[dict], key: str) -> float | None:
    vals = [e[key] for e in entries if key in e]
    return max(vals) if vals else None


@register_gate("stream", "stream")
def _check_stream_ratios(base: dict, fresh: dict, failures: list[str]) -> None:
    for block, key in (
        ("throughput", "splice_per_dirty"),
        ("throughput", "growth_copy_per_row"),
        ("grounding", "splice_per_visit"),
    ):
        b = _max_ratio(base.get(block, []), key)
        got = _max_ratio(fresh.get(block, []), key)
        tag = f"stream/{block}"
        if b is None:
            failures.append(f"{tag}: {key} missing from baseline")
            continue
        if got is None:
            failures.append(f"{tag}: {key} missing from fresh results")
            continue
        limit = b * STREAM_REL_SLACK + STREAM_ABS_SLACK
        if got > limit:
            failures.append(
                f"{tag}: {key} {got} > limit {limit:.2f} (baseline {b})"
            )
        else:
            print(f"ok {tag}: {key} {got} <= {limit:.2f}")


@register_gate("lru", "stream")
def _check_lru(_base: dict, fresh: dict, failures: list[str]) -> None:
    """Bounded serving memory: exact bounds, independent of baseline."""
    entries = fresh.get("serving_memory", [])
    if not entries:
        failures.append("serving_memory: block missing from fresh results")
        return
    for e in entries:
        cap = e.get("lru_capacity")
        peak = e.get("peak_resident_bins")
        tag = f"stream/serving_memory[capacity={cap}]"
        if cap is None or peak is None:
            failures.append(f"{tag}: lru_capacity/peak_resident_bins missing")
            continue
        if peak > cap:
            failures.append(
                f"{tag}: peak_resident_bins {peak} > capacity {cap} — the "
                "LRU bound did not hold"
            )
        else:
            print(f"ok {tag}: peak_resident_bins {peak} <= {cap}")
        if e.get("n_bins", 0) > cap and not e.get("evictions", 0):
            failures.append(
                f"{tag}: no evictions despite {e.get('n_bins')} bins — the "
                "eviction path was not exercised"
            )
        scans = e.get("promote_host_scans")
        if scans is None:
            failures.append(f"{tag}: promote_host_scans missing")
        elif scans != 0:
            failures.append(f"{tag}: promote_host_scans {scans} != 0")
        else:
            print(f"ok {tag}: promote_host_scans == 0")


@register_gate("transfer", "stream")
def _check_transfer(base: dict, fresh: dict, failures: list[str]) -> None:
    """Upload bytes per unit of per-site work, baseline-relative."""
    base_entries = base.get("transfer", [])
    fresh_entries = fresh.get("transfer", [])
    if not fresh_entries:
        failures.append("transfer: block missing from fresh results")
        return
    if not base_entries:
        failures.append("transfer: block missing from baseline")
        return
    for key in (
        "gcache_upload_per_reground_row",
        "promoter_upload_per_pair_ingest",
        "prepare_upload_per_row_ingest",
    ):
        b = _max_ratio(base_entries, key)
        got = _max_ratio(fresh_entries, key)
        tag = "stream/transfer"
        if b is None:
            failures.append(f"{tag}: {key} missing from baseline")
            continue
        if got is None:
            failures.append(f"{tag}: {key} missing from fresh results")
            continue
        limit = b * TRANSFER_REL_SLACK + TRANSFER_ABS_SLACK
        if got > limit:
            failures.append(
                f"{tag}: {key} {got} > limit {limit:.2f} (baseline {b})"
            )
        else:
            print(f"ok {tag}: {key} {got} <= {limit:.2f}")
    # the accounting itself must have seen traffic: a parallel-engine
    # ingest run with zero recorded bytes means the counters came unwired
    for key in ("gcache_bytes", "prepare_bytes"):
        got = _max_ratio(fresh_entries, key)
        if not got:
            failures.append(
                f"stream/transfer: {key} is 0/missing — transfer "
                "accounting not recording"
            )
        else:
            print(f"ok stream/transfer: {key} {got} > 0")


@register_gate("recovery", "stream")
def _check_recovery(_base: dict, fresh: dict, failures: list[str]) -> None:
    """Durability block: WAL overhead bound + bit-for-bit replay."""
    entries = fresh.get("recovery", [])
    if not entries:
        failures.append("recovery: block missing from fresh results")
        return
    for e in entries:
        tag = f"stream/recovery[batch_size={e.get('batch_size')}]"
        frac = e.get("wal_overhead_frac")
        if frac is None:
            failures.append(f"{tag}: wal_overhead_frac missing")
        elif frac >= RECOVERY_MAX_WAL_OVERHEAD_FRAC:
            failures.append(
                f"{tag}: wal_overhead_frac {frac} >= "
                f"{RECOVERY_MAX_WAL_OVERHEAD_FRAC} — the WAL append is no "
                "longer a small fraction of the ingest"
            )
        else:
            print(
                f"ok {tag}: wal_overhead_frac {frac} < "
                f"{RECOVERY_MAX_WAL_OVERHEAD_FRAC}"
            )
        if e.get("fixpoint_equal") is not True:
            failures.append(
                f"{tag}: fixpoint_equal is "
                f"{e.get('fixpoint_equal')!r} — recovery did not reach the "
                "uninterrupted run's state digest"
            )
        else:
            print(f"ok {tag}: fixpoint_equal (snapshot + WAL tail replay)")
        if not e.get("replayed_records"):
            failures.append(
                f"{tag}: replayed_records is 0/missing — the WAL tail "
                "replay was not exercised"
            )
        else:
            print(f"ok {tag}: replayed {e['replayed_records']} WAL records")


@register_gate("tails", "stream")
def _check_tails(base: dict, fresh: dict, failures: list[str]) -> None:
    """Serving block: coalescing speedup floor + p99 under load."""
    entries = fresh.get("serving", [])
    if not entries:
        failures.append("serving: block missing from fresh results")
        return
    floor = (
        TAILS_SMOKE_MIN_SPEEDUP if fresh.get("smoke") else TAILS_MIN_SPEEDUP
    )
    base_p99 = _max_ratio(base.get("serving", []), "p99_ms")
    for e in entries:
        tag = f"stream/serving[n_requests={e.get('n_requests')}]"
        speedup = e.get("speedup")
        if speedup is None:
            failures.append(f"{tag}: speedup missing")
        elif speedup < floor:
            failures.append(
                f"{tag}: coalescing speedup {speedup} < floor {floor} "
                "over per-arrival synchronous ingest"
            )
        else:
            print(f"ok {tag}: speedup {speedup} >= {floor}")
        if not e.get("queries"):
            failures.append(
                f"{tag}: no reader queries recorded — the concurrent-load "
                "latency measurement did not run"
            )
            continue
        p99 = e.get("p99_ms")
        if p99 is None:
            failures.append(f"{tag}: p99_ms missing")
            continue
        if base_p99 is None:
            failures.append("stream/serving: p99_ms missing from baseline")
            continue
        limit = base_p99 * TAILS_P99_REL_SLACK + TAILS_P99_ABS_SLACK
        if p99 > limit:
            failures.append(
                f"{tag}: resolve p99 under load {p99}ms > limit "
                f"{limit:.2f}ms (baseline {base_p99}ms)"
            )
        else:
            print(f"ok {tag}: p99 under load {p99}ms <= {limit:.2f}ms")


@register_gate("matchers", "parallel")
def _check_matchers(base: dict, fresh: dict, failures: list[str]) -> None:
    """fig4_matchers block: family coverage + quality separation."""
    bblock = base.get("fig4_matchers", {}).get("families", {})
    fblock = fresh.get("fig4_matchers", {}).get("families", {})
    if not bblock:
        failures.append("matchers: fig4_matchers block missing from baseline")
        return
    if not fblock:
        failures.append(
            "matchers: fig4_matchers block missing from fresh results"
        )
        return
    for fam, b in sorted(bblock.items()):
        tag = f"matchers/{fam}"
        got = fblock.get(fam)
        if got is None:
            failures.append(f"{tag}: family missing from fresh results")
            continue
        floor = b["f1"] - MATCHERS_F1_ABS_SLACK
        if got["f1"] < floor:
            failures.append(
                f"{tag}: f1 {got['f1']} < floor {floor:.3f} "
                f"(baseline {b['f1']})"
            )
        else:
            print(f"ok {tag}: f1 {got['f1']} >= {floor:.3f}")
    opt = fblock.get("hungarian")
    greedy = fblock.get("hungarian_greedy")
    if opt is not None and greedy is not None:
        if opt["f1"] < greedy["f1"]:
            failures.append(
                f"matchers: hungarian f1 {opt['f1']} < greedy "
                f"{greedy['f1']} — optimal assignment lost its edge"
            )
        else:
            print(
                f"ok matchers: hungarian f1 {opt['f1']} >= greedy "
                f"{greedy['f1']}"
            )


@register_gate("shard", "shard")
def _check_shard(base: dict, fresh: dict, failures: list[str]) -> None:
    """Sharded-serving block: bit-for-bit digest equality across shard
    counts (absolute — the ISSUE-9 equivalence bar at benchmark scale)
    plus the read-capacity scaling floor at 2 shards."""
    entries = fresh.get("shards", [])
    if not entries:
        failures.append("shard: 'shards' block missing from fresh results")
        return
    want = {e.get("n_shards") for e in base.get("shards", [])} or {1, 2, 4}
    got_counts = {e.get("n_shards") for e in entries}
    missing = want - got_counts
    if missing:
        failures.append(
            f"shard: shard counts {sorted(missing)} missing from fresh "
            f"results (have {sorted(got_counts)})"
        )
    by_n = {e["n_shards"]: e for e in entries}
    for n, e in sorted(by_n.items()):
        tag = f"shard[n={n}]"
        for key in ("refs", "ingest_refs_per_s", "resolve_qps_total"):
            if not e.get(key):
                failures.append(f"{tag}: {key} is 0/missing")
        if not e.get("digest"):
            failures.append(f"{tag}: digest missing")
        if e.get("replicas_agree") is not True:
            failures.append(
                f"{tag}: replicas_agree is {e.get('replicas_agree')!r} — "
                "the cross-replica digest all-gather disagreed"
            )
        else:
            print(f"ok {tag}: {e.get('ingest_refs_per_s')} refs/s ingest, "
                  f"{e.get('resolve_qps_total')} QPS, replicas agree")
    digests = {e.get("digest") for e in entries}
    if len(digests) != 1:
        failures.append(
            "shard: state digests diverged across shard counts — the "
            "sharded fixpoint is not bit-for-bit the single-host one: "
            + ", ".join(
                f"n={n}:{str(e.get('digest'))[:12]}"
                for n, e in sorted(by_n.items())
            )
        )
    else:
        print(f"ok shard: one digest across {sorted(by_n)} shards "
              "(bit-for-bit the 1-shard fixpoint)")
    e2 = by_n.get(2)
    if e2 is not None:
        eff = e2.get("qps_scaling_eff")
        if eff is None:
            failures.append("shard[n=2]: qps_scaling_eff missing")
        elif (fresh.get("cpu_count") or 1) < 2:
            print(f"note shard[n=2]: qps_scaling_eff {eff} not gated — "
                  f"measured on cpu_count={fresh.get('cpu_count')}, two "
                  "shards cannot run in parallel there")
        elif eff < SHARD_MIN_QPS_EFF_2:
            failures.append(
                f"shard[n=2]: qps_scaling_eff {eff} < floor "
                f"{SHARD_MIN_QPS_EFF_2} — aggregate resolve QPS no longer "
                "scales with the shard count"
            )
        else:
            print(f"ok shard[n=2]: qps_scaling_eff {eff} >= "
                  f"{SHARD_MIN_QPS_EFF_2}")


def _baseline_family(fresh: dict) -> str:
    """Which baseline file the fresh JSON belongs to."""
    if fresh.get("benchmark") == "shard_scaling" or "shards" in fresh:
        return "shard"
    if fresh.get("benchmark") == "stream_throughput" or "throughput" in fresh:
        return "stream"
    return "parallel"


def main(argv: list[str]) -> int:
    gate = "all"
    args = []
    for a in argv:
        if a.startswith("--gate="):
            gate = a.split("=", 1)[1]
        else:
            args.append(a)
    if gate != "all" and gate not in _GATES:
        print(f"unknown gate {gate!r}; choose from {gate_names()} or all")
        return 2
    if len(args) != 2:
        print(__doc__)
        return 2
    try:
        with open(args[0]) as f:
            base = json.load(f)
        with open(args[1]) as f:
            fresh = json.load(f)
    except OSError as e:
        # a gated baseline that was never produced must fail its CI
        # step loudly, not slip through as "gate does not apply"
        print(f"BENCH GATE INPUT MISSING: {e}")
        return 1
    family = _baseline_family(fresh)
    to_run = [
        g for g in _GATES.values()
        if g.family == family and gate in ("all", g.name)
    ]
    if not to_run:
        print(f"gate {gate!r} does not apply to {args[1]}")
        return 2
    failures: list[str] = []
    for g in to_run:
        g.fn(base, fresh, failures)
    if failures:
        print("BENCH REGRESSION:\n  " + "\n  ".join(failures))
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
