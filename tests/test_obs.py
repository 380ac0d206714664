"""Runtime observability (repro.obs): registry, spans, exporters.

Covers the ISSUE-6 acceptance surface:

* exact nearest-rank percentiles over raw histogram samples;
* registry and span-log thread-safety under ``ResolveService``
  concurrent readers (the serving read path records latency samples
  from many threads while ingests commit);
* span nesting/ordering through a real end-to-end ingest (the
  ``ingest -> {lsh, replay, cover_splice, rounds, commit}`` taxonomy);
* device-transfer accounting plumbed through ``IngestReport``;
* registry-backed counters staying consistent with the dataclass views;
* tracing overhead on the ingest path bounded (<5% + noise slack);
* Chrome-trace/JSON exporters producing parseable output, on the
  profiler's Unix-epoch clock;
* the round engine's span tree under one ``em.run`` root and its
  ``trace_id``, spans inside a ``jax.profiler`` trace, and the
  ``compile`` span / ``compile.*`` counters with tracing on and off.
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.data.synthetic import arrival_stream
from repro.obs.registry import MetricsRegistry
from repro.stream import ResolveService


@pytest.fixture(autouse=True)
def _fresh_registry():
    obs.reset()
    obs.get_registry().set_tracing(True)
    yield
    obs.get_registry().set_tracing(True)


def _stream(ds, n_batches, **kwargs):
    batches = arrival_stream(ds, n_batches)
    svc = ResolveService(**kwargs)
    for b in batches:
        svc.ingest(b.names, b.edges, ids=b.ids)
    return svc


# ---------------------------------------------------------------------------
# Histogram: exact percentiles, reservoir degradation
# ---------------------------------------------------------------------------


def test_histogram_percentiles_exact():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in range(1, 101):  # 1..100, shuffled order must not matter
        h.observe(((v * 37) % 100) + 1)
    s = h.summary()
    assert s["count"] == 100
    assert s["min"] == 1 and s["max"] == 100
    assert s["p50"] == 50
    assert s["p90"] == 90
    assert s["p99"] == 99
    assert h.percentile(100) == 100
    assert h.percentile(0) == 1  # nearest-rank: rank clamps to 1


def test_histogram_single_sample_and_empty():
    reg = MetricsRegistry()
    h = reg.histogram("x")
    assert h.summary()["p99"] == 0.0
    h.observe(42.0)
    s = h.summary()
    assert s["p50"] == s["p99"] == 42.0
    assert s["mean"] == 42.0


def test_histogram_reservoir_keeps_exact_aggregates():
    reg = MetricsRegistry()
    h = reg.histogram("r")
    h.max_samples = 64  # force the reservoir path
    n = 1000
    for v in range(n):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == n
    assert s["sum"] == sum(range(n))
    assert s["min"] == 0.0 and s["max"] == float(n - 1)
    assert len(h.samples) == 64  # bounded
    # percentiles degrade to an estimate but stay inside the value range
    assert 0.0 <= s["p50"] <= n - 1


def test_counter_gauge_and_reset_keep_cached_refs():
    reg = obs.get_registry()
    c = reg.counter("x.count")
    g = reg.gauge("x.peak")
    c.inc(5)
    g.max(3)
    g.max(2)  # high-water: must not lower
    assert reg.value("x.count") == 5
    assert reg.snapshot()["gauges"]["x.peak"] == 3
    obs.reset()
    # cached instrument references survive reset and stay wired in
    c.inc(2)
    assert reg.value("x.count") == 2
    assert reg.snapshot()["gauges"]["x.peak"] == 0.0


# ---------------------------------------------------------------------------
# Spans: nesting, disable, cap
# ---------------------------------------------------------------------------


def test_span_nesting_records_parent_and_depth():
    reg = obs.get_registry()
    with obs.span("outer"):
        with obs.span("inner"):
            time.sleep(0.001)
    spans = {s.name: s for s in reg.spans}
    assert spans["inner"].parent == "outer"
    assert spans["inner"].depth == 1
    assert spans["outer"].parent is None and spans["outer"].depth == 0
    # the child closes first and lies inside the parent's window
    assert spans["inner"].t_start >= spans["outer"].t_start
    assert (spans["inner"].t_start + spans["inner"].dur_s
            <= spans["outer"].t_start + spans["outer"].dur_s + 1e-9)


def test_span_disabled_is_noop():
    reg = obs.get_registry()
    reg.set_tracing(False)
    with obs.span("quiet", arg=1) as s:
        s.set(more=2)
    assert reg.spans == []


def test_span_log_cap_drops_oldest():
    reg = MetricsRegistry(max_spans=8)
    for i in range(20):
        with obs.span(f"s{i}", registry=reg):
            pass
    assert len(reg.spans) == 8
    assert reg.spans_dropped == 12
    assert reg.spans[-1].name == "s19"  # newest survives
    assert reg.snapshot()["spans_dropped"] == 12


# ---------------------------------------------------------------------------
# End-to-end: one ingest produces the span taxonomy + counters
# ---------------------------------------------------------------------------


def test_e2e_ingest_spans_and_counters(hepth_small):
    svc = _stream(hepth_small, 3, scheme="mmp")
    assert len(svc.reports) == 3
    snap = obs.get_registry().snapshot()
    c = snap["counters"]
    assert c["ingest.count"] == 3
    # registry-backed counters agree with the dataclass views
    assert c.get("ingest.neighborhood_evals", 0) == sum(
        r.neighborhood_evals for r in svc.reports
    )
    assert c.get("ingest.cover_splice_rows", 0) == sum(
        r.cover_splice_rows for r in svc.reports
    )
    assert c.get("ingest.grounding_splice_rows", 0) == sum(
        r.grounding_splice_rows for r in svc.reports
    )
    # per-stage spans, rolled up per name, one entry per ingest
    for name in ("ingest", "ingest.lsh", "ingest.replay",
                 "ingest.cover_splice", "ingest.grounding_splice",
                 "ingest.rounds", "ingest.commit"):
        assert snap["spans"][name]["count"] == 3, name
    # parent links form the documented tree
    by_name = {}
    for s in obs.get_registry().spans:
        by_name.setdefault(s.name, s)
    for child in ("ingest.lsh", "ingest.replay", "ingest.cover_splice",
                  "ingest.grounding_splice", "ingest.rounds",
                  "ingest.commit"):
        assert by_name[child].parent == "ingest", child
    # the ingest wall-clock histogram has one sample per ingest and the
    # stage spans sum to no more than the root span
    assert snap["histograms"]["ingest.wall_ms"]["count"] == 3
    stage_total = sum(
        snap["spans"][n]["total_s"]
        for n in snap["spans"] if n.startswith("ingest.")
    )
    assert stage_total <= snap["spans"]["ingest"]["total_s"] + 0.05


def test_e2e_parallel_ingest_transfer_accounting(hepth_small):
    svc = _stream(hepth_small, 2, scheme="mmp", parallel=True)
    snap = obs.get_registry().snapshot()
    c = snap["counters"]
    # the parallel engine stages bins and grounds rows -> bytes recorded
    assert c.get("transfer.prepare_bytes", 0) > 0
    assert c.get("transfer.gcache_bytes", 0) > 0
    assert obs.total_upload_bytes() == sum(
        c.get(f"transfer.{s}_bytes", 0) for s in ("gcache", "promoter",
                                                  "prepare")
    )
    # per-ingest deltas on the report sum to the cumulative counters
    assert sum(r.upload_bytes for r in svc.reports) == obs.total_upload_bytes()
    assert all(r.upload_bytes > 0 for r in svc.reports)
    # engine rounds published under em.*
    assert c.get("em.runs", 0) == 2
    assert snap["histograms"]["em.wall_ms"]["count"] == 2


def test_resolve_latency_histogram(hepth_small):
    svc = _stream(hepth_small, 2, scheme="smp")
    obs.reset()
    snap_obj = svc.snapshot()
    for _ in range(10):
        snap_obj.resolve_many([0, 1, 2, 3])
    svc.resolve_many([0, 1])
    svc.resolve(0)
    snap = obs.get_registry().snapshot()
    lat = snap["histograms"]["resolve.latency_ms"]
    assert lat["count"] == 12  # one sample per call, not per id
    assert snap["counters"]["resolve.queries"] == 10 * 4 + 2 + 1
    assert lat["p50"] <= lat["p99"]
    assert lat["p99"] < 1000.0  # sane units: milliseconds


# ---------------------------------------------------------------------------
# Thread-safety under concurrent readers
# ---------------------------------------------------------------------------


def test_registry_thread_safety_under_concurrent_readers(hepth_small):
    batches = arrival_stream(hepth_small, 6)
    svc = ResolveService(scheme="smp")
    svc.ingest(batches[0].names, batches[0].edges, ids=batches[0].ids)
    obs.reset()
    stop = threading.Event()
    errors: list[Exception] = []
    calls = [0] * 4

    def reader(i: int) -> None:
        rng = np.random.default_rng(i)
        try:
            while not stop.is_set():
                snap_obj = svc.snapshot()
                ids = rng.integers(0, max(snap_obj.n_entities, 1), size=16)
                snap_obj.resolve_many(ids)
                calls[i] += 1
                # concurrent snapshot() of the registry must never throw
                # and always be internally consistent JSON
                json.dumps(obs.get_registry().snapshot())
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    try:
        for b in batches[1:]:
            svc.ingest(b.names, b.edges, ids=b.ids)
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors
    snap = obs.get_registry().snapshot()
    # every reader call landed exactly one latency sample
    assert snap["histograms"]["resolve.latency_ms"]["count"] == sum(calls)
    assert snap["counters"]["resolve.queries"] == 16 * sum(calls)
    assert snap["counters"]["ingest.count"] == len(batches) - 1
    # span records from the ingest thread interleaved safely
    assert snap["spans"]["ingest"]["count"] == len(batches) - 1


# ---------------------------------------------------------------------------
# Overhead: tracing must stay cheap on the ingest path
# ---------------------------------------------------------------------------


def test_tracing_overhead_under_5_percent(hepth_small):
    def run_once() -> float:
        obs.reset()
        t0 = time.perf_counter()
        _stream(hepth_small, 4, scheme="smp")
        return time.perf_counter() - t0

    reg = obs.get_registry()
    reg.set_tracing(False)
    run_once()  # warm caches (jit, name levels) off the clock
    # interleaved, so that load from other processes falls on both sides
    times: dict[bool, list[float]] = {False: [], True: []}
    for tracing in (False, True, True, False):
        reg.set_tracing(tracing)
        times[tracing].append(run_once())
    t_off, t_on = min(times[False]), min(times[True])
    # <5% relative overhead, plus an absolute allowance for timer noise
    # at this corpus scale (CI machines jitter more than spans cost)
    assert t_on <= t_off * 1.05 + 0.35, (t_on, t_off)


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------


def test_chrome_trace_export(tmp_path, hepth_small):
    _stream(hepth_small, 2, scheme="smp")
    path = tmp_path / "trace.json"
    n = obs.write_chrome_trace(str(path))
    assert n > 0
    data = json.loads(path.read_text())
    events = data["traceEvents"]
    assert len(events) == n + 1  # + the process_name metadata record
    xs = [e for e in events if e["ph"] == "X"]
    assert xs, "no complete events exported"
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["name"], str)
    roots = [e for e in xs if e["name"] == "ingest"]
    assert len(roots) == 2
    kids = [e for e in xs if e.get("args", {}).get("parent") == "ingest"]
    assert kids
    # every event of one ingest carries that ingest's trace id
    assert {e["args"]["trace_id"] for e in kids} == {
        e["args"]["trace_id"] for e in roots}
    # timestamps are Unix-epoch microseconds, the profiler's clock
    assert abs(min(e["ts"] for e in xs) / 1e6 - time.time()) < 600


def test_snapshot_export(tmp_path):
    reg = obs.get_registry()
    reg.counter("a.b").inc(7)
    reg.histogram("c").observe(1.5)
    path = tmp_path / "snap.json"
    snap = obs.write_snapshot(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk == json.loads(json.dumps(snap))
    assert on_disk["counters"]["a.b"] == 7
    assert on_disk["histograms"]["c"]["count"] == 1


# ---------------------------------------------------------------------------
# Round engine spans, trace ids, the profiler's clock, compile spans
# ---------------------------------------------------------------------------


def test_run_parallel_spans_share_one_em_run_root(hepth_small):
    from repro.core import pipeline
    from repro.core.mln import MLNMatcher
    from repro.core.parallel import run_parallel

    packed, gg, _ = pipeline.prepare(hepth_small.entities,
                                     hepth_small.relations)
    reg = obs.get_registry()
    ids = []
    for _ in range(2):
        obs.reset()
        run_parallel(packed, MLNMatcher(), gg, scheme="mmp")
        spans = list(reg.spans)
        roots = [s for s in spans if s.depth == 0]
        assert [s.name for s in roots] == ["em.run"]
        root = roots[0]
        ids.append(root.trace_id)
        assert {s.trace_id for s in spans} == {root.trace_id}
        names = {s.name for s in spans}
        assert {"rounds.stage", "rounds.schedule", "rounds.full",
                "rounds.messages", "rounds.promote"} <= names, names
        for s in spans:
            assert root.t_start <= s.t_start
            assert s.t_start + s.dur_s <= root.t_start + root.dur_s + 1e-6
            if s.name in ("rounds.stage", "rounds.schedule", "rounds.full",
                          "rounds.fused", "rounds.promote"):
                assert s.parent == "em.run", (s.name, s.parent)
        # rounds.messages is the one new span nested in a full round
        assert {s.name for s in spans if s.parent == "rounds.full"} <= {
            "rounds.messages", "rounds.ground", "compile"}
        assert all(s.parent == "rounds.full" for s in spans
                   if s.name == "rounds.messages")
    assert ids[0] != ids[1]


def test_trace_id_is_inherited_and_fresh_per_root():
    reg = obs.get_registry()
    with obs.span("a"):
        with obs.span("a.child"):
            pass
    with obs.span("b"):
        pass
    seen = {}

    def other_thread():
        with obs.span("c"):
            with obs.span("c.child"):
                pass

    t = threading.Thread(target=other_thread)
    t.start()
    t.join()
    for s in reg.spans:
        seen[s.name] = s.trace_id
    assert seen["a"] == seen["a.child"]
    assert seen["c"] == seen["c.child"]
    assert len({seen["a"], seen["b"], seen["c"]}) == 3


def test_spans_land_in_the_profiler_trace_on_its_clock(tmp_path):
    import glob
    import os

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.obs.export import chrome_trace_events

    reg = obs.get_registry()
    x = jnp.ones(8)
    (x * 2).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        obs.reset()  # a fresh clock pair, a moment before the spans
        with obs.span("probe.outer"):
            with obs.span("probe.inner"):
                (x * 2).block_until_ready()
                time.sleep(0.002)
    path = max(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    pd = ProfileData.from_file(path)
    t0_ns = None
    found = {}
    for plane in pd.planes:
        if plane.name == "Task Environment":
            t0_ns = int(dict(plane.stats)["profile_start_time"])
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("probe."):
                    found[e.name] = (e.start_ns, e.duration_ns)
    recs = {s.name: s for s in reg.spans}
    exported = {e["name"]: e for e in chrome_trace_events() if e["ph"] == "X"}
    for name in ("probe.outer", "probe.inner"):
        start_ns, dur_ns = found[name]
        trace_us = (t0_ns + start_ns) / 1e3
        assert abs(trace_us - reg.epoch_us(recs[name].t_start)) < 100, name
        assert abs(dur_ns / 1e3 - recs[name].dur_s * 1e6) < 100, name
        # the exported Chrome trace is on the same clock
        assert abs(exported[name]["ts"] - trace_us) < 100, name


def test_compile_inside_a_span_is_a_nested_compile_span():
    import jax
    import jax.numpy as jnp

    reg = obs.get_registry()
    x = jnp.arange(5.0)
    n0 = reg.value("compile.programs")

    def freshly_jitted(v):
        return jnp.sin(v) * 3.0 + 1.0

    with obs.span("outer"):
        jax.jit(freshly_jitted)(x).block_until_ready()
    assert reg.value("compile.programs") > n0
    outer = next(s for s in reg.spans if s.name == "outer")
    comp = [s for s in reg.spans if s.name == "compile"
            and "freshly_jitted" in (s.args or {}).get("program", "")]
    assert comp
    for c in comp:
        assert c.parent == "outer" and c.depth == 1
        assert c.trace_id == outer.trace_id
        assert outer.t_start <= c.t_start + 1e-3
        assert c.t_start + c.dur_s <= outer.t_start + outer.dur_s + 1e-6


def test_tracing_off_records_no_span_but_counts_compiles():
    import jax
    import jax.numpy as jnp

    reg = obs.get_registry()
    x = jnp.arange(5.0)
    reg.set_tracing(False)
    n0 = reg.value("compile.programs")
    m0 = reg.value("compile.cache_misses")

    def freshly_jitted_untraced(v):
        return jnp.cos(v) - 2.0

    with obs.span("quiet"):
        jax.jit(freshly_jitted_untraced)(x).block_until_ready()
    # a persistent-cache write, as JAX reports it
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert reg.spans == []
    assert reg.value("compile.programs") > n0
    assert reg.value("compile.cache_misses") == m0 + 1


def test_quality_reexport_is_core_metrics():
    from repro.core import metrics as core_metrics
    from repro.obs import quality

    assert quality.prf is core_metrics.prf
    assert quality.PRF is core_metrics.PRF
    assert quality.soundness is core_metrics.soundness
    assert quality.completeness is core_metrics.completeness
