"""Exporters: JSON snapshots and Chrome-trace files.

Two ways out of the registry:

* :func:`write_snapshot` — ``MetricsRegistry.snapshot()`` as a JSON
  file; what the benchmarks commit into ``BENCH_*.json`` blocks.
* :func:`write_chrome_trace` — the span log as a Chrome
  ``trace_event`` file (``{"traceEvents": [...]}``, complete ``"X"``
  events in microseconds).  Loads in ``chrome://tracing`` and
  `Perfetto <https://ui.perfetto.dev>`_; CI exports one per push from a
  hepth ingest and uploads it as a workflow artifact.  Its timestamps
  are Unix-epoch microseconds, the clock of a ``jax.profiler`` trace,
  so the file lines up with the profiler's ``perfetto_trace.json.gz``
  of the same stretch (the spans are also inside that trace, as
  ``TraceAnnotation`` events: :mod:`repro.obs.tracing`).
"""

from __future__ import annotations

import json

from repro.obs.registry import MetricsRegistry, get_registry

__all__ = ["write_chrome_trace", "write_snapshot"]


def write_snapshot(path: str, registry: MetricsRegistry | None = None) -> dict:
    """Dump ``registry.snapshot()`` to ``path`` as JSON; returns it."""
    reg = registry if registry is not None else get_registry()
    snap = reg.snapshot()
    with open(path, "w") as f:
        json.dump(snap, f, indent=2, sort_keys=True)
        f.write("\n")
    return snap


def chrome_trace_events(registry: MetricsRegistry | None = None) -> list[dict]:
    """The span log as Chrome ``trace_event`` dicts (phase ``X``).

    Timestamps are microseconds since the Unix epoch (the registry's
    clock pair, :meth:`~repro.obs.registry.MetricsRegistry.epoch_us`),
    one ``tid`` per recording thread, so the viewer reconstructs the
    nesting of concurrent ingests and readers; each event's args carry
    its ``trace_id``.
    """
    reg = registry if registry is not None else get_registry()
    events: list[dict] = [{
        "name": "process_name",
        "ph": "M",
        "pid": 0,
        "tid": 0,
        "args": {"name": "repro"},
    }]
    with reg._lock:
        spans = list(reg.spans)
    for rec in spans:
        ev = {
            "name": rec.name,
            "ph": "X",
            "ts": round(reg.epoch_us(rec.t_start), 3),
            "dur": round(rec.dur_s * 1e6, 3),
            "pid": 0,
            "tid": rec.thread_id % (1 << 31),
        }
        args = dict(rec.args) if rec.args else {}
        if rec.parent:
            args["parent"] = rec.parent
        args["trace_id"] = rec.trace_id
        ev["args"] = args
        events.append(ev)
    return events


def write_chrome_trace(path: str,
                       registry: MetricsRegistry | None = None) -> int:
    """Write the span log as a Chrome-trace/Perfetto JSON file.

    Returns the number of span events written (excluding metadata).
    Open the file at ``chrome://tracing`` or https://ui.perfetto.dev.
    """
    events = chrome_trace_events(registry)
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        f.write("\n")
    return len(events) - 1
