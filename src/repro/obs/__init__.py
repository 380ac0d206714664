"""Unified runtime observability: metrics registry, tracing, exporters.

The paper's framework decomposes EM into per-stage work (blocking,
replay, cover splice, grounding splice, message-passing rounds,
promotion, commit — §4/§5 of 1103.2410); this package is where that
story is *measured*, in one substrate instead of counters smeared over
report dataclasses and benchmark plumbing:

* :mod:`repro.obs.registry` — process-wide, thread-safe counters /
  gauges / histograms (exact p50/p90/p99); ``get_registry()`` /
  ``reset()``.
* :mod:`repro.obs.tracing` — nestable ``span()`` context managers that
  also annotate a running ``jax.profiler`` trace, one ``trace_id`` per
  root span, and ``compile`` spans / ``compile.*`` counters from
  ``jax.monitoring``; the span taxonomy is in the module docstring and
  ``docs/ARCHITECTURE.md``.
* :mod:`repro.obs.transfer` — host→device upload-byte accounting for
  the three transfer sites (grounding cache, promoter, bin staging).
* :mod:`repro.obs.export` — JSON snapshots and Chrome-trace/Perfetto
  ``trace_event`` files on the profiler's (Unix-epoch) clock.
* :mod:`repro.obs.quality` — the paper's quality metrics
  (:mod:`repro.core.metrics`), re-exported so runtime and quality
  numbers report through one surface.

``IngestReport`` and ``EMResult`` remain the public per-call dataclass
views; their counters are registry-backed (``ingest.*`` / ``em.*``
counter families, published at the end of each ingest/run), which is
what ``benchmarks/stream_throughput.py`` and ``table1_parallel.py``
consume via ``snapshot()``.

The fault-tolerance plane reports through the same registry: the
durability families ``wal.*`` (``appends``/``bytes`` counters,
``append_ms`` histogram), ``ckpt.*`` (``saves`` counter, ``last_seq``
gauge), ``recover.*`` (``replayed`` counter, ``wall_ms`` histogram),
``ingest.aborts`` (rolled-back ingests), and the serving degradation
counters ``serve.retries`` / ``serve.quarantined`` /
``serve.faults.flush`` / ``serve.faults.bisections`` plus the
``serve.backoff_ms`` histogram — the taxonomy
``docs/ARCHITECTURE.md`` catalogs and ``tests/test_faults.py``
exercises under injected faults.
"""

from repro.obs.export import write_chrome_trace, write_snapshot  # noqa: F401
from repro.obs.registry import (  # noqa: F401
    MetricsRegistry,
    get_registry,
    reset,
)
from repro.obs.tracing import Span, SpanRecord, span  # noqa: F401
from repro.obs.transfer import record_transfer, total_upload_bytes  # noqa: F401

__all__ = [
    "MetricsRegistry",
    "Span",
    "SpanRecord",
    "get_registry",
    "record_transfer",
    "reset",
    "span",
    "total_upload_bytes",
    "write_chrome_trace",
    "write_snapshot",
]
