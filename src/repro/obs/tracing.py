"""Structured tracing spans: nestable context managers over the registry.

A span measures one stage of host work on the ``perf_counter`` clock.
Device work is not fenced: a span's duration holds device time only
where the span reads a result back (the engine's ``int()`` syncs).
Device time per stage comes from a ``jax.profiler`` trace instead: while
a span is open it also holds a ``jax.profiler.TraceAnnotation`` of its
name, so under ``jax.profiler.trace(dir)`` the spans land in the
profiler's own ``.xplane.pb`` beside the device ops, on the same clock.

Spans nest per thread: a thread-local stack tracks the open span, and
each record carries its parent's name and depth, so both the in-process
nesting tests and the Chrome-trace export (which reconstructs nesting
from timestamps within a ``tid``) see the same tree.  A span opened with
no span open on its thread is a root and takes a fresh ``trace_id`` (a
per-process sequence number); every span under it inherits the id, so
all spans of one resolution or one ingest share it.  The span taxonomy
is documented in ``docs/ARCHITECTURE.md`` (Observability section); the
stable stage names are:

    ingest                      one ResolveService.ingest call
      ingest.lsh                MinHash/LSH probe (stream/delta._probe)
      ingest.replay             localized canopy replay
      ingest.cover_splice       incremental assemble + packed splice
        cover.levels            similarity levels of the fresh rows' pairs
      ingest.grounding_splice   GroundingMaintainer delta + array splice
      ingest.rounds             fixpoint advance (engine.advance)
        em.run                  one run_parallel call (a root in batch use)
          rounds.stage          universe, bin staging, per-bin uploads
          rounds.schedule       host scheduling between dispatches
          rounds.ground         bin grounding dispatches (GroundingCache)
          rounds.fused          fused multi-round while_loop dispatches
          rounds.full           per-bin full-round dispatches
            rounds.messages     maximal messages from one bin's labels
          rounds.promote        step-7 promotion (device or host), whole
      ingest.commit             atomic cluster/fixpoint publish
    cover.canopies              the batch canopy build (core/cover)
    cover.pack                  the batch row staging, one pass per bin
      cover.levels              similarity levels of the member pairs
    compile                     one XLA program load, under whatever
                                span was open on the compiling thread

``compile`` spans come from a ``jax.monitoring`` listener registered
once per process: each backend-compile event (a compile, or a program
served by the persistent cache) becomes a span ending at the event, and
raises the counter ``compile.programs``; each persistent-cache write
raises ``compile.cache_misses``.  The counters count with tracing off
too.

Disabling (``registry.set_tracing(False)``) makes :func:`span` yield a
shared no-op whose every method is a pass — the hot path pays one
attribute read.  With tracing ON the cost is two ``perf_counter`` calls,
one ``TraceAnnotation`` (inert unless a profiler trace is running) and
one locked list append per span; the <5% ingest-overhead guard in
``tests/test_obs.py`` holds the bill.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time

from jax import monitoring
from jax.profiler import TraceAnnotation

from repro.obs.registry import MetricsRegistry, get_registry

__all__ = ["Span", "SpanRecord", "span"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


@dataclasses.dataclass
class SpanRecord:
    """One closed span, as stored in the registry's span log."""

    name: str
    t_start: float  # perf_counter at enter
    dur_s: float
    thread_id: int
    parent: str | None
    depth: int
    args: dict | None = None
    trace_id: int = 0  # shared by every span under one root span


_local = threading.local()
_trace_ids = itertools.count(1)


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class Span:
    """An open span; created by :func:`span`, closed by ``__exit__``."""

    __slots__ = ("name", "registry", "args", "t_start", "parent", "depth",
                 "trace_id", "_annotation")

    def __init__(self, name: str, registry: MetricsRegistry,
                 args: dict | None):
        self.name = name
        self.registry = registry
        self.args = args
        self.t_start = 0.0
        self.parent: str | None = None
        self.depth = 0
        self.trace_id = 0
        self._annotation = TraceAnnotation(name)

    def __enter__(self) -> Span:
        st = _stack()
        if st:
            self.parent = st[-1].name
            self.trace_id = st[-1].trace_id
        else:
            self.trace_id = next(_trace_ids)
        self.depth = len(st)
        st.append(self)
        self._annotation.__enter__()
        self.t_start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dur = time.perf_counter() - self.t_start
        self._annotation.__exit__(exc_type, exc, tb)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        self.registry.record_span(SpanRecord(
            name=self.name,
            t_start=self.t_start,
            dur_s=dur,
            thread_id=threading.get_ident(),
            parent=self.parent,
            depth=self.depth,
            args=self.args,
            trace_id=self.trace_id,
        ))

    def set(self, **kv) -> None:
        """Attach args to the record (shown in the Chrome-trace UI)."""
        if self.args is None:
            self.args = {}
        self.args.update(kv)


class _NoopSpan:
    """Shared do-nothing span returned when tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return None

    def set(self, **kv):
        return None


_NOOP = _NoopSpan()


def span(name: str, registry: MetricsRegistry | None = None, **args):
    """Open a tracing span: ``with span("ingest.replay"): ...``.

    ``args`` become Chrome-trace event args.  When tracing is disabled
    on the registry this returns a shared no-op object.
    """
    reg = registry if registry is not None else get_registry()
    if not reg.tracing:
        return _NOOP
    return Span(name, reg, args or None)


def _on_compile(event: str, duration: float, **kwargs) -> None:
    """``jax.monitoring`` duration listener: one ``compile`` span per
    program load, ending now, nested under the thread's open span."""
    if event != COMPILE_EVENT:
        return
    reg = get_registry()
    reg.counter("compile.programs").inc()
    if not reg.tracing:
        return
    end = time.perf_counter()
    st = _stack()
    reg.record_span(SpanRecord(
        name="compile",
        t_start=end - duration,
        dur_s=float(duration),
        thread_id=threading.get_ident(),
        parent=st[-1].name if st else None,
        depth=len(st),
        args={"program": kwargs["fun_name"]} if "fun_name" in kwargs else None,
        trace_id=st[-1].trace_id if st else next(_trace_ids),
    ))


def _on_event(event: str, **kwargs) -> None:
    if event == CACHE_MISS_EVENT:
        get_registry().counter("compile.cache_misses").inc()


monitoring.register_event_duration_secs_listener(_on_compile)
monitoring.register_event_listener(_on_event)
