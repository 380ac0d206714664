"""Host self time of the canopy build (obs span ``cover.canopies``) in
set-up, in seconds: the spans the ``batch_at_scale`` driver kept before
the harness cleared the log."""

from chipbench.trace_reduce import span_self_times


def read(run):
    spans = getattr(run, "setup_spans", None) or []
    s = sum(d for sp, d in zip(spans, span_self_times(spans)) if sp.name == "cover.canopies")
    return s if s > 0 else None
