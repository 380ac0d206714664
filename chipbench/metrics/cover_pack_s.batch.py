"""Host self time of the cover's row staging in set-up, in seconds: obs
span ``cover.pack`` with its nested ``cover.levels`` (the similarity
levels of the member pairs), from the spans the ``batch_at_scale``
driver kept before the harness cleared the log."""

from chipbench.trace_reduce import span_self_times


def read(run):
    spans = getattr(run, "setup_spans", None) or []
    names = ("cover.pack", "cover.levels")
    s = sum(d for sp, d in zip(spans, span_self_times(spans)) if sp.name in names)
    return s if s > 0 else None
