"""Host self time of the maximal messages built from each bin's labels
(obs span ``rounds.messages``, nested in ``rounds.full``) in the window,
in milliseconds per resolution.

``full_round_ms.batch`` reads the self time of ``rounds.full``, which
excludes this span: the two together are what ``full_round_ms.batch``
read before ``rounds.messages`` existed."""


def read(run):
    s = run.span_seconds(("rounds.messages",))
    return 1e3 * s / run.units if run.units and s > 0 else None
