"""Host self time of bin grounding (obs span ``rounds.ground``) in the
window, in milliseconds per resolution."""


def read(run):
    s = run.span_seconds(("rounds.ground",))
    return 1e3 * s / run.units if run.units and s > 0 else None
