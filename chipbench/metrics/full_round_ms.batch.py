"""Self time of the per-bin full rounds (obs span ``rounds.full``; its
device work shows there because the round's outputs are read back inside
it) in the window, in milliseconds per resolution."""


def read(run):
    s = run.span_seconds(("rounds.full",))
    return 1e3 * s / run.units if run.units and s > 0 else None
