"""Host self time of the round engine's staging (obs span
``rounds.stage``: the pair universe, the per-bin tensors and their
uploads) in the window, in milliseconds per resolution."""


def read(run):
    s = run.span_seconds(("rounds.stage",))
    return 1e3 * s / run.units if run.units and s > 0 else None
