"""Host self time of the round engine's scheduling between dispatches
(obs span ``rounds.schedule``: live and certified rows, activity masks,
the match-set unions and the pool's messages) in the window, in
milliseconds per resolution."""


def read(run):
    s = run.span_seconds(("rounds.schedule",))
    return 1e3 * s / run.units if run.units and s > 0 else None
