"""Share of the traced slice of the window in which the device is idle
and no stage span names the host's work: the innermost span open is
``em.run`` itself (its self time) or none at all."""

UNNAMED = ("no span open", "em.run")


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    gaps = run.trace.idle_gaps
    return sum(gaps.get(n, 0.0) for n in UNNAMED) / run.trace.window_s
