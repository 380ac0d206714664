"""Share of the traced slice of the window in which no operation ran on
the device (1 - busy / traced window)."""


def read(run):
    return run.trace.idle_share if run.trace is not None and run.trace.busy_s > 0 else None
