"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device's op intervals over the window."""


def read(run):
    if run.trace is None or not run.trace.window_s:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s
