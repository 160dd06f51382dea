"""Device: share of the traced window, in %, in which no program ran on
the serving chip (device 0): 1 - (union of its program intervals) /
window."""


def read(run):
    if (run.trace is None or not run.trace.has_device(0)
            or not run.trace.window_s):
        return None
    return 100.0 * (1.0 - run.trace.busy_s(0) / run.trace.window_s)
