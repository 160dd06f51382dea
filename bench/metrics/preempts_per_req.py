"""Scheduler: CFS preemptions in the window (``EngineMetrics.preemptions``
delta) per request due in the window."""


def read(run):
    due = run.due_in_window()
    return run.counters["preemptions"] / due if due else None
