"""Fused step: median host wall, in ms, of the window's all-decode steps,
each ending in ``block_until_ready`` on the pools."""
import statistics


def read(run):
    walls = [s.wall for s in run.steps if s.kind == "decode"]
    return 1e3 * statistics.median(walls) if walls else None
