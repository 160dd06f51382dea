"""Fused step: median host wall, in ms, of the window's steps that ran
prompt chunks (with or without decode lanes), each ending in
``block_until_ready`` on the pools."""
import statistics


def read(run):
    walls = [s.wall for s in run.steps if s.chunk_tokens]
    return 1e3 * statistics.median(walls) if walls else None
