"""Front end: device-0 idle time inside the engine's ``aqua.step`` spans
that start in the traced window, in ms per step: what the host's own work
in a step (planning, parks and restores, packing, readback, retirement,
prefetch) leaves the chip waiting."""
from pathlib import Path

import spans

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    t = spans.of(run, ROOT)
    steps = t.in_window(spans.STEP) if t else []
    if not steps or not t.has_device(0):
        return None
    return t.idle_inside(steps) / len(steps) / 1e6
