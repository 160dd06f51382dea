"""Fused step: device time of ``copy`` operations inside the fused-step
programs (``aqua_step_*``) over those programs' device time in the traced
window, in %: the layout and pool copies a step pays besides its work."""
from pathlib import Path

import spans

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    t = spans.of(run, ROOT)
    progs = {p: ns for p, ns in (t.program_ns() if t else {}).items()
             if p.startswith(spans.STEP_PROGRAM)}
    total = sum(progs.values())
    return 100.0 * t.op_in("copy", progs) / total if total else None
