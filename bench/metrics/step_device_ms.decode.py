"""Fused step: median device time, in ms, of one execution of the
all-decode step program (``aqua_step_decode``) starting in the traced
window: the chip's own share of a decode step's wall
(``step_ms.decode``)."""
from pathlib import Path

import spans

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    t = spans.of(run, ROOT)
    return spans.median_ms(t.executions("aqua_step_decode") if t else [])
