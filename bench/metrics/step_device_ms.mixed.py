"""Fused step: median device time, in ms, of one execution of the step
program with prompt chunks (``aqua_step_mixed`` or ``aqua_step_chunk``)
starting in the traced window: the chip's own share of a mixed step's
wall (``step_ms.mixed``)."""
from pathlib import Path

import spans

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    t = spans.of(run, ROOT)
    return spans.median_ms(
        t.executions("aqua_step_mixed", "aqua_step_chunk") if t else [])
