"""State runtime: mean host wall, in ms, of the program's
``aqua.kv.park`` and ``aqua.kv.restore`` spans that start in the traced
window and moved pages (``pages`` > 0): what one tier move of a request's
context costs the serving loop."""
import statistics
from pathlib import Path

import spans

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    t = spans.of(run, ROOT)
    moves = [s.ns for s in (t.in_window("aqua.kv.park", "aqua.kv.restore")
                            if t else [])
             if int(s.args.get("pages", 0)) > 0]
    return statistics.mean(moves) / 1e6 if moves else None
