"""Tail and rate arithmetic over a measured window.

Every statistic is over ALL requests and ALL of the window: a request that
has no first token when the window closes counts at the time it has waited
so far (a censored sample), and so does the gap a request is still in at
the close. No statistic is a median of per-chunk medians.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-quantile (0..1) with linear interpolation between order
    statistics at rank ``q * (n - 1)``; None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    r = q * (len(xs) - 1)
    lo = math.floor(r)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (r - lo)


@dataclass
class Record:
    """What the benchmark saw of one request, on the host clock."""
    due: float                            # when it was due to be sent
    submitted: float = math.nan           # when submit() returned
    first_chunk: Optional[float] = None   # start of its first chunk's step
    # end of the step that produced each output token
    tokens: List[float] = field(default_factory=list)
    finished: Optional[float] = None
    parked: bool = False                  # parked at least once
    restored: bool = False                # and brought back afterwards


def ttft_samples(recs: Sequence[Record], t0: float, t1: float) -> List[float]:
    """Seconds to first token of every request due in ``[t0, t1)``, timed
    from when it was due; one with no first token by ``t1`` counts as
    ``t1 - due`` (censored)."""
    out = []
    for r in recs:
        if t0 <= r.due < t1:
            first = r.tokens[0] if r.tokens and r.tokens[0] < t1 else t1
            out.append(first - r.due)
    return out


def tbt_samples(recs: Sequence[Record], t0: float, t1: float) -> List[float]:
    """Every gap between consecutive output tokens that ends in
    ``[t0, t1)``, plus the gap each unfinished request is still in at
    ``t1`` (censored: from its last token to the close)."""
    out = []
    for r in recs:
        ts = [t for t in r.tokens if t < t1]
        out.extend(b - a for a, b in zip(ts, ts[1:]) if b >= t0)
        done = r.finished is not None and r.finished < t1
        if ts and not done:
            out.append(t1 - ts[-1])
    return out


def tokens_in(recs: Sequence[Record], t0: float, t1: float) -> int:
    """Output tokens produced in ``[t0, t1)``."""
    return sum(1 for r in recs for t in r.tokens if t0 <= t < t1)


def end_to_end(recs: Sequence[Record], t0: float, t1: float) -> Dict:
    """The window's end-to-end numbers: p95 TTFT and p95 gap between
    tokens (ms, None without samples) and output tokens per second."""
    ttft = ttft_samples(recs, t0, t1)
    tbt = tbt_samples(recs, t0, t1)
    p_ttft, p_tbt = percentile(ttft, 0.95), percentile(tbt, 0.95)
    return {"ttft_p95_ms": None if p_ttft is None else 1e3 * p_ttft,
            "tbt_p95_ms": None if p_tbt is None else 1e3 * p_tbt,
            "output_tokens_per_s": tokens_in(recs, t0, t1) / (t1 - t0),
            "n_ttft": len(ttft), "n_tbt": len(tbt),
            "censored_ttft": sum(1 for r in recs if t0 <= r.due < t1
                                 and not (r.tokens and r.tokens[0] < t1))}
