"""The one traffic generator: every mix is a parameter file in
``bench/traffic/<name>.json`` that this module reads.

Keys of a traffic file:

``loop``
    ``"open"`` (requests due on a schedule, whatever the server does) or
    ``"closed"`` (``clients`` callers, each sending its next request the
    moment the previous one finished, with no think time).
``arrivals`` (open loop)
    A Poisson process at ``base_rate`` requests per second whose rate is
    multiplied by ``spike_factor`` for ``spike_s`` seconds starting
    ``spike_at_s`` into every ``period_s`` (leave out ``spike_factor`` for a
    steady rate).
``prompt``, ``output``
    Lognormal lengths in tokens: ``median``, ``sigma`` (of the log) and the
    clip ``[min, max]``.
``clients``, ``queue`` (closed loop)
    The number of callers, and the size of each batch of lengths they
    take from in turn (a run that needs more takes another batch).
``preroll_s``
    Traffic served before the measured window opens, so that the window
    never starts from an empty engine.

Every run seed gets the same amount of work, at other times and in another
order. The horizon splits into stretches of constant rate (the base and the
spike part of each period); each stretch holds a fixed number of arrivals,
its expected count (rounded on the running total, so that the counts add up
to the expected total), at times the run's ``--seed`` draws uniformly
inside the stretch: a Poisson process given its counts. The lengths are the
lognormal's quantiles at evenly spaced probabilities, dealt to the stretches
in one fixed order; the seed permutes them among the arrivals of each
stretch (prompt and output lengths independently) and draws the token ids.
So runs with different seeds differ in timing, order and content, never in
the amount of work a stretch offers. The rate envelope follows
``core/workload.py: make_bursty_requests``, copied here so that a change to
the program cannot change the yardstick.
"""
from __future__ import annotations

import json
import math
import statistics
from dataclasses import dataclass
from pathlib import Path
from typing import List, Sequence

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Item:
    """One request: when it is due (seconds after the traffic starts; 0 for
    a closed loop, whose caller decides), its prompt and output lengths,
    and its token ids."""
    index: int
    due: float
    prompt: tuple
    max_new_tokens: int


def load(name: str, root: Path = HERE / "traffic") -> dict:
    """The traffic file ``<root>/<name>.json``."""
    path = Path(root) / f"{name}.json"
    spec = json.loads(path.read_text())
    spec["name"] = name
    return spec


def rate_at(t: float, arr: dict) -> float:
    """Offered rate of the modulated Poisson process at time t."""
    rate = float(arr["base_rate"])
    factor = float(arr.get("spike_factor", 1.0))
    if factor != 1.0:
        phase = t % float(arr["period_s"])
        start = float(arr["spike_at_s"])
        if start <= phase < start + float(arr["spike_s"]):
            rate *= factor
    return rate


def mean_rate(arr: dict) -> float:
    """Time-averaged offered rate."""
    factor = float(arr.get("spike_factor", 1.0))
    if factor == 1.0:
        return float(arr["base_rate"])
    share = float(arr["spike_s"]) / float(arr["period_s"])
    return float(arr["base_rate"]) * (1.0 + (factor - 1.0) * share)


def stretches(arr: dict, horizon: float) -> List[tuple]:
    """``[0, horizon)`` cut where the rate changes: ``(start, end, rate)``
    of every stretch, in order (one stretch per period for a steady rate
    with a ``period_s``, else one in all)."""
    cuts = {0.0, float(horizon)}
    if "period_s" in arr:
        period = float(arr["period_s"])
        spiky = float(arr.get("spike_factor", 1.0)) != 1.0
        k = 0
        while k * period < horizon:
            cuts.add(k * period)
            if spiky:
                at = k * period + float(arr["spike_at_s"])
                cuts |= {at, at + float(arr["spike_s"])}
            k += 1
    cuts = sorted(c for c in cuts if 0.0 <= c <= horizon)
    return [(a, b, rate_at((a + b) / 2, arr))
            for a, b in zip(cuts, cuts[1:])]


def arrival_times(arr: dict, horizon: float, seed: int) -> List[tuple]:
    """Arrivals in ``[0, horizon)`` as ``(time, stretch)``, in time order:
    each stretch's fixed count, at times drawn uniformly inside it from
    ``seed``."""
    rng = _rng(seed, 0)
    out: List[tuple] = []
    expected = 0.0
    for j, (a, b, rate) in enumerate(stretches(arr, horizon)):
        n = -math.floor(expected + 0.5)
        expected += rate * (b - a)
        n += math.floor(expected + 0.5)
        out += [(float(t), j) for t in np.sort(rng.uniform(a, b, n))]
    return out


def quantile_lengths(dist: dict, n: int) -> List[int]:
    """``n`` lengths: the lognormal's quantiles at probabilities
    ``(i + 1/2) / n``, rounded and clipped to ``[min, max]``."""
    nd = statistics.NormalDist()
    mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
        out.append(min(max(int(round(x)), lo), hi))
    return out


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def items(spec: dict, seed: int, n: int, vocab: int,
          dues: Sequence[float] = (), block: int = 0) -> List[Item]:
    """``n`` requests for run ``seed``: the quantile lengths permuted by the
    seed, token ids from the seed in ``[1, vocab)``, due at ``dues``
    (zeros when not given). ``block`` numbers further batches of the same
    run, each with streams of its own; their items are indexed on from
    ``block * n``."""
    prompts = quantile_lengths(spec["prompt"], n)
    outputs = quantile_lengths(spec["output"], n)
    order_p = _rng(seed, 1 + 3 * block).permutation(n)
    order_o = _rng(seed, 2 + 3 * block).permutation(n)
    tok = _rng(seed, 3 + 3 * block)
    dues = list(dues) or [0.0] * n
    out = []
    for i in range(n):
        p = prompts[order_p[i]]
        out.append(Item(block * n + i, float(dues[i]),
                        tuple(int(x) for x in tok.integers(1, vocab, p)),
                        int(outputs[order_o[i]])))
    return out


def open_loop(spec: dict, seed: int, horizon: float, vocab: int
              ) -> List[Item]:
    """Every request of an open-loop mix due in ``[0, horizon)``.

    The lengths are dealt to the arrivals in one fixed order; the run seed
    then reorders them only among the arrivals of one stretch, so that every
    seed offers each stretch the same work."""
    arrivals = arrival_times(spec["arrivals"], horizon, seed)
    n = len(arrivals)
    deal = np.random.default_rng(0)
    prompts = np.asarray(quantile_lengths(spec["prompt"], n))[
        deal.permutation(n)]
    outputs = np.asarray(quantile_lengths(spec["output"], n))[
        deal.permutation(n)]
    rp, ro = _rng(seed, 1), _rng(seed, 2)
    for key in sorted({j for _, j in arrivals}):
        idx = np.asarray([i for i, (_, j) in enumerate(arrivals) if j == key])
        prompts[idx] = prompts[idx[rp.permutation(len(idx))]]
        outputs[idx] = outputs[idx[ro.permutation(len(idx))]]
    tok = _rng(seed, 3)
    return [Item(i, due,
                 tuple(int(x) for x in tok.integers(1, vocab,
                                                    int(prompts[i]))),
                 int(outputs[i])) for i, (due, _) in enumerate(arrivals)]


def closed_loop(spec: dict, seed: int, vocab: int, block: int = 0
                ) -> List[Item]:
    """Batch ``block`` of the queue the closed loop's clients take from in
    turn: ``queue`` requests, the lengths' quantiles in the seed's order."""
    return items(spec, seed, int(spec["queue"]), vocab, block=block)
