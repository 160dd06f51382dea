#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest steady Poisson rate, at
the cell's own lengths, that the system serves without a growing backlog.

    python3 bench/sweep.py --workload <cell> --rates 0.4,0.55,0.7 \\
        --seconds 60 [--seed n]

One process serves each rate in turn on a fresh engine (same weights and
programs): the cell's traffic file with its spikes removed and
``base_rate`` set to the rate. A rate is sustained when the output tokens
served in the window keep up with the tokens the rate offers (rate x the
mean output length, within 10%) and the queue of requests not yet finished
grows by less than ``--slack``. The benchmark's own runs never sweep; a
traffic file records the rate chosen from one sweep.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import harness  # noqa: E402
import traffic as traffic_mod  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--slack", type=int, default=4)
    args = ap.parse_args()
    harness.enable_compile_cache()
    out = []
    for rate in map(float, args.rates.split(",")):
        base = harness.Cell(args.workload, args.seed)
        tr = dict(base.traffic)
        tr["arrivals"] = {k: v for k, v in tr["arrivals"].items()
                          if not k.startswith("spike")}
        tr["arrivals"]["base_rate"] = rate
        cell = harness.Cell(args.workload, args.seed, traffic=tr)
        cell.devices()
        cell.build()
        cell.warm()
        run = cell.serve(args.seconds)
        t0, t1 = run.t0, run.t1

        def open_at(t):
            return sum(1 for r in run.records if r.due < t
                       and not (r.finished is not None and r.finished < t))
        due = run.due_in_window()
        done = sum(1 for r in run.records if r.finished is not None
                   and t0 <= r.finished < t1)
        row = {"rate": rate, "due": due, "finished": done,
               "open_at_start": open_at(t0), "open_at_end": open_at(t1),
               **harness.stats.end_to_end(run.records, t0, t1)}
        offered = rate * statistics.mean(
            traffic_mod.quantile_lengths(tr["output"], 1000))
        row["offered_tokens_per_s"] = offered
        row["sustained"] = (row["open_at_end"] - row["open_at_start"]
                            < args.slack
                            and row["output_tokens_per_s"] >= 0.9 * offered)
        print("sweep:", json.dumps(row), flush=True)
        out.append(row)
        cell.engine = None
        del cell, base
        import gc
        gc.collect()
    ok = [r["rate"] for r in out if r["sustained"]]
    print(json.dumps({"knee": max(ok) if ok else None, "rows": out}))


if __name__ == "__main__":
    main()
