#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: whole runs of the cell,
many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 20 [--control fp8]

Each seed is one run as ``bench/run.py`` makes it (``harness.main``: the
cell's weights, program and traffic, its pre-roll and a window of
``--seconds``, then the comparison that decides ``correct``). Without
``--control`` the program's widest gaps over sound runs give the lower
reading. With ``--control fp8`` the plain reference computed with fp8
operands, one precision below the configuration's bfloat16, is put in the
program's place at the same positions: every such run has to come out not
correct, and its smallest widest gap is the upper reading. The benchmark's
own runs never run the control.
"""
import argparse
import contextlib
import io
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", choices=("fp8",), default=None)
    args = ap.parse_args()
    rows = []
    for seed in map(int, args.seeds.split(",")):
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = harness.main(["--workload", args.workload, "--seed",
                               str(seed), "--seconds", str(args.seconds),
                               "--trace", "0"], control=args.control)
        lines = out.getvalue().strip().splitlines()
        print("\n".join(lines[:-1]), flush=True)
        res = json.loads(lines[-1]) if rc == 0 and lines else {}
        row = {"seed": seed, "rc": rc, "correct": res.get("correct"),
               **{k: v["value"] for k, v in res.get("compared", {}).items()}}
        print("calibrate:", json.dumps(row), flush=True)
        rows.append(row)
    gaps = [r["worst_gap"] for r in rows if "worst_gap" in r]
    key = "upper" if args.control else "lower"
    print(json.dumps({"workload": args.workload, "control": args.control,
                      key: (min if args.control else max)(gaps)
                      if gaps else None, "rows": rows}))


if __name__ == "__main__":
    main()
