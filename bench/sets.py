#!/usr/bin/env python3
"""Repeated runs of one cell, each a process of its own, and the spread
of every metric: what the bounds in ``BENCHMARK.json`` are set from.

  python3 bench/sets.py --workload q3s50-decode --seeds 1,2,3,4,5,6 \
      --sets 2 --seconds 40 --out sets.json

Runs ``bench/run.py`` once per seed in each set (the same seeds in
every set) and prints, per set and metric, the median and the spread
(interquartile distance over the median, by ``statistics.quantiles``).
Each run's last stdout line and last stderr lines are kept in ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from stats import spread  # noqa: E402


def one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        capture_output=True, text=True, cwd=os.path.dirname(HERE))
    lines = p.stdout.strip().splitlines()
    res = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    return {"seed": seed, "rc": p.returncode, "result": res,
            "stderr": p.stderr[-3000:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    runs = []
    for k in range(args.sets):
        for s in seeds:
            r = one(args.workload, s, args.seconds, args.trace)
            r["set"] = k
            runs.append(r)
            res = r["result"] or {}
            print(json.dumps({"set": k, "seed": s, "rc": r["rc"],
                              "correct": res.get("correct"),
                              "metrics": {m: v["value"] for m, v in
                                          res.get("metrics", {}).items()},
                              "checks": res.get("checks")}), flush=True)
            with open(args.out, "w", encoding="utf-8") as fh:
                json.dump(runs, fh)
    for k in range(args.sets):
        got = [r["result"] for r in runs if r["set"] == k and r["result"]]
        names = sorted({m for g in got for m in g["metrics"]})
        for m in names:
            vals = [g["metrics"][m]["value"] for g in got
                    if m in g["metrics"]]
            if len(vals) >= 2:
                print(f"set {k} {m}: median {statistics.median(vals)!r} "
                      f"spread {spread(vals)!r} n {len(vals)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
