#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload q3s50-decode --seed 7 --seconds 40 \
      --trace 0

One process per run: it sets the cell up (weights from the seed, SASP
deploy, warm-up of every program the window uses), measures for
``--seconds``, checks what the window served against the float32
reference, and prints one JSON object as the last line of standard
output. ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` traces the window with the profiler and reports its per-layer
metrics. The numbers compared by the output check are the last lines
of standard error and the last key of the result.

With no TPU, or fewer chips than the cell asks for, or without the
``repro`` package beside ``bench/``, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # The TPU runtime starts here, before anything of the program runs:
    # it takes 5-12 s in a process (PERF.md), and set-up leaves it out.
    import jax
    t = time.monotonic()
    jax.devices()
    runtime_s = time.monotonic() - t
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    try:
        import repro.serve.engine  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: the repro package is not importable ({e}); run "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    import harness
    cell = harness.load_cell(args.workload)
    res = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, runtime_s=runtime_s)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
