#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate it
sustains without a growing backlog. One process sets the cell up once
and offers each rate for ``--seconds``; between rates the queue is
dropped and the requests in flight finish.

  python3 bench/sweep.py --workload q3s50-chat --rates 1.5,2,2.5,3 \
      --seconds 40 --seed 5

Per rate, one JSON line: requests due and finished, tokens/s, TTFT p90,
and the queue length each second. A rate is sustained when the queue
at the end of its window is no longer than in its first quarter plus
the slot count. The benchmark's own runs never run this; the rate a
chat cell offers (``bench/cells/<cell>.json``) is 0.8 of the knee found
here once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import harness  # noqa: E402


class Sampled(harness.Window):
    """A window that also samples the engine's queue length."""

    def run(self, seconds):
        self.queue_len = []
        step = self.eng.step
        start = time.monotonic()

        def sampled_step():
            now = time.monotonic()
            if len(self.queue_len) <= (now - start):
                self.queue_len.append(len(self.eng.queue))
            return step()

        self.eng.step = sampled_step
        try:
            super().run(seconds)
        finally:
            del self.eng.step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.check_devices(cell.chips)
    harness.enable_cache()
    eng, cfg, _ = harness.build_engine(cell.config, cell.mix, args.seed)
    harness.warm_up(eng, cell.mix)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        win = Sampled(eng, cell.mix, args.seed + k, rate, cfg.vocab_size)
        win.run(args.seconds)
        e2e = win.end_to_end()
        q = win.queue_len
        head = max(q[:max(1, len(q) // 4)]) if q else 0
        print(json.dumps({
            "rate": rate, "due": len(win.reqs),
            "finished": len(win.finished()), "queue_per_s": q,
            "sustained": bool(q and q[-1] <= head + cell.mix.slots),
            **e2e}), flush=True)
        eng.queue.clear()
        while eng.has_work():
            eng.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
