#!/usr/bin/env python3
"""Readings that the output check's limits are set from: for each seed,
the numbers a run compares (the widest and the mean gap of the served
tokens below the float32 reference's best), and the same numbers for
the control, on the same prompts and tokens.

The control is the nearest precision below the configuration's bf16:
int8 weights. Where the program has that path (the SASP configurations:
``build_serving_params(..., int8_weights=True)``, int8 FFN tiles with
per-tile scales), the program with it switched on is the control; its
token at each position is the argmax of ``lm.forward``. Otherwise the
reference with int8 weights (per output column) is. Each control token
is then read against the float32 reference, as a served token is, and
judged by ``harness.judge`` with the cell's limits: the control has to
come out as not correct.

With ``--fault <name>`` it instead plants one fault of ``faults.py`` in
the engine and makes whole runs of the cell (``harness.run``, with the
window as long as ``--seconds``): the run has to come out as not
correct.

  python3 bench/control.py --workload q3s50-decode --seeds 1,2,3 \
      --seconds 15 > readings.jsonl
  python3 bench/control.py --workload q3s50-decode --seeds 4 \
      --seconds 15 --fault state_unchanged

One line of JSON per seed. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

import faults  # noqa: E402
import harness  # noqa: E402


def program_int8_tokens(conf, seed, picked):
    """The program's int8-weight path's argmax at every position that
    predicts a served token."""
    import jax
    import jax.numpy as jnp

    import reference
    import weights
    from repro.launch import serve
    from repro.models import lm
    spec = weights.Spec.from_config(conf)
    cfg = harness.model_config(conf)
    params = harness.program_tree(
        weights.make(weights.root_key(seed), spec), cfg)
    s = conf["serve"]
    params, cfg = serve.build_serving_params(
        params, cfg, path=s["path"], sparsity=float(s["sparsity"]),
        int8_weights=True, block_k=s["block"], block_n=s["block"],
        scope=s["scope"], verbose=False)
    top = jax.jit(lambda p, t: jnp.argmax(
        lm.forward(p, cfg, tokens=t)[0], axis=-1).astype(jnp.int32))
    out = []
    for prompt, served in picked:
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        S = -(-len(seq) // reference.PAD) * reference.PAD
        toks = np.zeros((1, S), np.int32)
        toks[0, :len(seq)] = seq
        t = np.asarray(top(params, jnp.asarray(toks)))
        out.append(t[len(prompt) - 1:len(prompt) - 1 + len(served)])
    del params
    gc.collect()
    return out


def compare(conf, seed: int, picked, limits,
            sample_tokens: int = harness.SAMPLE_TOKENS):
    """Program and control readings on the served (prompt, tokens), and
    each judged with ``limits``."""
    if float(conf["serve"]["sparsity"]) > 0:
        kind = "program int8 weights"
        alt = program_int8_tokens(conf, seed, picked)
    else:
        kind = "reference int8 weights"
        alt = [g["top"] for g in harness.reference_gaps(
            conf, seed, picked, int8=True)]
    gaps = harness.reference_gaps(conf, seed, picked, alt=alt)
    out = {"seed": seed, "control": kind, "requests": len(picked)}
    for side, key in (("program", "served"), ("controlled", "alt")):
        v = np.concatenate([g[key] for g in gaps])
        ok, checks = harness.judge([{"served": g[key]} for g in gaps],
                                   limits, 0, 0, sample_tokens)
        out[side] = {"tokens": int(v.size), "gap_max": float(v.max()),
                     "gap_mean": float(v.mean()), "correct": ok,
                     "checks": checks}
    return out


def readings(cell, seed: int, seconds: float):
    """Serve the cell's window, then compare on its sample."""
    conf, mix = cell.config, cell.mix
    eng, cfg, _ = harness.build_engine(conf, mix, seed, False)
    harness.warm_up(eng, mix)
    win = harness.Window(eng, mix, seed, cell.cell.get("rate_per_s"),
                         cfg.vocab_size)
    win.run(seconds)
    picked = [(np.asarray(r.prompt), list(r.out_tokens))
              for r in harness.sample(win.finished(), seed)]
    eng.on_token = None
    del eng, win
    gc.collect()
    return compare(conf, seed, picked, cell.cell["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.check_devices(cell.chips)
    harness.enable_cache()
    if args.fault:
        faults.FAULTS[args.fault](setattr)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        if args.fault:
            res = harness.run(cell, seed, args.seconds, False, t_start=t0)
            r = {"seed": seed, "fault": args.fault,
                 "correct": res["correct"], "checks": res["checks"]}
        else:
            r = readings(cell, seed, args.seconds)
        r["seconds"] = time.monotonic() - t0
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
