"""Shared helpers for the per-figure benchmarks."""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Optional

import numpy as np

QOS_CACHE = os.path.join("experiments", "qos_results.json")
DRYRUN_DIR = os.path.join("experiments", "dryrun")


def load_qos() -> Optional[Dict]:
    if os.path.exists(QOS_CACHE):
        with open(QOS_CACHE) as f:
            return json.load(f)
    return None


def measured_qos_fn(qos: Dict) -> Callable[[int, float, str], float]:
    """Interpolating qos_fn(tile, sparsity, quant) from the trained-model
    sweep — feeds the codesign explorer with MEASURED degradation."""
    table: Dict = {}
    for r in qos["records"]:
        table.setdefault((r["tile"], r["quant"]), []).append(
            (r["rate"], r["ter"]))
    for k in table:
        table[k].sort()

    def fn(tile, sparsity, quant):
        key = (tile, quant)
        if key not in table:
            key = min(table, key=lambda k: abs(k[0] - tile))
        xs, ys = zip(*table[key])
        return float(np.interp(sparsity, xs, ys))

    return fn


def load_dryrun_reports() -> List[Dict]:
    out = []
    if not os.path.isdir(DRYRUN_DIR):
        return out
    for f in sorted(os.listdir(DRYRUN_DIR)):
        if f.endswith(".json"):
            with open(os.path.join(DRYRUN_DIR, f)) as fh:
                out.append(json.load(fh))
    return out


def time_fn(fn, *args, reps: int = 5, warmup: int = 2) -> float:
    """Median wall time in microseconds."""
    for _ in range(warmup):
        r = fn(*args)
    _block(r)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        r = fn(*args)
        _block(r)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def _block(r):
    import jax
    jax.block_until_ready(r)


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.2f},{derived}")
