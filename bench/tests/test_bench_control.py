"""The control of the output check comes out as not correct: the
precision below the configurations' bf16 (int8 weights; the program's
own int8 path where it has one) moves the served tokens' mean gap
below the float32 reference past a limit that the sound bf16 program
passes, judged by the harness's own ``judge``. On the chip, at the
cells' size, the same comparison (``control.compare``) sets the cells'
limits (PERF.md); here it runs on the CPU at a small width, on three
seeds, over requests the engine serves to completion (so that no clock
decides which are compared), with a limit between the readings of this
size."""
import itertools

import numpy as np
import pytest

import bench_tiny
import control
import harness
import traffic

MIX = dict(bench_tiny.MIX,
           output={"median": 24, "sigma": 0.5, "min": 12, "max": 48})
N_REQUESTS = 12
# readings at this size (CPU), seeds 1-3: the program at most 3.8e-4
# (sasp50) and 6.1e-4 (dense), the control at least 1.2e-3 and 2.1e-3
LIMIT = {"qwen3-32b-sasp50": 6.5e-4, "qwen2.5-32b-dense": 1.2e-3}


def served(c, seed):
    from repro.serve.engine import Request
    eng, cfg, _ = harness.build_engine(c.config, c.mix, seed)
    reqs = [Request(rid=i, prompt=g["prompt"], max_new_tokens=g["max_new"])
            for i, g in enumerate(itertools.islice(
                traffic.requests(c.mix, seed, cfg.vocab_size), N_REQUESTS))]
    eng.run(reqs)
    return [(np.asarray(r.prompt), list(r.out_tokens)) for r in reqs]


@pytest.mark.parametrize("name", sorted(LIMIT))
def test_the_control_is_not_correct(name):
    c = bench_tiny.cell(name, small=True, mix=MIX)
    for seed in (1, 2, 3):
        r = control.compare(c.config, seed, served(c, seed),
                            {"gap_mean": LIMIT[name]}, sample_tokens=800)
        assert r["program"]["tokens"] >= 200, r
        assert r["program"]["correct"], r
        assert not r["controlled"]["correct"], r
        assert r["controlled"]["checks"]["gap_mean"][0] > LIMIT[name], r
