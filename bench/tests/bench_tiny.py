"""Small cells for the CPU tests: the real configuration files with
their widths shrunk, and a short closed-loop mix.

``TINY`` keeps the files' weight scales; ``SMALL`` is wider and scales
the weights by sqrt(5120 / 512), so that activations and logits have
the spread they have at the published width (the control test needs
that: at the tiny width the int8 control barely moves the tokens)."""
import json
import os

import harness
import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
SMALL = dict(hidden_size=512, intermediate_size=1024, num_hidden_layers=2,
             num_attention_heads=8, num_key_value_heads=2, vocab_size=1024)
SMALL_SCALE = (5120 / 512) ** 0.5
MIX = {"loop": "closed", "clients": 2, "slots": 2, "cache_len": 128,
       "buckets": [32, 64],
       "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
       "output": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
       "strata": 4, "lead_s": 0.5}
# the tiny bf16 cell: sound runs read a mean gap near 2e-5
LIMITS = {"gap_mean": 0.001}


def config(name, small=False, **over):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as fh:
        conf = json.load(fh)
    conf.update(SMALL if small else TINY)
    if "head_dim" in conf:
        conf["head_dim"] = 64
    if small:
        w = conf["weights"]
        conf["weights"] = dict(w, std=w["std"] * SMALL_SCALE,
                               out_std=w["out_std"] * SMALL_SCALE)
    conf.update(over)
    return conf


def cell(name="qwen3-32b-sasp50", small=False, mix=None, limits=None,
         **over):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    return harness.Cell(
        name="tiny", chips=1, config=config(name, small, **over),
        mix=traffic.Mix.from_dict("tiny", mix or MIX),
        cell={"limits": dict(limits or LIMITS)},
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


def run(c, seed=2**31 + 11, seconds=2.0):
    import time
    return harness.run(c, seed, seconds, False, t_start=time.monotonic(),
                       require_tpu=False, cache=False, sample_tokens=120)
