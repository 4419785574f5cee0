"""The plain reference against the engine's own programs at a tiny size
on the CPU, in float32: the bucketed, left-padded batched prefill that
admission runs, then cached decode through the KV ring, compared on
logits at every position. One configuration carries qk-norm and the
packed tile-skip FFN (half the tiles pruned), the other qkv bias on
the dense path."""
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import harness
import reference
import weights

SEED = 2**31 + 5
CACHE = 64
BUCKET = 32


def program(name):
    from repro.launch import serve
    conf = bench_tiny.config(name, torch_dtype="float32")
    conf["serve"] = dict(conf["serve"], compute_dtype="float32")
    spec = weights.Spec.from_config(conf)
    key = weights.root_key(SEED)
    W = weights.make(key, spec)
    keep = weights.keep_masks(key, spec) if spec.sparsity else None
    cfg = harness.model_config(conf)
    params = harness.program_tree(W, cfg)
    s = conf["serve"]
    params, cfg = serve.build_serving_params(
        params, cfg, path=s["path"], sparsity=s["sparsity"],
        block_k=s["block"], block_n=s["block"], scope=s["scope"],
        verbose=False)
    return spec, W, keep, params, cfg


@pytest.mark.parametrize("name", ["qwen3-32b-sasp50", "qwen2.5-32b-dense"])
def test_engine_prefill_and_cached_decode_match_the_reference(name):
    from repro.models import lm
    from repro.serve.engine import Engine
    spec, W, keep, params, cfg = program(name)
    if spec.sparsity:
        assert "sasp_fused" in params["segments"][0]["slot0"]["ffn"]
    eng = Engine(params, cfg, batch_slots=2, cache_len=CACHE,
                 buckets=(BUCKET,))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, spec.vocab, n).astype(np.int32)
               for n in (20, 13)]
    follow = rng.integers(0, spec.vocab, (2, 6)).astype(np.int32)
    toks = np.zeros((2, BUCKET), np.int32)
    poss = np.tile(np.arange(BUCKET) - BUCKET, (2, 1)).astype(np.int32)
    for i, p in enumerate(prompts):
        toks[i, BUCKET - len(p):] = p
        poss[i] = np.arange(BUCKET) - (BUCKET - len(p))
    last, caches = eng._prefill(params, jnp.asarray(toks),
                                jnp.asarray(poss), eng.caches,
                                jnp.asarray([0, 1], jnp.int32),
                                jnp.asarray([True, True]))
    got = [[np.asarray(last[i])] for i in range(2)]
    pos = np.array([len(p) for p in prompts], np.int32)
    for t in range(follow.shape[1]):
        lg, caches = lm.decode_step(params, cfg,
                                    jnp.asarray(follow[:, t:t + 1]),
                                    jnp.asarray(pos + t), caches)
        for i in range(2):
            got[i].append(np.asarray(lg[i, 0]))
    for i, p in enumerate(prompts):
        ref = reference.logits(spec, W, keep,
                               np.concatenate([p, follow[i]]))
        want = ref[len(p) - 1:]
        diff = np.max(np.abs(np.stack(got[i]) - want))
        # float32 both sides; only the order of summation differs
        assert diff <= 1e-4 * np.max(np.abs(want)), diff


def test_the_reference_sees_the_pruned_tiles():
    spec, W, keep, _, _ = program("qwen3-32b-sasp50")
    seq = np.arange(1, 17, dtype=np.int32)
    pruned = reference.logits(spec, W, keep, seq)
    unpruned = reference.logits(spec, W, None, seq)
    assert np.max(np.abs(pruned - unpruned)) > 1e-3 * np.max(
        np.abs(unpruned))
    for name, k in keep.items():
        frac = 1 - float(np.mean(np.asarray(k)))
        assert frac == pytest.approx(spec.sparsity), name
