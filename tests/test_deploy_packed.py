"""Packed deployment pipeline: deploy_packed parity vs the masked-dense
reference across forward/prefill/decode, engine fast-path semantics
(batched left-padded prefill, on-device sampling, EOS masking), and
hypothesis property tests over random (tp, sparsity, block size,
int8/fp32) packing configs — visit-count conservation and
reshard↔from-scratch bit-identity as PROPERTIES, with fixed-grid twins
that run even where hypothesis is unavailable."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # twins below still run
    HAVE_HYPOTHESIS = False

from repro.configs import SASPConfig, get_config, reduced
from repro.core.deploy import deploy_packed, packed_summary
from repro.core.pruning import prune_params
from repro.models import lm
from repro.serve.engine import Engine, Request

KEY = jax.random.PRNGKey(0)


def _pruned(scope="all", sparsity=0.5, layers=2, d_model=64, vocab=64):
    sasp = SASPConfig(enabled=True, block_k=16, block_n=16,
                      sparsity=sparsity, scope=scope)
    cfg = dataclasses.replace(
        reduced(get_config("qwen3-32b"), layers=layers, d_model=d_model,
                vocab=vocab),
        sasp=sasp)
    params = lm.init_params(KEY, cfg)
    pruned, _ = prune_params(params, sasp)
    return pruned, cfg


@pytest.mark.parametrize("fuse_ffn", [True, False])
@pytest.mark.parametrize("scope", ["ffn", "all"])
def test_deploy_packed_forward_parity(scope, fuse_ffn):
    pruned, cfg = _pruned(scope=scope)
    toks = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    ref = lm.forward(pruned, cfg, toks)
    pp, pcfg = deploy_packed(pruned, cfg, fuse_ffn=fuse_ffn)
    got = lm.forward(pp, pcfg, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_deploy_packed_prefill_decode_parity():
    pruned, cfg = _pruned()
    toks = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    lg0, c0 = lm.prefill(pruned, cfg, toks, cache_len=32)
    pp, pcfg = deploy_packed(pruned, cfg)
    lg1, c1 = lm.prefill(pp, pcfg, toks, cache_len=32)
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg0),
                               rtol=1e-4, atol=1e-4)
    t = jnp.asarray([[int(jnp.argmax(lg0[0, 0]))]], jnp.int32)
    pos = jnp.asarray([8], jnp.int32)
    d0, _ = lm.decode_step(pruned, cfg, t, pos, c0)
    d1, _ = lm.decode_step(pp, pcfg, t, pos, c1)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d0),
                               rtol=1e-4, atol=1e-4)


def test_deploy_packed_int8_close():
    pruned, cfg = _pruned(scope="ffn")
    toks = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    ref = np.asarray(lm.forward(pruned, cfg, toks))
    pp, pcfg = deploy_packed(pruned, cfg, quantize=True)
    got = np.asarray(lm.forward(pp, pcfg, toks))
    scale = np.abs(ref).max() + 1e-9
    assert np.abs(got - ref).max() / scale < 5e-2


def test_packed_summary_reports_compression():
    pruned, cfg = _pruned(scope="all", sparsity=0.5)
    pp, _ = deploy_packed(pruned, cfg)
    s = packed_summary(pp)
    assert s["n_fused_ffns"] == 1          # one stacked FFN container
    assert s["n_packed_matrices"] == 4     # wq/wk/wv/wo stacked
    assert 0 < s["compression"] < 1.0      # strictly smaller than dense


def _live_visits(vals) -> int:
    """Visits whose block carries any nonzero value (padding visits are
    zero-valued by construction)."""
    v = np.asarray(vals)
    return int(np.count_nonzero(np.any(v != 0, axis=(-2, -1))))


@pytest.mark.parametrize("kind", ["col", "row"])
def test_tp_shard_visit_counts_sum_to_unsharded(kind):
    """TP-sharded packing (DESIGN.md §10) must conserve work: the
    per-shard live visit counts sum to the unsharded nnz — no block is
    dropped and none is double-visited."""
    from repro.core.deploy import pack_weight

    rng = np.random.default_rng(0)
    K, N, bk, bn = 32, 64, 8, 8
    w = rng.normal(size=(2, K, N)).astype(np.float32)      # L-stacked
    mask = rng.random((2, K // bk, N // bn)) > 0.5
    wz = (w.reshape(2, K // bk, bk, N // bn, bn)
          * mask[:, :, None, :, None]).reshape(2, K, N)

    pw0 = pack_weight(wz, block_k=bk, block_n=bn)
    pw2 = pack_weight(wz, block_k=bk, block_n=bn, tp=2, shard_kind=kind)
    assert pw2.shards == 2 and pw2.shard_kind == kind
    assert pw2.vals.shape[:2] == (2, 2)        # (L, tp, nnz, bk, bn)
    for layer in range(2):
        ref = _live_visits(pw0.vals[layer])
        got = sum(_live_visits(pw2.vals[layer, s]) for s in range(2))
        assert got == ref == int(mask[layer].sum()), (layer, got, ref)


def test_tp_sharded_deploy_single_device_parity():
    """A mesh-deployed (TP-sharded) param tree must stay loadable and
    exact on a single device: the shard-loop fallback drivers reproduce
    the unsharded packed forward bit-for-bit (col shards) / within fp32
    summation-order noise (row/fused reductions). sparsity=0.25 so the
    FFN path carries nonzero signal (at 0.5 this reduced config prunes
    the whole d_ff grid and the comparison proves nothing about the
    shard reduction)."""
    pruned, cfg = _pruned(scope="all", sparsity=0.25)
    toks = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    for fuse_ffn in (True, False):
        pp0, c0 = deploy_packed(pruned, cfg, fuse_ffn=fuse_ffn)
        pp2, c2 = deploy_packed(pruned, cfg, fuse_ffn=fuse_ffn, tp=2)
        slot = pp2["segments"][0]["slot0"]
        cont = slot["ffn"]["sasp_fused"] if fuse_ffn \
            else slot["ffn"]["sasp_packed"]["w1"]
        assert cont.shards == 2            # sharding actually engaged
        assert slot["mixer"]["sasp_packed"]["wo"].shards == 2
        ref = lm.forward(pp0, c0, toks)
        got = lm.forward(pp2, c2, toks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def _assert_trees_equal(a, b):
    """Exact (bitwise) equality of two packed param trees."""
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree_util.tree_leaves_with_path(b)
    assert len(la) == len(lb), (len(la), len(lb))
    for (pa, xa), (pb, xb) in zip(la, lb):
        xa, xb = np.asarray(xa), np.asarray(xb)
        assert xa.shape == xb.shape and np.array_equal(xa, xb), \
            jax.tree_util.keystr(pa)


@pytest.mark.parametrize("fuse_ffn", [True, False])
def test_reshard_packed_matches_from_scratch(fuse_ffn):
    """Elastic re-deploy fast path (ROADMAP): re-partitioning an
    existing unsharded pack by slicing + padding its visit lists must be
    BIT-IDENTICAL to packing from scratch at the new tp — same visit
    sets, same empty-column flush entries, same shared-nnz padding."""
    from repro.core.deploy import reshard_packed

    pruned, cfg = _pruned(scope="all", sparsity=0.25)
    pp1, _ = deploy_packed(pruned, cfg, fuse_ffn=fuse_ffn)
    pp2, _ = deploy_packed(pruned, cfg, fuse_ffn=fuse_ffn, tp=2)
    rs = reshard_packed(pp1, cfg, tp=2)
    _assert_trees_equal(pp2["segments"], rs["segments"])


def test_reshard_packed_quantized_and_roundtrip():
    """int8 containers reshard exactly too (per-visit scales travel with
    their visits; epsilon scales of flush entries match), and resharding
    back to tp=1 reproduces the original pack — so mesh-shape changes
    can go sharded→sharded without keeping the unsharded pack around."""
    from repro.core.deploy import reshard_packed

    pruned, cfg = _pruned(scope="all", sparsity=0.25)
    for fuse_ffn in (True, False):
        pp1, _ = deploy_packed(pruned, cfg, fuse_ffn=fuse_ffn,
                               quantize=True)
        pp2, _ = deploy_packed(pruned, cfg, fuse_ffn=fuse_ffn,
                               quantize=True, tp=2)
        rs = reshard_packed(pp1, cfg, tp=2)
        _assert_trees_equal(pp2["segments"], rs["segments"])
        back = reshard_packed(rs, cfg, tp=1)
        _assert_trees_equal(pp1["segments"], back["segments"])


def test_reshard_packed_forward_parity():
    """The resharded tree must also SERVE identically: single-device
    shard-loop forward of reshard(tp=2) matches the unsharded packed
    forward (same contract as the from-scratch sharded deploy)."""
    from repro.core.deploy import reshard_packed

    pruned, cfg = _pruned(scope="all", sparsity=0.25)
    toks = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    pp1, c1 = deploy_packed(pruned, cfg)
    rs = reshard_packed(pp1, cfg, tp=2)
    ref = lm.forward(pp1, c1, toks)
    got = lm.forward(rs, c1, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Property tests: random packing configs (hypothesis + fixed twins)
# ---------------------------------------------------------------------------


def _random_blockmasked(seed, K, N, bk, bn, sparsity, layers=2):
    """(L, K, N) weights with a random block mask applied — the input
    contract of pack_weight (pruned tiles already zeroed)."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(layers, K, N)).astype(np.float32)
    mask = rng.random((layers, K // bk, N // bn)) >= sparsity
    wz = (w.reshape(layers, K // bk, bk, N // bn, bn)
          * mask[:, :, None, :, None]).reshape(layers, K, N)
    return wz, mask


def _assert_packed_equal(a, b, ctx):
    for name in ("vals", "kn", "scale", "bias"):
        xa, xb = getattr(a, name), getattr(b, name)
        assert (xa is None) == (xb is None), (name, ctx)
        if xa is not None:
            assert np.array_equal(np.asarray(xa), np.asarray(xb)), \
                (name, ctx)
    assert a.shards == b.shards and a.shard_kind == b.shard_kind, ctx
    assert a.shape == b.shape and a.block == b.block, ctx


def _check_pack_properties(tp, sparsity, block, quantize, kind, seed):
    """The two properties, on one random config:

    1. **Visit conservation** — per layer, the shards' live (nonzero-
       valued) visits sum to the mask's surviving block count: no block
       dropped, none double-visited, at ANY shard count/sparsity
       (including entirely-empty shards, which carry only zero-valued
       flush/padding visits).
    2. **Reshard ↔ from-scratch bit-identity** — slicing + re-padding
       an existing pack to ``tp`` equals packing the dense weight from
       scratch at ``tp`` bit-for-bit (values, coords, int8 scales), and
       resharding back to 1 reproduces the original pack.
    """
    from repro.core.deploy import _reshard_weight, pack_weight

    K = N = 32
    wz, mask = _random_blockmasked(seed, K, N, block, block, sparsity)
    base = pack_weight(wz, block_k=block, block_n=block,
                       quantize=quantize)
    scratch = pack_weight(wz, block_k=block, block_n=block, tp=tp,
                          shard_kind=kind, quantize=quantize)
    ctx = dict(tp=tp, sparsity=sparsity, block=block,
               quantize=quantize, kind=kind, seed=seed)
    for layer in range(wz.shape[0]):
        ref = int(mask[layer].sum())
        v = np.asarray(scratch.vals)[layer]
        got = _live_visits(v) if tp == 1 else sum(
            _live_visits(v[s]) for s in range(tp))
        assert got == ref, (layer, got, ref, ctx)
    rs = _reshard_weight(base, tp, kind)
    _assert_packed_equal(rs, scratch, ctx)
    back = _reshard_weight(rs, 1, kind)
    _assert_packed_equal(back, base, ctx)


if HAVE_HYPOTHESIS:
    @pytest.mark.slow
    @settings(max_examples=40, deadline=None)
    @given(tp=st.sampled_from([1, 2, 4]),
           sparsity=st.floats(0.0, 0.97),
           block=st.sampled_from([4, 8]),
           quantize=st.booleans(),
           kind=st.sampled_from(["col", "row"]),
           seed=st.integers(0, 2**16))
    def test_pack_weight_properties_random_configs(
            tp, sparsity, block, quantize, kind, seed):
        _check_pack_properties(tp, sparsity, block, quantize, kind, seed)


@pytest.mark.parametrize("tp,kind", [(2, "col"), (4, "row")])
@pytest.mark.parametrize("quantize", [False, True])
def test_pack_weight_properties_fixed_grid(tp, kind, quantize):
    """Hypothesis-free twin of the property test (runs everywhere),
    including a high-sparsity case that forces empty shards."""
    for sparsity, seed in ((0.3, 0), (0.9, 1)):
        _check_pack_properties(tp, sparsity, 8, quantize, kind, seed)


def _check_deploy_reshard_property(tp, sparsity, quantize):
    """Deploy-level property: for a whole deployed tree (fused FFN +
    attention containers), reshard_packed to ``tp`` is bit-identical to
    deploy_packed from scratch at ``tp``, and round-trips back."""
    from repro.core.deploy import reshard_packed

    pruned, cfg = _pruned(scope="all", sparsity=sparsity)
    pp1, _ = deploy_packed(pruned, cfg, quantize=quantize)
    pp2, _ = deploy_packed(pruned, cfg, quantize=quantize, tp=tp)
    rs = reshard_packed(pp1, cfg, tp=tp)
    _assert_trees_equal(pp2["segments"], rs["segments"])
    back = reshard_packed(rs, cfg, tp=1)
    _assert_trees_equal(pp1["segments"], back["segments"])


if HAVE_HYPOTHESIS:
    @pytest.mark.slow
    @settings(max_examples=5, deadline=None)
    @given(tp=st.sampled_from([1, 2]),
           sparsity=st.sampled_from([0.25, 0.5]),
           quantize=st.booleans())
    def test_deploy_reshard_property_random_configs(
            tp, sparsity, quantize):
        _check_deploy_reshard_property(tp, sparsity, quantize)


def test_deploy_reshard_property_fixed():
    """Hypothesis-free twin of the deploy-level reshard property."""
    _check_deploy_reshard_property(2, 0.25, quantize=True)


def test_engine_packed_matches_masked_engine_tokens():
    pruned, cfg = _pruned(scope="ffn", sparsity=0.5)
    pp, pcfg = deploy_packed(pruned, cfg)
    prompt = np.arange(1, 11, dtype=np.int32)
    a = Engine(pruned, cfg, batch_slots=1, cache_len=64).run(
        [Request(rid=0, prompt=prompt, max_new_tokens=6)])[0].out_tokens
    b = Engine(pp, pcfg, batch_slots=1, cache_len=64).run(
        [Request(rid=0, prompt=prompt, max_new_tokens=6)])[0].out_tokens
    assert a == b


def test_batched_prefill_slot_isolation():
    """Multi-slot batched (left-padded) prefill must be bit-equivalent
    to solo serving for every sequence, across unequal prompt lengths."""
    cfg = reduced(get_config("qwen3-32b"), layers=2, d_model=64, vocab=64)
    params = lm.init_params(KEY, cfg)
    prompts = [np.arange(1, 7, dtype=np.int32),
               np.arange(30, 40, dtype=np.int32),
               np.arange(5, 13, dtype=np.int32)]
    solo = [Engine(params, cfg, batch_slots=1, cache_len=64).run(
        [Request(rid=0, prompt=p, max_new_tokens=5)])[0].out_tokens
        for p in prompts]
    eng = Engine(params, cfg, batch_slots=3, cache_len=64)
    together = eng.run([Request(rid=i, prompt=p, max_new_tokens=5)
                        for i, p in enumerate(prompts)])
    got = {r.rid: r.out_tokens for r in together}
    for i in range(len(prompts)):
        assert got[i] == solo[i], i


def test_engine_eos_stops_early():
    cfg = reduced(get_config("qwen3-32b"), layers=2, d_model=64, vocab=64)
    params = lm.init_params(KEY, cfg)
    prompt = np.arange(1, 9, dtype=np.int32)
    ref = Engine(params, cfg, batch_slots=1, cache_len=64).run(
        [Request(rid=0, prompt=prompt, max_new_tokens=8)])[0].out_tokens
    assert len(ref) == 8
    eos = ref[2]                      # appears in the greedy stream
    out = Engine(params, cfg, batch_slots=1, cache_len=64).run(
        [Request(rid=0, prompt=prompt, max_new_tokens=8,
                 eos_id=int(eos))])[0].out_tokens
    stop = ref.index(eos) + 1         # first emission, EOS included
    assert out == ref[:stop]


def test_engine_temperature_sampling_on_device():
    cfg = reduced(get_config("qwen3-32b"), layers=2, d_model=64, vocab=64)
    params = lm.init_params(KEY, cfg)
    prompt = np.arange(1, 9, dtype=np.int32)
    outs = set()
    for seed in range(3):
        eng = Engine(params, cfg, batch_slots=1, cache_len=64,
                     rng_seed=seed)
        r = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=8,
                             temperature=1.5)])[0]
        assert len(r.out_tokens) == 8
        assert all(0 <= t < cfg.vocab_size for t in r.out_tokens)
        outs.add(tuple(r.out_tokens))
    assert len(outs) > 1              # different seeds, different streams


# ---------------------------------------------------------------------------
# The layer scan reads each layer's fused FFN container in place
# (models/lm.py ``_scan_layers``): same arithmetic as handing each layer
# its own slice, and the same program as before for everything else.
# ---------------------------------------------------------------------------


def _in_place_model(quantize: bool):
    """qwen3-shaped, 3 layers at d 256, bf16, FFN tiles pruned at 0.5 on
    64×64 blocks (8 d_ff blocks), packed into the fused container. w2 is
    rescaled to w1's magnitude first, so global pruning leaves live w2
    tiles (as chip_smoke.py does)."""
    sasp = SASPConfig(enabled=True, block_k=64, block_n=64, sparsity=0.5,
                      scope="ffn")
    cfg = dataclasses.replace(
        reduced(get_config("qwen3-32b"), layers=3, d_model=256, vocab=64),
        sasp=sasp, param_dtype="bfloat16", compute_dtype="bfloat16")
    params = lm.init_params(KEY, cfg)
    ffn = params["segments"][0]["slot0"]["ffn"]
    w1, w2 = ffn["w1"]["w"], ffn["w2"]["w"]
    r = jnp.mean(jnp.abs(w1.astype(jnp.float32))) / jnp.mean(
        jnp.abs(w2.astype(jnp.float32)))
    ffn["w2"] = {"w": (w2.astype(jnp.float32) * r).astype(w2.dtype)}
    pruned, _ = prune_params(params, sasp)
    pp, pcfg = deploy_packed(pruned, cfg, quantize=quantize)
    fused = pp["segments"][0]["slot0"]["ffn"]["sasp_fused"]
    assert fused.shards == 1 and fused.w1v.shape[0] == 3
    assert _live_visits(fused.w2v) > 0           # real work in the FFN
    return pp, pcfg


def _layer_loop(params, cfg, x, caches, run_slot):
    """Reference for the layer scan: a Python loop over layers, each
    handed its own slice of every stacked param, so the fused FFN runs
    on a 3-D container (the kernel's stack of one)."""
    plan = lm.segment_plan(cfg)
    caches = caches or (None,) * len(plan)
    out = []
    for seg, seg_c, (pattern, repeat) in zip(params["segments"], caches,
                                             plan):
        per_layer = []
        for i in range(repeat):
            sp = jax.tree.map(lambda a: a[i], seg)
            c = None if seg_c is None else jax.tree.map(lambda a: a[i],
                                                        seg_c)
            new = {}
            for slot, spec in enumerate(pattern):
                name = f"slot{slot}"
                x, new[name] = run_slot(sp[name], spec, x,
                                        None if c is None else c[name])
            per_layer.append(new)
        out.append(jax.tree.map(lambda *a: jnp.stack(a), *per_layer))
    return x, tuple(out)


def _loop_prefill(params, cfg, tokens, cache_len):
    x = lm._embed_in(params, cfg, tokens, None)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)

    def run(sp, spec, x, _):
        x, _, c = lm._apply_slot_full(sp, spec, cfg, x, positions, True,
                                      cache_len)
        return x, c
    x, caches = _layer_loop(params, cfg, x, None, run)
    return lm.logits_fn(params, cfg, x[:, -1:]), caches


def _loop_decode(params, cfg, tokens, pos, caches):
    x = lm._embed_in(params, cfg, tokens, None)
    x, caches = _layer_loop(
        params, cfg, x, caches, lambda sp, spec, x, c:
        lm._apply_slot_decode(sp, spec, cfg, x, pos, c))
    return lm.logits_fn(params, cfg, x), caches


def _loop_prefill_past(params, cfg, tokens, positions, past):
    x = lm._embed_in(params, cfg, tokens, None)
    x, caches = _layer_loop(
        params, cfg, x, past, lambda sp, spec, x, c:
        lm._apply_slot_prefill_past(sp, spec, cfg, x, positions, c))
    return lm.logits_fn(params, cfg, x[:, -1:]), caches


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _sliced_scan(body, carry, seg_params, xs=None):
    """The layer scan as it was: every param scanned, so each layer's
    fused FFN container arrives as a per-layer slice."""
    return jax.lax.scan(body, carry, (seg_params, xs))


# Every bf16 value rounded where the program computes it: XLA may keep
# bf16 intermediates in float32, and does so differently in a loop body
# and in straight-line code.
_NO_EXCESS = {"xla_allow_excess_precision": False}


@pytest.mark.parametrize("ref", ["sliced_scan", "layer_loop"])
@pytest.mark.parametrize("quantize", [False, True], ids=["bf16", "int8"])
def test_in_place_fused_ffn_matches_per_layer_slices(monkeypatch,
                                                     quantize, ref):
    """Prefill, decode and suffix prefill through the layer scan, which
    reads the stacked fused FFN container in place by layer index, are
    bit-identical to handing each layer its own slice: the scan that
    slices every param, or a Python loop over layers."""
    pp, cfg = _in_place_model(quantize)
    assert not cfg.logit_softcap
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(1, 64, (2, 8)), jnp.int32)
    step = jnp.asarray([[3], [5]], jnp.int32)
    pos = jnp.asarray([8, 8], jnp.int32)
    suffix = jnp.asarray(rng.integers(1, 64, (2, 4)), jnp.int32)
    spos = jnp.broadcast_to(jnp.arange(9, 13, dtype=jnp.int32), (2, 4))
    programs = {
        "prefill": lambda p, c: lm.prefill(p, cfg, toks, cache_len=16),
        "decode": lambda p, c: lm.decode_step(p, cfg, step, pos, c),
        "prefill_past": lambda p, c: lm.prefill_with_past(p, cfg, suffix,
                                                          spos, c),
    }
    opts = None
    if ref == "layer_loop":
        opts = _NO_EXCESS
        refs = {
            "prefill": lambda p, c: _loop_prefill(p, cfg, toks, 16),
            "decode": lambda p, c: _loop_decode(p, cfg, step, pos, c),
            "prefill_past": lambda p, c: _loop_prefill_past(
                p, cfg, suffix, spos, c),
        }
    caches = jax.jit(programs["prefill"])(pp, None)[1]
    got = {k: jax.jit(f, compiler_options=opts)(pp, caches)
           for k, f in programs.items()}
    if ref == "sliced_scan":
        monkeypatch.setattr(lm, "_scan_layers", _sliced_scan)
        jax.clear_caches()              # trace the programs again
        refs = programs
    for k, f in refs.items():
        _assert_trees_equal(got[k], jax.jit(f, compiler_options=opts)(
            pp, caches))


@pytest.mark.parametrize("entry", ["prefill", "decode", "prefill_past",
                                   "loss"])
@pytest.mark.parametrize("tree", ["dense", "masked", "packed_per_matrix"])
def test_programs_without_fused_ffn_unchanged(monkeypatch, tree, entry):
    """A param tree without an unsharded fused FFN container traces to
    the same program as a scan that keeps every param in its scanned
    operands: the in-place read changes nothing else."""
    pruned, cfg = _pruned(scope="all", sparsity=0.25)
    if tree == "dense":
        params, cfg = lm.init_params(KEY, cfg), dataclasses.replace(
            cfg, sasp=SASPConfig())
    elif tree == "masked":
        params = pruned
    else:
        params, cfg = deploy_packed(pruned, cfg, fuse_ffn=False)
    toks = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    _, caches = lm.prefill(params, cfg, toks, cache_len=16)
    pos = jnp.asarray([8], jnp.int32)
    spos = jnp.arange(8, 10, dtype=jnp.int32)[None]
    fns = {
        "prefill": lambda p: lm.prefill(p, cfg, toks, cache_len=16),
        "decode": lambda p: lm.decode_step(p, cfg, toks[:, :1], pos,
                                           caches),
        "prefill_past": lambda p: lm.prefill_with_past(
            p, cfg, toks[:, :2], spos, caches),
        "loss": lambda p: lm.loss_fn(p, cfg, {"tokens": toks})[0],
    }
    got = str(jax.make_jaxpr(fns[entry])(params))
    monkeypatch.setattr(lm, "_scan_layers", _sliced_scan)
    assert str(jax.make_jaxpr(fns[entry])(params)) == got


def test_layer_scan_does_not_slice_fused_ffn():
    """The decode program's layer scan carries the stacked fused FFN
    weights as loop-invariant operands, not as per-layer slices."""
    pp, cfg = _in_place_model(False)
    fused = pp["segments"][0]["slot0"]["ffn"]["sasp_fused"]
    _, caches = lm.prefill(pp, cfg, jnp.ones((1, 4), jnp.int32),
                           cache_len=8)
    jaxpr = jax.make_jaxpr(lambda p: lm.decode_step(
        p, cfg, jnp.ones((1, 1), jnp.int32), jnp.asarray([4], jnp.int32),
        caches))(pp)
    (scan,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    nc = scan.params["num_consts"]
    nx = len(scan.invars) - nc - scan.params["num_carry"]
    consts = [v.aval.shape for v in scan.invars[:nc]]
    xs = [v.aval.shape for v in scan.invars[-nx:]]
    for w in (fused.w1v, fused.w3v, fused.w2v):
        assert w.shape in consts and w.shape not in xs
