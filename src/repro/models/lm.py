"""Decoder-only LM assembled from the substrate modules.

Layer stacks are compiled into a **segment plan**: the per-layer kind
sequence (mixer ∈ {attn, mamba} × attn-locality × ffn ∈ {dense, moe}) is
compressed into segments ``(pattern, repeat)`` where ``pattern`` is a short
tuple of layer specs and ``repeat`` is how many times it tiles. Each segment
runs as one ``lax.scan`` over stacked params with the pattern unrolled in
the body — e.g. jamba-1.5 (72 layers) is one scan over 9 repeats of an
8-layer pattern [7×mamba + 1×attn, alternating dense/MoE FFN], and gemma3
(34 layers) is a scan over 5 repeats of [5×local + 1×global] plus a
4-layer local remainder segment. This keeps compile time flat in depth
(1-core container; 70+ dry-run lowers) while supporting heterogeneous
stacks exactly.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ATTN_LOCAL,
    FFN_MOE,
    MIXER_ATTN,
    ModelConfig,
)
from repro.core.sparse import PackedFFN
from repro.models import attention as attn_mod
from repro.models import ffn as ffn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import KVCache
from repro.models.modules import (
    as_dtype,
    embedding_apply,
    embedding_init,
    fold_rng,
    rmsnorm_apply,
    rmsnorm_init,
    softcap,
)
from repro.models.ssm import SSMCache

LayerSpec = Tuple[int, int, int]            # (mixer, attn_kind, ffn_kind)


def _moe_dispatch(p, cfg, x):
    """EP (shard_map all_to_all) when an active mesh supports it, else
    the single-shard path."""
    from repro.distribution import context as dctx
    from repro.distribution.moe_ep import can_use_ep, moe_ffn_dp, \
        moe_ffn_ep
    mesh = dctx.active_mesh()
    if mesh is not None and dctx.sharding_profile() == "dp_only":
        return moe_ffn_dp(p, cfg, x, mesh)
    if can_use_ep(cfg, x.shape, mesh):
        return moe_ffn_ep(p, cfg, x, mesh)
    return moe_mod.moe_ffn_local(p, cfg, x)
Segment = Tuple[Tuple[LayerSpec, ...], int]  # (pattern, repeat)


def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


def segment_plan(cfg: ModelConfig) -> List[Segment]:
    mixers = cfg.layer_mixer_kinds()
    attns = cfg.layer_attn_kinds()
    ffns = cfg.layer_ffn_kinds()
    specs = list(zip(mixers, attns, ffns))
    L = cfg.num_layers
    p = 1
    for per in (cfg.hybrid_attn_period, cfg.local_global_period,
                cfg.moe_period):
        if per:
            p = _lcm(p, per)
    p = min(p, L)
    segments: List[Segment] = []
    full = L // p
    if full:
        segments.append((tuple(specs[:p]), full))
    rem = specs[full * p:]
    if rem:
        if all(s == rem[0] for s in rem):
            segments.append(((rem[0],), len(rem)))
        else:
            segments.append((tuple(rem), 1))
    return segments


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _slot_init(key, cfg: ModelConfig, spec: LayerSpec) -> Dict:
    mixer, _, ffn_kind = spec
    ks = jax.random.split(key, 2)
    p = {
        "norm1": rmsnorm_init(cfg.d_model, dtype=as_dtype(cfg.param_dtype)),
        "norm2": rmsnorm_init(cfg.d_model, dtype=as_dtype(cfg.param_dtype)),
    }
    if mixer == MIXER_ATTN:
        p["mixer"] = attn_mod.attn_init(ks[0], cfg)
    else:
        p["mixer"] = ssm_mod.ssm_init(ks[0], cfg)
    if ffn_kind == FFN_MOE:
        p["ffn"] = moe_mod.moe_init(ks[1], cfg)
    else:
        p["ffn"] = ffn_mod.ffn_init(ks[1], cfg)
    return p


def init_params(key, cfg: ModelConfig) -> Dict:
    dt = as_dtype(cfg.param_dtype)
    plan = segment_plan(cfg)
    keys = jax.random.split(key, 2 + len(plan))
    params: Dict[str, Any] = {
        "embed": embedding_init(keys[0], cfg.vocab_size, cfg.d_model,
                                dtype=dt),
        "final_norm": rmsnorm_init(cfg.d_model, dtype=dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embedding_init(keys[1], cfg.vocab_size,
                                           cfg.d_model, dtype=dt)
    segs = []
    for si, (pattern, repeat) in enumerate(plan):
        seg = {}
        for slot, spec in enumerate(pattern):
            skeys = jax.random.split(
                fold_rng(keys[2 + si], slot), repeat)
            seg[f"slot{slot}"] = jax.vmap(
                lambda k: _slot_init(k, cfg, spec))(skeys)
        segs.append(seg)
    params["segments"] = tuple(segs)
    return params


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------


def _slot_window(cfg: ModelConfig, spec: LayerSpec, seq_len: int) -> int:
    if spec[1] == ATTN_LOCAL and cfg.sliding_window:
        return cfg.sliding_window
    return max(seq_len, 1) + 1          # effectively unbounded causal


def _apply_slot_full(sp: Dict, spec: LayerSpec, cfg: ModelConfig,
                     x: jnp.ndarray, positions: jnp.ndarray,
                     want_cache: bool, cache_len: int,
                     uniform_cache: bool = False):
    mixer, _, ffn_kind = spec
    S = x.shape[1]
    h = rmsnorm_apply(sp["norm1"], x, eps=cfg.norm_eps)
    cache = None
    if mixer == MIXER_ATTN:
        window = _slot_window(cfg, spec, S)
        y, (k, v) = attn_mod.attn_apply_full(sp["mixer"], cfg, h, positions,
                                             window)
        if want_cache:
            # uniform_cache: every attention layer gets the FULL
            # cache_len ring (the paged KV pool needs one token-page
            # geometry across layers, serve/memory.py). The local-window
            # cap is a pure memory optimization — the decode window mask
            # governs which entries attend, so outputs are identical.
            cap = min(window, cache_len) if (
                spec[1] == ATTN_LOCAL and not uniform_cache) \
                else cache_len
            cache = attn_mod.build_cache_from_prefill(
                k, v, cap, quant=cfg.kv_quant,
                positions=positions if positions.ndim == 2 else None)
    else:
        y, ssm_cache = ssm_mod.ssm_apply_full(sp["mixer"], cfg, h)
        if want_cache:
            cache = ssm_cache
    x = x + y
    h2 = rmsnorm_apply(sp["norm2"], x, eps=cfg.norm_eps)
    if ffn_kind == FFN_MOE:
        y2, aux = _moe_dispatch(sp["ffn"], cfg, h2)
    else:
        y2 = ffn_mod.ffn_apply(sp["ffn"], cfg, h2)
        aux = jnp.zeros((), jnp.float32)
    return x + y2, aux, cache


def _apply_slot_decode(sp: Dict, spec: LayerSpec, cfg: ModelConfig,
                       x: jnp.ndarray, pos: jnp.ndarray, cache):
    mixer, _, ffn_kind = spec
    h = rmsnorm_apply(sp["norm1"], x, eps=cfg.norm_eps)
    if mixer == MIXER_ATTN:
        window = _slot_window(cfg, spec, int(1e9) - 2)
        y, cache = attn_mod.attn_apply_decode(sp["mixer"], cfg, h, pos,
                                              cache, window)
    else:
        y, cache = ssm_mod.ssm_apply_decode(sp["mixer"], cfg, h, cache)
    x = x + y
    h2 = rmsnorm_apply(sp["norm2"], x, eps=cfg.norm_eps)
    if ffn_kind == FFN_MOE:
        y2, _ = _moe_dispatch(sp["ffn"], cfg, h2)
    else:
        y2 = ffn_mod.ffn_apply(sp["ffn"], cfg, h2)
    return x + y2, cache


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _scan_layers(body, carry, seg_params, xs=None):
    """``lax.scan`` of ``body(carry, (slot_params, xs_i))`` over one
    segment's stacked layers — the one scan every runner uses.

    An unsharded fused FFN container (``sasp_fused``, ``shards == 1``)
    is not scanned: a scanned operand reaches the body as a per-layer
    slice, which XLA copies before the kernel reads it (three weight
    copies per layer per program). The body closes over the whole stack
    instead, and each layer's FFN gets the stack with its layer index
    (``sasp_fused_layer``), which the kernel's index maps read in place.
    Without such a container this is the plain scan."""
    scanned, held = dict(seg_params), {}
    for slot, sp in seg_params.items():
        ffn = sp.get("ffn")
        fused = ffn.get("sasp_fused") if isinstance(ffn, dict) else None
        if isinstance(fused, PackedFFN) and fused.shards == 1:
            held[slot] = fused
            rest = {k: v for k, v in ffn.items() if k != "sasp_fused"}
            scanned[slot] = {**sp, "ffn": rest}
    if len(held) == 0:
        return jax.lax.scan(body, carry, (seg_params, xs))
    repeat = next(iter(held.values())).w1v.shape[0]

    def layered(c, inp):
        slot_params, layer, xs_i = inp
        slot_params = dict(slot_params)
        for slot, fused in held.items():
            ffn = {**slot_params[slot]["ffn"], "sasp_fused": fused,
                   "sasp_fused_layer": layer}
            slot_params[slot] = {**slot_params[slot], "ffn": ffn}
        return body(c, (slot_params, xs_i))

    return jax.lax.scan(layered, carry,
                        (scanned, jnp.arange(repeat, dtype=jnp.int32), xs))


def _run_segments_full(params, cfg: ModelConfig, x, positions,
                       want_cache: bool, cache_len: int,
                       uniform_cache: bool = False):
    plan = segment_plan(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    all_caches = []
    for seg_params, (pattern, repeat) in zip(params["segments"], plan):

        def body(carry, inp):
            from repro.distribution import context as dctx
            slot_params, _ = inp
            xc, aux = carry
            xc = dctx.shard_batch(xc)
            caches = {}
            for slot, spec in enumerate(pattern):
                xc, a, c = _apply_slot_full(
                    slot_params[f"slot{slot}"], spec, cfg, xc, positions,
                    want_cache, cache_len, uniform_cache)
                aux = aux + a
                if want_cache:
                    caches[f"slot{slot}"] = c
            return (xc, aux), caches

        body = _maybe_remat(body, cfg)
        (x, aux_total), seg_caches = _scan_layers(
            body, (x, aux_total), seg_params)
        all_caches.append(seg_caches)
    return x, aux_total, tuple(all_caches) if want_cache else None


def _apply_slot_prefill_past(sp: Dict, spec: LayerSpec, cfg: ModelConfig,
                             x: jnp.ndarray, positions: jnp.ndarray,
                             cache: KVCache):
    """One layer of the suffix prefill (prefix sharing, DESIGN.md §16):
    like :func:`_apply_slot_full` but attention reads the resident
    prefix through ``cache`` and the returned cache holds ONLY the
    suffix tokens. Attention-only stacks (the paged pool rejects
    SSM/hybrid)."""
    mixer, _, ffn_kind = spec
    assert mixer == MIXER_ATTN, "suffix prefill is attention-only"
    C = cache.k.shape[1]
    S = x.shape[1]
    h = rmsnorm_apply(sp["norm1"], x, eps=cfg.norm_eps)
    # global layers: window = C, the ring capacity. Sequential decode
    # can never attend an entry more than C - 1 positions back (the
    # ring holds exactly the last C positions and overwrites before
    # attending), so delta >= C pairs only arise here from OLD-LAP
    # entries a post-wrap past gather still carries — entries the
    # sequential path has already overwritten. Capping at C masks them,
    # which keeps this pass step-equivalent to sequential decode (the
    # speculative verify relies on this, DESIGN.md §17). Pre-wrap
    # callers (suffix prefill of a fresh prompt: all deltas <=
    # prompt_len - 1 <= C - 1) see every valid pair unmasked, exactly
    # as the reference full prefill does. Local layers share
    # cfg.sliding_window exactly.
    window = cfg.sliding_window if (
        spec[1] == ATTN_LOCAL and cfg.sliding_window) else C
    y, new_cache = attn_mod.attn_apply_prefill_past(
        sp["mixer"], cfg, h, positions, cache, window)
    x = x + y
    h2 = rmsnorm_apply(sp["norm2"], x, eps=cfg.norm_eps)
    if ffn_kind == FFN_MOE:
        y2, _ = _moe_dispatch(sp["ffn"], cfg, h2)
    else:
        y2 = ffn_mod.ffn_apply(sp["ffn"], cfg, h2)
    return x + y2, new_cache


def _run_segments_prefill_past(params, cfg: ModelConfig, x, positions,
                               past):
    plan = segment_plan(cfg)
    new_caches = []
    for seg_params, seg_past, (pattern, repeat) in zip(
            params["segments"], past, plan):

        def body(xc, inp):
            from repro.distribution import context as dctx
            slot_params, slot_caches = inp
            xc = dctx.shard_batch(xc)
            out_caches = {}
            for slot, spec in enumerate(pattern):
                xc, c = _apply_slot_prefill_past(
                    slot_params[f"slot{slot}"], spec, cfg, xc,
                    positions, slot_caches[f"slot{slot}"])
                out_caches[f"slot{slot}"] = c
            return xc, out_caches

        body = _maybe_remat(body, cfg)
        x, seg_new = _scan_layers(body, x, seg_params, seg_past)
        new_caches.append(seg_new)
    return x, tuple(new_caches)


def prefill_with_past(params, cfg: ModelConfig, tokens, positions, past,
                      all_logits: bool = False):
    """Suffix-only prefill for prefix sharing (DESIGN.md §16).

    tokens: (B, S) the SUFFIX of each prompt, left-padded; positions:
    (B, S) absolute positions (pads < 0); past: ring caches (the
    gather of each request's matched prefix pages — all other ring
    slots hold pos = -1 and mask out). Returns (last-token logits
    (B, 1, V), suffix-only caches) — the caches scatter to the fresh
    suffix pages and must never touch the shared prefix pages.

    ``all_logits=True`` returns logits for EVERY suffix position
    ((B, S, V)) — the speculative verify pass (DESIGN.md §17) needs
    the target's prediction after each drafted token in one call."""
    x = _embed_in(params, cfg, tokens, None)
    positions = jnp.asarray(positions, jnp.int32)
    x, caches = _run_segments_prefill_past(params, cfg, x, positions,
                                           past)
    logits = logits_fn(params, cfg, x if all_logits else x[:, -1:])
    if cfg.logit_softcap:
        logits = softcap(logits, cfg.logit_softcap)
    return logits, caches


def _run_segments_decode(params, cfg: ModelConfig, x, pos, caches):
    plan = segment_plan(cfg)
    new_caches = []
    for seg_params, seg_caches, (pattern, repeat) in zip(
            params["segments"], caches, plan):

        def body(xc, inp):
            slot_params, slot_caches = inp
            out_caches = {}
            for slot, spec in enumerate(pattern):
                xc, c = _apply_slot_decode(
                    slot_params[f"slot{slot}"], spec, cfg, xc, pos,
                    slot_caches[f"slot{slot}"])
                out_caches[f"slot{slot}"] = c
            return xc, out_caches

        x, seg_new = _scan_layers(body, x, seg_params, seg_caches)
        new_caches.append(seg_new)
    return x, tuple(new_caches)


def _embed_in(params, cfg: ModelConfig, tokens, embeds):
    cdt = as_dtype(cfg.compute_dtype)
    if embeds is not None:
        return embeds.astype(cdt)
    return embedding_apply(params["embed"], tokens, dtype=cdt)


def _head_table(params, cfg: ModelConfig):
    return (params["embed"]["emb"] if cfg.tie_embeddings
            else params["lm_head"]["emb"])


def logits_fn(params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = rmsnorm_apply(params["final_norm"], x, eps=cfg.norm_eps)
    emb = _head_table(params, cfg).astype(x.dtype)
    return jnp.einsum("bsd,vd->bsv", x, emb,
                      preferred_element_type=jnp.float32)


def forward(params, cfg: ModelConfig, tokens=None, embeds=None
            ) -> jnp.ndarray:
    """Full-sequence forward -> logits (B, S, V). Smoke/QoS path."""
    x = _embed_in(params, cfg, tokens, embeds)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x, _, _ = _run_segments_full(params, cfg, x, positions, False, 0)
    logits = logits_fn(params, cfg, x)
    if cfg.logit_softcap:
        logits = softcap(logits, cfg.logit_softcap)
    return logits


# ---------------------------------------------------------------------------
# Loss (chunked cross-entropy: never materializes (B, S, V) at once)
# ---------------------------------------------------------------------------


def _xent_chunk(x_chunk, targets, emb, cfg: ModelConfig):
    logits = jnp.einsum("btd,vd->btv", x_chunk, emb,
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = softcap(logits, cfg.logit_softcap)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (lse - tgt).sum()


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            xent_chunk: int = 512):
    """batch: tokens (B, S) [+ optional embeds (B, S, d)]. Next-token CE +
    MoE aux. Returns (loss, metrics)."""
    tokens = batch["tokens"]
    x = _embed_in(params, cfg, tokens, batch.get("embeds"))
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    x, aux, _ = _run_segments_full(params, cfg, x, positions, False, 0)
    x = rmsnorm_apply(params["final_norm"], x, eps=cfg.norm_eps)
    emb = _head_table(params, cfg).astype(x.dtype)

    xs = x[:, :-1]
    tgt = tokens[:, 1:]
    n = xs.shape[1]
    c = min(xent_chunk, n)
    while n % c:
        c -= 1
    xs = jnp.moveaxis(xs.reshape(B, n // c, c, -1), 1, 0)
    tg = jnp.moveaxis(tgt.reshape(B, n // c, c), 1, 0)

    def body(tot, inp):
        xc, tc = inp
        return tot + _xent_chunk(xc, tc, emb, cfg), None

    # checkpoint: backward recomputes each chunk's logits instead of
    # stacking (B, chunk, V) f32 residuals across chunks (12+ GiB/device
    # at 50k vocab — see EXPERIMENTS.md §Perf iteration log)
    total, _ = jax.lax.scan(jax.checkpoint(body),
                            jnp.zeros((), jnp.float32), (xs, tg))
    ce = total / (B * n)
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Serving entry points
# ---------------------------------------------------------------------------


def prefill(params, cfg: ModelConfig, tokens=None, embeds=None,
            cache_len: Optional[int] = None, positions=None,
            uniform_cache: bool = False):
    """Process the prompt; returns (last-token logits (B, 1, V), caches).

    positions: optional per-batch (B, S) absolute positions for the
    LEFT-padded multi-slot batched prefill (serve/engine.py): row i of a
    prompt of length L_i is [-(S - L_i), …, -1 padded, 0 … L_i - 1].
    Pad columns are masked out of attention and written to the KV cache
    with pos = -1; the last column is every sequence's final real token,
    so the returned logits stay (B, 1, V). Default: shared arange(S).

    uniform_cache: build every attention layer's ring at the FULL
    cache_len (no local-window cap) — required by the paged KV pool
    (serve/memory.py), bit-identical outputs (the window mask governs).
    """
    x = _embed_in(params, cfg, tokens, embeds)
    B, S = x.shape[0], x.shape[1]
    cache_len = cache_len or S
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    else:
        positions = jnp.asarray(positions, jnp.int32)
    x, _, caches = _run_segments_full(params, cfg, x, positions, True,
                                      cache_len, uniform_cache)
    logits = logits_fn(params, cfg, x[:, -1:])
    if cfg.logit_softcap:
        logits = softcap(logits, cfg.logit_softcap)
    return logits, caches


def decode_step(params, cfg: ModelConfig, tokens, pos, caches,
                embeds=None):
    """One decode step. tokens: (B, 1) int32 (or embeds (B, 1, d));
    pos: (B,) absolute positions. Returns (logits (B, 1, V), caches)."""
    x = _embed_in(params, cfg, tokens, embeds)
    x, caches = _run_segments_decode(params, cfg, x, pos, caches)
    logits = logits_fn(params, cfg, x)
    if cfg.logit_softcap:
        logits = softcap(logits, cfg.logit_softcap)
    return logits, caches


def init_caches(params, cfg: ModelConfig, batch: int, cache_len: int,
                uniform_cap: bool = False):
    """Zero-initialized cache pytree matching the segment plan.

    uniform_cap: every attention layer gets capacity = cache_len (the
    paged KV pool's page geometry must be shared across layers; the
    window mask keeps local-attention semantics identical)."""
    cdt = as_dtype(cfg.compute_dtype)
    plan = segment_plan(cfg)
    caches = []
    for pattern, repeat in plan:
        seg = {}
        for slot, spec in enumerate(pattern):
            if spec[0] == MIXER_ATTN:
                cap = cache_len if uniform_cap else min(
                    _slot_window(cfg, spec, cache_len), cache_len)
                c = attn_mod.init_kv_cache(batch, cap, cfg.num_kv_heads,
                                           cfg.attn_head_dim, cdt,
                                           quant=cfg.kv_quant)
            else:
                c = ssm_mod.init_ssm_cache(cfg, batch, cdt)
            seg[f"slot{slot}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (repeat,) + a.shape).copy(), c)
        caches.append(seg)
    return tuple(caches)
