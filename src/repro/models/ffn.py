"""Feed-forward layers — the paper's primary SASP surface.

Execution paths (DESIGN.md §4):
  * dense              — no SASP.
  * masked-dense       — params carry per-matrix block masks ("sasp_masks");
                         tiles are zeroed but the matmul stays dense. Used
                         in training and as the numerical reference.
  * bsr                — params carry BlockSparseWeight containers
                         ("sasp_bsr"); pruned tiles are *skipped*
                         (gathered-matmul), FLOPs/bytes ∝ (1 - sparsity).
  * kernel             — Pallas tile-skip kernel (TPU-native), same
                         container.
  * packed             — deployment containers from ``core.deploy``
                         (DESIGN.md §9): per-matrix "sasp_packed"
                         PackedSASPWeight (compact sorted block list,
                         bias+act fused into the kernel flush) or the
                         whole-FFN "sasp_fused" PackedFFN (one kernel
                         launch, no HBM (M, d_ff) intermediate). Zero
                         per-call repacking — the serving fast path.
                         Inside the layer scan an unsharded
                         "sasp_fused" stays stacked and comes with
                         "sasp_fused_layer", the layer the kernel reads
                         in place (models/lm.py ``_scan_layers``).
  * quant              — weight-only INT8 (+ per-block scales); composes
                         with any of the above.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.pruning import apply_block_mask
from repro.core.quantization import QuantizedWeight
from repro.core.sparse import BlockSparseWeight, bsr_matmul
from repro.models.modules import act_fn, as_dtype, dense_init


def ffn_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict:
    dt = as_dtype(cfg.param_dtype)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    p = {
        "w1": dense_init(ks[0], d, f, dtype=dt),
        "w2": dense_init(ks[1], f, d, dtype=dt, scale=out_scale),
    }
    if cfg.ffn_gated:
        p["w3"] = dense_init(ks[2], d, f, dtype=dt)
    return p


def _materialize(p: Dict, name: str, dtype) -> jnp.ndarray:
    """Resolve one weight matrix through the masked/quantized views."""
    entry = p[name]
    if isinstance(entry, dict) and "qw" in entry:       # int8 weight-only
        qw: QuantizedWeight = entry["qw"]
        bk, bn = qw.block
        K, N = qw.q.shape[-2:]
        KB, NB = K // bk, N // bn
        qb = qw.q.reshape(*qw.q.shape[:-2], KB, bk, NB, bn).astype(
            jnp.float32)
        w = (qb * qw.scale[..., :, None, :, None]).reshape(qw.q.shape)
    else:
        w = entry["w"]
    masks = p.get("sasp_masks")
    if masks is not None and name in masks:
        w = apply_block_mask(w, masks[name])
    return w.astype(dtype)


def _bsr_mm_sharded(x2d, w, cfg, kernel: bool):
    """Block-sparse matmul under an active mesh: shard_map over 'model'
    (each shard owns its NB-slice of blocks and computes its output
    columns locally — no gather collectives; the jnp gather path under
    plain GSPMD all-gathers x per k_max step, see EXPERIMENTS.md §Perf
    A iter 5)."""
    from jax.sharding import PartitionSpec as P

    from repro.distribution import context as dctx

    mesh = dctx.active_mesh()
    NB = w.idx.shape[-1]
    tp = dctx.axis_size("model")

    def compute(xx, ww):
        if kernel:
            from repro.kernels.sasp_gemm.ops import sasp_matmul
            return sasp_matmul(xx, ww)
        return bsr_matmul(xx, ww)

    if mesh is None or tp <= 1 or NB % tp:
        return compute(x2d, w)
    bax = dctx.batch_axes(x2d.shape[0])
    wspec = BlockSparseWeight(
        vals=P(None, "model", None, None), idx=P(None, "model"),
        shape=w.shape, block=w.block,
        scale=None if w.scale is None else P(None, "model"))

    def body(xx, ww):
        # local slice: same (K, sliced N) semantics
        w_loc = BlockSparseWeight(ww.vals, ww.idx,
                                  (w.shape[0], w.shape[1] // tp),
                                  w.block, ww.scale)
        return compute(xx, w_loc)

    return dctx.shard_map(
        body, mesh=mesh,
        in_specs=(P(bax, None), wspec),
        out_specs=P(bax, "model"))(x2d, w)


def _mm(p: Dict, name: str, x2d: jnp.ndarray, cfg: ModelConfig
        ) -> jnp.ndarray:
    """(M, K) @ weight[name] with whatever SASP view is attached."""
    bsr = p.get("sasp_bsr")
    if bsr is not None and name in bsr:
        w: BlockSparseWeight = bsr[name]
        return _bsr_mm_sharded(x2d, w, cfg, cfg.sasp.path == "kernel")
    w = _materialize(p, name, x2d.dtype)
    return x2d @ w


def _rs_ag_int8(y_part: jnp.ndarray, out_dtype) -> jnp.ndarray:
    """TP partial-sum reduction, inside a shard_map body over 'model':
    reduce-scatter (fp32) + INT8 all-gather of the reduced shards
    (per-row scales) — 3 B/elem on the wire vs 4 B/elem for a ring
    all-reduce (0.75×), the paper's quantization theme applied to the TP
    activation traffic (§Roofline). int8 happens AFTER the reduction, so
    no quantization error accumulates."""
    y_rs = jax.lax.psum_scatter(y_part, "model", scatter_dimension=1,
                                tiled=True)      # (M, d/tp) reduced
    amax = jnp.max(jnp.abs(y_rs.astype(jnp.float32)), axis=1,
                   keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(y_rs.astype(jnp.float32) / scale), -127,
                 127).astype(jnp.int8)
    qg = jax.lax.all_gather(q, "model", axis=1, tiled=True)
    sg = jax.lax.all_gather(scale, "model", axis=1, tiled=True)
    seg = jnp.repeat(sg, y_rs.shape[1], axis=1)
    return (qg.astype(jnp.float32) * seg).astype(out_dtype)


def _tp_reduce(y_part: jnp.ndarray, cfg: Optional[ModelConfig],
               out_dtype) -> jnp.ndarray:
    """Cross-shard reduction of a partial (M, d): the rs+int8-ag wire
    format when the config opts in and d splits, else an exact psum."""
    if cfg is not None and cfg.tp_comm == "rs_ag_int8":
        from repro.distribution import context as dctx
        if y_part.shape[1] % dctx.axis_size("model") == 0:
            return _rs_ag_int8(y_part, out_dtype)
    return jax.lax.psum(y_part, "model").astype(out_dtype)


def _ffn_tp_rs_ag_int8(p: Dict, cfg: ModelConfig, x2: jnp.ndarray):
    """Dense FFN with the TP output reduction done as reduce-scatter
    (bf16) + INT8 all-gather of the reduced shards — see
    :func:`_rs_ag_int8`."""
    from jax.sharding import PartitionSpec as P

    from repro.distribution import context as dctx

    mesh = dctx.active_mesh()
    tp = dctx.axis_size("model")
    M, d = x2.shape
    f = p["w1"]["w"].shape[-1]
    bax = dctx.batch_axes(M)

    def body(xx, w1, w2, w3):
        h = xx @ w1
        if cfg.ffn_gated:
            h = act_fn(cfg.act)(h) * (xx @ w3)
        else:
            h = act_fn(cfg.act)(h)
        y_part = h @ w2                          # (M, d) partial over tp
        return _rs_ag_int8(y_part, xx.dtype)

    w3 = p["w3"]["w"] if cfg.ffn_gated else p["w1"]["w"]
    return dctx.shard_map(
        body, mesh=mesh,
        in_specs=(P(bax, None), P(None, "model"), P("model", None),
                  P(None, "model")),
        out_specs=P(bax, None),
    )(x2, p["w1"]["w"], p["w2"]["w"], w3)


def _can_rs_ag(p: Dict, cfg: ModelConfig, x2) -> bool:
    from repro.distribution import context as dctx

    if cfg.tp_comm != "rs_ag_int8" or cfg.moe is not None:
        return False
    mesh = dctx.active_mesh()
    if mesh is None:
        return False
    tp = dctx.axis_size("model")
    d = x2.shape[-1]
    f = p["w1"]["w"].shape[-1]
    return (tp > 1 and d % tp == 0 and f % tp == 0
            and "sasp_bsr" not in p and "sasp_masks" not in p
            and "sasp_packed" not in p and "sasp_fused" not in p
            and isinstance(p["w1"], dict) and "w" in p["w1"])


def _sq(arr, from_end: int):
    """Drop the size-1 shard axis at position ndim-from_end (the local
    view inside a shard_map body)."""
    return None if arr is None else jnp.squeeze(
        arr, axis=arr.ndim - from_end)


def _take(arr, s: int, from_end: int):
    return None if arr is None else jnp.take(
        arr, s, axis=arr.ndim - from_end)


def _pw_local(w, shape, *, with_bias: bool):
    """Shard-local view of a TP-sharded PackedSASPWeight whose arrays
    arrived in a shard_map body with the shard axis mapped (size 1)."""
    from repro.core.sparse import PackedSASPWeight
    return PackedSASPWeight(
        _sq(w.vals, 4), _sq(w.kn, 3), shape, w.block,
        scale=_sq(w.scale, 2),
        bias=_sq(w.bias, 2) if with_bias else None,
        act=w.act if with_bias else None)


def packed_mm_sharded(x2: jnp.ndarray, pw, cfg: Optional[ModelConfig]
                      ) -> jnp.ndarray:
    """TP-sharded packed tile-skip matmul (DESIGN.md §10): one shard_map
    over 'model', each rank running the kernel over its shard-LOCAL
    visit list — pruning savings stay local to the shard instead of
    being averaged away. col-sharded weights emit their output columns
    in place (out sharded over 'model'); row-sharded weights emit
    partials and reduce (psum, or rs+int8-ag when cfg opts in). Falls
    back to a sequential per-shard loop when no matching mesh is active
    (single-device parity / tests)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.deploy import packed_matmul
    from repro.core.sparse import PackedSASPWeight
    from repro.distribution import context as dctx

    K, N = pw.shape
    tp, kind = pw.shards, pw.shard_kind
    mesh = dctx.active_mesh()
    if mesh is None or dctx.axis_size("model") != tp:
        return _packed_mm_shard_loop(x2, pw)
    bax = dctx.batch_axes(x2.shape[0])

    from repro.distribution.sharding import axis_at

    def ax(arr, from_end):
        return None if arr is None else axis_at(arr.ndim, from_end,
                                                "model")

    wspec = PackedSASPWeight(
        ax(pw.vals, 4), ax(pw.kn, 3), pw.shape, pw.block,
        scale=ax(pw.scale, 2),
        bias=(ax(pw.bias, 2) if kind == "col"
              else None if pw.bias is None
              else P(*([None] * pw.bias.ndim))),
        act=pw.act, shards=tp, shard_kind=kind)

    if kind == "col":
        def body(xx, w):
            return packed_matmul(xx, _pw_local(w, (K, N // tp),
                                               with_bias=True))
        return dctx.shard_map(
            body, mesh=mesh, in_specs=(P(bax, None), wspec),
            out_specs=P(bax, "model"))(x2, pw)

    def body(xx, w):                    # row: partial over shards
        y = packed_matmul(xx, _pw_local(w, (K // tp, N),
                                        with_bias=False))
        y = _tp_reduce(y, cfg, xx.dtype)
        if w.bias is not None:
            y = y + w.bias.astype(y.dtype)
        return y

    return dctx.shard_map(
        body, mesh=mesh, in_specs=(P(bax, "model"), wspec),
        out_specs=P(bax, None))(x2, pw)


def _packed_mm_shard_loop(x2: jnp.ndarray, pw) -> jnp.ndarray:
    """Meshless reference for a TP-sharded container: run every shard's
    visit list sequentially and concatenate (col) / sum (row). Keeps
    sharded deployments loadable on a single device."""
    from repro.core.deploy import packed_matmul
    from repro.core.sparse import PackedSASPWeight

    K, N = pw.shape
    tp = pw.shards
    if tp <= 1:
        return packed_matmul(x2, pw)
    outs = []
    for s in range(tp):
        if pw.shard_kind == "col":
            loc = PackedSASPWeight(
                _take(pw.vals, s, 4), _take(pw.kn, s, 3), (K, N // tp),
                pw.block, scale=_take(pw.scale, s, 2),
                bias=_take(pw.bias, s, 2), act=pw.act)
            outs.append(packed_matmul(x2, loc))
        else:
            ks = K // tp
            loc = PackedSASPWeight(
                _take(pw.vals, s, 4), _take(pw.kn, s, 3), (ks, N),
                pw.block, scale=_take(pw.scale, s, 2), bias=None,
                act=None)
            outs.append(packed_matmul(x2[:, s * ks:(s + 1) * ks], loc))
    if pw.shard_kind == "col":
        return jnp.concatenate(outs, axis=-1)
    y = sum(outs[1:], outs[0])
    if pw.bias is not None:
        y = y + pw.bias.astype(y.dtype)
    return y


def _packed_ffn_fused_sharded(x2: jnp.ndarray, pf,
                              cfg: ModelConfig) -> jnp.ndarray:
    """TP-sharded fused gated-FFN (DESIGN.md §10): each rank runs the
    single-launch fused kernel over its contiguous d_ff visit shard,
    then partials reduce across 'model' (psum or rs+int8-ag). b2 is
    added once, after the reduction."""
    from jax.sharding import PartitionSpec as P

    from repro.core.sparse import PackedFFN
    from repro.distribution import context as dctx
    from repro.kernels.sasp_gemm import ops as sasp_ops

    tp = pf.shards
    mesh = dctx.active_mesh()
    d = pf.d_model

    def run_local(xx, w1v, w3v, w2v, b1, b3, scales):
        return sasp_ops.fused_ffn_matmul(
            xx, w1v, w3v, w2v, b1, b3,
            jnp.zeros((d,), jnp.float32), scales=scales, act=pf.act)

    if mesh is None or dctx.axis_size("model") != tp:
        parts = []
        for s in range(tp):
            sc = None if pf.s1 is None else (
                _take(pf.s1, s, 2), _take(pf.s3, s, 2),
                _take(pf.s2, s, 2))
            parts.append(run_local(
                x2, _take(pf.w1v, s, 4), _take(pf.w3v, s, 4),
                _take(pf.w2v, s, 4), _take(pf.b1, s, 3),
                _take(pf.b3, s, 3), sc))
        return sum(parts[1:], parts[0]) + pf.b2.astype(x2.dtype)

    bax = dctx.batch_axes(x2.shape[0])

    from repro.distribution.sharding import axis_at

    def ax(arr, from_end):
        return None if arr is None else axis_at(arr.ndim, from_end,
                                                "model")

    pfspec = PackedFFN(
        ax(pf.w1v, 4), ax(pf.w3v, 4), ax(pf.w2v, 4),
        ax(pf.b1, 3), ax(pf.b3, 3), P(*([None] * pf.b2.ndim)),
        d_model=pf.d_model, d_ff=pf.d_ff, block_f=pf.block_f,
        act=pf.act, s1=ax(pf.s1, 2), s3=ax(pf.s3, 2), s2=ax(pf.s2, 2),
        shards=tp, jv=ax(pf.jv, 2))

    def body(xx, w):
        sc = None if w.s1 is None else (
            _sq(w.s1, 2), _sq(w.s3, 2), _sq(w.s2, 2))
        y = run_local(xx, _sq(w.w1v, 4), _sq(w.w3v, 4), _sq(w.w2v, 4),
                      _sq(w.b1, 3), _sq(w.b3, 3), sc)
        y = _tp_reduce(y, cfg, xx.dtype)
        return y + w.b2.astype(y.dtype)

    return dctx.shard_map(
        body, mesh=mesh, in_specs=(P(bax, None), pfspec),
        out_specs=P(bax, None))(x2, pf)


def _ffn_apply_packed(p: Dict, cfg: ModelConfig, x2: jnp.ndarray
                      ) -> Optional[jnp.ndarray]:
    """Deployment fast path: fused whole-FFN kernel if a PackedFFN is
    attached, else per-matrix packed GEMMs (w1 carries the activation as
    its flush epilogue, so no separate elementwise pass). TP-sharded
    containers (``shards > 1``) route through the shard_map drivers.
    Returns None when no packed container is present."""
    from repro.core.deploy import packed_ffn_apply, packed_matmul

    fused = p.get("sasp_fused")
    if fused is not None:
        if fused.shards > 1:
            return _packed_ffn_fused_sharded(x2, fused, cfg)
        return packed_ffn_apply(x2, fused, layer=p.get("sasp_fused_layer"))
    packed = p.get("sasp_packed")
    if packed is not None and "w1" in packed:
        if packed["w1"].shards > 1:
            h = packed_mm_sharded(x2, packed["w1"], cfg)  # act in flush
            if cfg.ffn_gated and "w3" in packed:
                h = h * packed_mm_sharded(x2, packed["w3"], cfg)
            return packed_mm_sharded(h, packed["w2"], cfg)
        h = packed_matmul(x2, packed["w1"])         # act fused in flush
        if cfg.ffn_gated and "w3" in packed:
            h = h * packed_matmul(x2, packed["w3"])
        return packed_matmul(h, packed["w2"])       # bias fused if any
    return None


def ffn_apply(p: Dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    *lead, d = x.shape
    x2 = x.reshape(-1, d)
    if "sasp_fused" in p or "sasp_packed" in p:
        y = _ffn_apply_packed(p, cfg, x2)
        if y is not None:
            return y.reshape(*lead, d).astype(x.dtype)
    if _can_rs_ag(p, cfg, x2):
        y = _ffn_tp_rs_ag_int8(p, cfg, x2)
        return y.reshape(*lead, d).astype(x.dtype)
    act = act_fn(cfg.act)
    h = _mm(p, "w1", x2, cfg)
    if cfg.ffn_gated:
        h = act(h) * _mm(p, "w3", x2, cfg)
    else:
        h = act(h)
    y = _mm(p, "w2", h, cfg)
    if "b" in p.get("w2", {}):
        y = y + p["w2"]["b"].astype(y.dtype)
    return y.reshape(*lead, d).astype(x.dtype)
