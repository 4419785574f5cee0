"""Packed SASP deployment pipeline (DESIGN.md §9).

``deploy_packed`` is the single load-time conversion entry point for
serving: it walks a (pruned, optionally INT8) param tree and attaches
compact kernel-ready containers so that NO per-call repacking happens on
the serving path:

* per-matrix :class:`~repro.core.sparse.PackedSASPWeight` — the sorted
  (nnz, bk, bn) block list ``kernels.sasp_gemm`` consumes directly, with
  bias and activation folded into the kernel's flush epilogue. Attached
  under ``sasp_packed`` next to the weights (FFN w1/w2/w3 and, for
  ``scope="all"``, attention wq/wk/wv/wo).
* whole-FFN :class:`~repro.core.sparse.PackedFFN` — the fused gated-FFN
  schedule (single kernel launch, no HBM (M, d_ff) intermediate),
  attached under ``sasp_fused``.

Layer stacks (the ``lax.scan``-over-layers layout, leading ``repeat``
axis) are packed per layer and padded to one shared static nnz/nv
(padding = duplicated last visit with zero values; see
``kernels.sasp_gemm.ops.pad_block_list``). Per-matrix and TP-sharded
containers slice under scan like every other stacked param; an
unsharded fused container is read in place by layer index, never sliced
(``models.lm._scan_layers``, DESIGN.md §9).

Masks are recovered from the nonzero tile structure of the (already
pruned) weights, so the conversion needs nothing beyond the deployed
params themselves — pruning is static by deployment time (DESIGN.md §4).

Container format in one breath (full spec: ``core/sparse.py``
docstring): a sorted VISIT LIST per matrix — (n, k)-ordered surviving
blocks for ``PackedSASPWeight``, d_ff column-blocks with global
indices ``jv`` for ``PackedFFN`` — padded across layers/shards to one
shared static nnz by duplicating the last visit with zero values, and
TP-partitioned by SHARD KIND: ``col`` (shard-local output columns,
fused bias/act) or ``row`` (shard-local input rows, partial outputs,
bias after the cross-shard reduction, never an activation).

Mesh-shape changes do NOT rebuild from here: ``reshard_packed`` (end
of this file) slices and re-pads the existing visit lists to the new
shard count, bit-identically to a from-scratch ``deploy_packed`` —
see DESIGN.md §10 "Elastic re-deploy".
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.sparse import PackedFFN, PackedSASPWeight
from repro.kernels.sasp_gemm import ops as sasp_ops

Params = Dict[str, Any]

_ATTN_MATS = ("wq", "wk", "wv", "wo")
_FFN_MATS = ("w1", "w2", "w3")


def _fit_block(dim: int, want: int) -> int:
    """Largest block ≤ ``want`` that divides ``dim`` (mask granularity is
    free at deploy time — nonzero-tile detection is correct at any tile
    size, so we pick the best-fitting one)."""
    b = min(max(1, want), dim)
    while dim % b:
        b -= 1
    return b


def _value_dtype(entry: Params):
    """Packed values keep the dtype of the weight they are packed from,
    so a model served in bf16 is packed in bf16. An int8 ``qw`` entry
    dequantizes to float32."""
    w = entry.get("w") if isinstance(entry, dict) else None
    return np.float32 if w is None else w.dtype


def _dense_weight(entry: Params) -> Optional[np.ndarray]:
    """Materialize one matrix dict {w}|{qw} to dense fp32 (numpy)."""
    if not isinstance(entry, dict):
        return None
    if "w" in entry:
        return np.asarray(entry["w"], np.float32)
    if "qw" in entry:
        qw = entry["qw"]
        bk, bn = qw.block
        q = np.asarray(qw.q, np.float32)
        sc = np.asarray(qw.scale, np.float32)
        K, N = q.shape[-2:]
        KB, NB = K // bk, N // bn
        qb = q.reshape(*q.shape[:-2], KB, bk, NB, bn)
        qb = qb * sc[..., :, None, :, None]
        return qb.reshape(q.shape)
    return None


def pack_weight(w: np.ndarray, *, block_k: int, block_n: int,
                bias: Optional[np.ndarray] = None,
                act: Optional[str] = None,
                quantize: bool = False,
                tp: int = 1,
                shard_kind: str = "col",
                dtype=np.float32) -> PackedSASPWeight:
    """(K, N) or layer-stacked (L, K, N) dense weight (pruned tiles
    already zeroed) -> PackedSASPWeight. Stacked inputs are packed per
    layer and padded to a shared nnz (dup-last-visit zero padding).

    ``tp > 1`` partitions each layer's sorted block list into ``tp``
    shard-local lists (DESIGN.md §10): ``shard_kind="col"`` slices the
    output-column blocks (kn n-coords become shard-local; each shard's
    pruning savings stay local instead of averaging away), ``"row"``
    slices the input-row blocks (for down-projections whose INPUT is
    already column-sharded; outputs are partial and need a reduction,
    so ``act`` must be None and ``bias`` is kept whole, to be added
    after the reduction). All (layer × shard) lists share one static
    nnz via the same dup-last-visit padding as the scan layout.
    ``dtype``: dtype of the packed values (ignored when ``quantize``).
    """
    w = np.asarray(w, np.float32)
    if w.ndim == 2:
        w = w[None]
        bias = None if bias is None else np.asarray(bias)[None]
        squeeze = True
    else:
        squeeze = False
    L, K, N = w.shape
    bk = _fit_block(K, block_k)
    bn = _fit_block(N, block_n)
    KB, NB = K // bk, N // bn
    assert shard_kind in ("col", "row"), shard_kind
    if tp > 1:
        blocks = NB if shard_kind == "col" else KB
        assert blocks % tp == 0, (shard_kind, blocks, tp)
        assert shard_kind == "col" or act is None, \
            "row-sharded outputs are partial; no nonlinear epilogue"

    def _slice(wi, s):
        if tp == 1:
            return wi
        if shard_kind == "col":
            ns = N // tp
            return wi[:, s * ns:(s + 1) * ns]
        ks = K // tp
        return wi[s * ks:(s + 1) * ks, :]

    packs = []                            # [L][tp] of (vals, kn, scale)
    for i in range(L):
        layer = []
        for s in range(tp):
            ws = _slice(w[i], s)
            kb, nb = ws.shape[0] // bk, ws.shape[1] // bn
            m = np.any(ws.reshape(kb, bk, nb, bn), axis=(1, 3))
            layer.append(sasp_ops.build_kernel_weight(
                ws, m, bk, bn, quantize=quantize))
        packs.append(layer)
    nnz = max(np.asarray(p[0]).shape[0] for lp in packs for p in lp)

    def _pad_stack(layer):
        vs, ks, ss = [], [], []
        for v, kn, sc in layer:
            v, kn, sc = sasp_ops.pad_block_list(
                np.asarray(v), np.asarray(kn),
                None if sc is None else np.asarray(sc), nnz)
            vs.append(v)
            ks.append(kn)
            ss.append(sc)
        if tp == 1:
            return vs[0], ks[0], ss[0]
        return (np.stack(vs), np.stack(ks),
                None if ss[0] is None else np.stack(ss))

    per_layer = [_pad_stack(lp) for lp in packs]
    vals = np.stack([p[0] for p in per_layer])
    vals = jnp.asarray(vals if quantize else vals.astype(dtype))
    kn = jnp.asarray(np.stack([p[1] for p in per_layer]))
    scale = None if per_layer[0][2] is None else jnp.asarray(
        np.stack([p[2] for p in per_layer]).astype(np.float32))
    b = None
    if bias is not None:
        b = np.asarray(bias, np.float32)
        if tp > 1 and shard_kind == "col":     # fused per column shard
            b = b.reshape(L, tp, N // tp)
        b = jnp.asarray(b)
    if squeeze:
        vals, kn = vals[0], kn[0]
        scale = None if scale is None else scale[0]
        b = None if b is None else b[0]
    return PackedSASPWeight(vals, kn, (K, N), (bk, bn), scale=scale,
                            bias=b, act=act, shards=tp,
                            shard_kind=shard_kind if tp > 1 else None)


def pack_ffn(w1: np.ndarray, w3: np.ndarray, w2: np.ndarray, *,
             block_f: int, act: str,
             b1: Optional[np.ndarray] = None,
             b3: Optional[np.ndarray] = None,
             b2: Optional[np.ndarray] = None,
             quantize: bool = False,
             tp: int = 1,
             dtype=np.float32) -> PackedFFN:
    """Gated-FFN triple (each (d, F)/(F, d) or layer-stacked with a
    leading L axis) -> PackedFFN for the fused kernel.

    ``tp > 1`` partitions the d_ff visit schedule contiguously by d_ff
    column-block shard (DESIGN.md §10): shard s packs d_ff columns
    [s·F/tp, (s+1)·F/tp) of w1/w3 and the matching w2 rows, so every
    shard runs the fused kernel over ITS surviving blocks only and
    yields a partial (M, d). b2 is NOT folded into the per-shard flush
    (it would be added tp times under the cross-shard reduction); it
    stays whole on the container for the driver to add once.
    ``dtype``: dtype of w1v/w3v/w2v (ignored when ``quantize``).
    """
    w1 = np.asarray(w1, np.float32)
    squeeze = w1.ndim == 2

    def _lift(a):
        return None if a is None else np.asarray(a, np.float32)[
            None] if squeeze else np.asarray(a, np.float32)

    if squeeze:
        w1 = w1[None]
    w3 = _lift(w3)
    w2 = _lift(w2)
    b1, b3, b2 = _lift(b1), _lift(b3), _lift(b2)
    L, d, F = w1.shape
    bf = _fit_block(F, block_f)
    if tp > 1:
        assert (F // bf) % tp == 0, (F, bf, tp)

    def _build(i, s):
        if tp == 1:
            sl = slice(None)
        else:
            fs = F // tp
            sl = slice(s * fs, (s + 1) * fs)
        pk = sasp_ops.build_fused_ffn(
            w1[i][:, sl], w3[i][:, sl], w2[i][sl, :], block_f=bf,
            b1=None if b1 is None else b1[i][sl],
            b3=None if b3 is None else b3[i][sl],
            b2=None if (b2 is None or tp > 1) else b2[i],
            quantize=quantize, return_visits=True)
        jv = np.asarray(pk[-1])
        if tp > 1:          # shard-local keep indices -> global d_ff blocks
            jv = np.where(jv >= 0, jv + s * ((F // bf) // tp), -1)
        return pk[:-1] + (jv.astype(np.int32),)

    packs = [_build(i, s) for i in range(L) for s in range(tp)]
    nv = max(np.asarray(p[0]).shape[0] for p in packs)

    def _pad_visits(p):
        """Append zero visits up to the shared nv (zero w2v => padded
        visits contribute exactly nothing) — pack once, pad in place."""
        w1v, w3v, w2v, b1v, b3v, b2v, sc, jv = [
            np.asarray(a) if a is not None and not isinstance(a, tuple)
            else a for a in p]
        pad = nv - w1v.shape[0]
        if pad:
            def z(a):
                return np.concatenate(
                    [a, np.zeros((pad,) + a.shape[1:], a.dtype)])
            w1v, w3v, w2v = z(w1v), z(w3v), z(w2v)
            b1v, b3v = z(b1v), z(b3v)
            jv = np.concatenate([jv, np.full((pad,), -1, np.int32)])
            if sc is not None:
                sc = tuple(z(np.asarray(s)) for s in sc)
        return w1v, w3v, w2v, b1v, b3v, b2v, sc, jv

    repacked = [_pad_visits(p) for p in packs]

    def _stack(idx, dt=None):
        a = np.stack([np.asarray(p[idx]) for p in repacked])
        if tp > 1:                         # (L·tp, …) -> (L, tp, …)
            a = a.reshape((L, tp) + a.shape[1:])
        return jnp.asarray(a if dt is None else a.astype(dt))

    vdt = None if quantize else dtype
    w1v, w3v, w2v = _stack(0, vdt), _stack(1, vdt), _stack(2, vdt)
    b1v, b3v = _stack(3), _stack(4)
    jv = _stack(7)
    if tp > 1:
        # per-shard packs carried zero b2 placeholders; keep the real
        # bias whole — drivers add it once after the shard reduction
        b2v = jnp.asarray(b2 if b2 is not None
                          else np.zeros((L, d), np.float32))
    else:
        b2v = _stack(5)
    if repacked[0][6] is None:
        s1 = s3 = s2 = None
    else:
        def _stack_s(idx):
            a = np.stack([np.asarray(p[6][idx]) for p in repacked])
            if tp > 1:
                a = a.reshape((L, tp) + a.shape[1:])
            return jnp.asarray(a)
        s1, s3, s2 = _stack_s(0), _stack_s(1), _stack_s(2)
    if squeeze:
        w1v, w3v, w2v = w1v[0], w3v[0], w2v[0]
        b1v, b3v, b2v = b1v[0], b3v[0], b2v[0]
        jv = jv[0]
        s1 = None if s1 is None else s1[0]
        s3 = None if s3 is None else s3[0]
        s2 = None if s2 is None else s2[0]
    return PackedFFN(w1v, w3v, w2v, b1v, b3v, b2v, d_model=d, d_ff=F,
                     block_f=bf, act=act, s1=s1, s3=s3, s2=s2,
                     shards=tp, jv=jv)


# ---------------------------------------------------------------------------
# Apply (serving hot path)
# ---------------------------------------------------------------------------


def packed_matmul(x: jnp.ndarray, pw: PackedSASPWeight, *,
                  block_m: int = 128) -> jnp.ndarray:
    """(…, K) @ packed weight -> (…, N) through the tile-skip kernel,
    bias + activation fused into the flush. Zero per-call repacking."""
    scales = None if pw.scale is None else pw.scale
    return sasp_ops.sasp_matmul_packed(
        x, pw.vals, pw.kn, scales, n=pw.shape[1], block_m=block_m,
        bias=pw.bias, act=pw.act)


def packed_ffn_apply(x: jnp.ndarray, pf: PackedFFN, *, layer=None,
                     block_m: int = 128) -> jnp.ndarray:
    """Whole gated FFN in one fused kernel launch. ``layer``: for a
    layer-stacked container, the layer to run, read in place."""
    scales = None if pf.s1 is None else (pf.s1, pf.s3, pf.s2)
    return sasp_ops.fused_ffn_matmul(
        x, pf.w1v, pf.w3v, pf.w2v, pf.b1, pf.b3, pf.b2, scales=scales,
        layer=layer, act=pf.act, block_m=block_m)


# ---------------------------------------------------------------------------
# deploy_packed — the load-time conversion entry point
# ---------------------------------------------------------------------------


# TP-eligibility gates, SHARED by deploy_packed and reshard_packed so
# the two walks cannot silently diverge (the parity tests in
# tests/test_deploy_packed.py assert bit-identity between them; a rule
# added to only one side would break that contract for configs the
# tests don't cover).


def _shard_blocks(kind: str, K: int, N: int, bk: int, bn: int) -> int:
    """Block count along the dimension a shard kind partitions."""
    return (N // bn) if kind == "col" else (K // bk)


def _fused_tp(d_ff: int, block_f: int, tp: int) -> int:
    """Shard count the fused d_ff visit schedule supports (1 = stay
    unsharded)."""
    return tp if tp > 1 and (d_ff // block_f) % tp == 0 else 1


def _attn_tp(cfg: ModelConfig, tp: int) -> int:
    """col shards of wq/wk/wv must land on head boundaries (RoPE and
    the (B, S, H, D) reshape are per head)."""
    return tp if (tp > 1 and cfg.num_heads % tp == 0
                  and cfg.num_kv_heads % tp == 0) else 1


def _tp_fits(w: np.ndarray, kind: str, cfg: ModelConfig, tp: int) -> bool:
    """Does the matrix's block grid split evenly into ``tp`` shards?"""
    K, N = w.shape[-2:]
    bk = _fit_block(K, cfg.sasp.block_k)
    bn = _fit_block(N, cfg.sasp.block_n)
    return _shard_blocks(kind, K, N, bk, bn) % tp == 0


def _pack_matrix_group(node: Params, names, cfg: ModelConfig,
                       quantize: bool, act_for: Dict[str, Optional[str]],
                       tp: int = 1,
                       kinds: Optional[Dict[str, str]] = None
                       ) -> Optional[Dict[str, PackedSASPWeight]]:
    """Pack a group of matrices that serve together. TP sharding is
    all-or-nothing across the group (the sharded driver keeps the whole
    group inside one shard_map body, so every matrix must split)."""
    kinds = kinds or {}
    mats = []
    for name in names:
        entry = node.get(name)
        w = None if entry is None else _dense_weight(entry)
        if w is None:
            continue
        if w.ndim not in (2, 3):        # MoE expert grids etc.
            return None
        bias = None
        if isinstance(entry, dict) and "b" in entry:
            bias = np.asarray(entry["b"], np.float32)
        mats.append((name, w, bias))
    if tp > 1 and any(not _tp_fits(w, kinds.get(n, "col"), cfg, tp)
                      for n, w, _ in mats):
        tp = 1
    out = {}
    for name, w, bias in mats:
        out[name] = pack_weight(
            w, block_k=cfg.sasp.block_k, block_n=cfg.sasp.block_n,
            bias=bias, act=act_for.get(name), quantize=quantize,
            tp=tp, shard_kind=kinds.get(name, "col"),
            dtype=_value_dtype(node[name]))
    return out or None


_FFN_KINDS = {"w1": "col", "w3": "col", "w2": "row"}
_ATTN_KINDS = {"wq": "col", "wk": "col", "wv": "col", "wo": "row"}


def _deploy_slot(slot: Params, cfg: ModelConfig, *, quantize: bool,
                 fuse_ffn: bool, attn: bool, tp: int = 1) -> Params:
    slot = dict(slot)

    ffn = slot.get("ffn")
    if (isinstance(ffn, dict) and "w1" in ffn and "w2" in ffn
            and "router" not in ffn):       # MoE expert grids: masked path
        ffn = {k: v for k, v in ffn.items()
               if k not in ("sasp_bsr",)}      # packed replaces BSR
        gated = "w3" in ffn
        w1 = _dense_weight(ffn.get("w1"))
        w2 = _dense_weight(ffn.get("w2"))
        w3 = _dense_weight(ffn.get("w3")) if gated else None
        if w1 is not None and w2 is not None and w1.ndim in (2, 3):
            b2 = ffn["w2"].get("b") if isinstance(ffn["w2"], dict) \
                else None
            if gated and fuse_ffn and w3 is not None:
                F = w1.shape[-1]
                tp_f = _fused_tp(F, _fit_block(F, cfg.sasp.block_n), tp)
                ffn["sasp_fused"] = pack_ffn(
                    w1, w3, w2, block_f=cfg.sasp.block_n, act=cfg.act,
                    b1=ffn["w1"].get("b"), b3=ffn["w3"].get("b"),
                    b2=b2, quantize=quantize, tp=tp_f,
                    dtype=_value_dtype(ffn["w1"]))
            else:
                # per-matrix packed: act folds into w1's flush epilogue,
                # the gate product (if any) stays in jnp (models/ffn.py)
                act_for = {"w1": cfg.act}
                packed = _pack_matrix_group(
                    ffn, _FFN_MATS, cfg, quantize, act_for, tp=tp,
                    kinds=_FFN_KINDS)
                if packed is not None:
                    ffn["sasp_packed"] = packed
            slot["ffn"] = ffn

    mixer = slot.get("mixer")
    if attn and isinstance(mixer, dict) and all(
            m in mixer for m in _ATTN_MATS):
        mixer = dict(mixer)
        tp_a = _attn_tp(cfg, tp)
        packed = _pack_matrix_group(mixer, _ATTN_MATS, cfg, quantize, {},
                                    tp=tp_a, kinds=_ATTN_KINDS)
        if packed is not None:
            mixer["sasp_packed"] = packed
            slot["mixer"] = mixer

    return slot


def deploy_packed(params: Params, cfg: ModelConfig, *,
                  quantize: Optional[bool] = None,
                  fuse_ffn: bool = True,
                  attn: Optional[bool] = None,
                  mesh=None,
                  tp: Optional[int] = None) -> Tuple[Params,
                                                     ModelConfig]:
    """Convert a (pruned) param tree into packed serving form.

    Returns ``(params', cfg')`` where every dense/MoE-free FFN (and, for
    ``scope="all"`` or ``attn=True``, every attention projection) carries
    a kernel-ready packed container, and ``cfg'`` has
    ``sasp.path="kernel"`` so the model routes through them. Dense
    weights stay in the tree as the source of truth (XLA dead-code
    eliminates them from the serving graph); ``sasp_bsr`` overlays are
    dropped — the compact block list replaces the padded k_max × NB
    trace-time list.

    quantize: pack values as int8 + per-block scales (default: follow
    ``cfg.sasp.quantize``). fuse_ffn: use the whole-FFN fused container
    for gated FFNs (False = per-matrix packed GEMMs). mesh / tp:
    TP-shard every visit list by output-block shard for the mesh's
    'model' axis (DESIGN.md §10) — each shard carries only ITS surviving
    blocks, so per-shard pruning savings stay local; matrices whose
    block grid does not divide fall back to unsharded containers.
    """
    quantize = cfg.sasp.quantize if quantize is None else quantize
    attn = (cfg.sasp.scope == "all") if attn is None else attn
    if tp is None:
        tp = mesh.shape.get("model", 1) if mesh is not None else 1

    out = dict(params)
    segs = []
    for seg in params.get("segments", ()):
        new_seg = {}
        for slot_name, slot in seg.items():
            new_seg[slot_name] = _deploy_slot(
                slot, cfg, quantize=quantize, fuse_ffn=fuse_ffn,
                attn=attn, tp=tp)
        segs.append(new_seg)
    out["segments"] = tuple(segs)
    cfg = dataclasses.replace(
        cfg, sasp=dataclasses.replace(cfg.sasp, enabled=True,
                                      path="kernel"))
    return out, cfg


_PACKED_OVERLAYS = ("sasp_packed", "sasp_fused", "sasp_bsr")


def strip_packed(params: Params) -> Params:
    """Drop every deployment overlay (packed / fused / BSR containers)
    from a deployed tree, leaving the dense source-of-truth weights —
    the starting point for re-deploying the SAME weights at a different
    fidelity (``draft_pack``, ``reshard_packed`` rebuilds)."""
    out = dict(params)
    segs = []
    for seg in params.get("segments", ()):
        new_seg = {}
        for slot_name, slot in seg.items():
            slot = dict(slot)
            for part in ("ffn", "mixer"):
                sub = slot.get(part)
                if isinstance(sub, dict) and any(
                        k in sub for k in _PACKED_OVERLAYS):
                    slot[part] = {k: v for k, v in sub.items()
                                  if k not in _PACKED_OVERLAYS}
            new_seg[slot_name] = slot
        segs.append(new_seg)
    out["segments"] = tuple(segs)
    return out


def draft_pack(params: Params, cfg: ModelConfig, *,
               sparsity: float, quantize: bool = False,
               fuse_ffn: bool = True, mesh=None,
               tp: Optional[int] = None) -> Tuple[Params, ModelConfig]:
    """Self-speculation drafter on the sparsity ladder (DESIGN.md §17).

    Re-prune the DEPLOYED weights at a HIGHER sparsity and pack the
    result: the returned ``(params', cfg')`` is a cheap drafter for the
    full-fidelity target built from the SAME weights — identical
    architecture, so identical cache geometry, so drafter and target
    share one paged KV pool. Greedy exactness never depends on the
    drafter (every emitted token is a target argmax); drafter fidelity
    only moves the acceptance rate.

    sparsity: the drafter's global tile sparsity (normally well above
    the target's — equal or lower is legal but buys nothing).
    quantize: additionally pack drafter values as int8 + per-block
    scales (the ladder's other axis)."""
    if not 0.0 < float(sparsity) < 1.0:
        raise ValueError(
            f"draft sparsity={sparsity} must lie in (0, 1)")
    from repro.core.pruning import prune_params
    dsasp = dataclasses.replace(
        cfg.sasp, enabled=True, sparsity=float(sparsity),
        quantize=bool(quantize))
    dcfg = dataclasses.replace(cfg, sasp=dsasp)
    dense = strip_packed(params)
    pruned, _ = prune_params(dense, dsasp)
    return deploy_packed(pruned, dcfg, quantize=bool(quantize),
                         fuse_ffn=fuse_ffn, mesh=mesh, tp=tp)


# ---------------------------------------------------------------------------
# Elastic re-deploy: reshard existing containers (ROADMAP fast path)
# ---------------------------------------------------------------------------


def _zero_block_scale() -> np.float32:
    """Per-block int8 scale of an all-zero block, computed with the SAME
    array arithmetic as build_kernel_weight / build_fused_ffn so
    resharded containers stay bit-identical to from-scratch packs."""
    amax = np.zeros((1,), np.float32)
    return (np.maximum(amax, 1e-12) / 127.0).astype(np.float32)[0]


def _reshard_weight(pw: PackedSASPWeight, tp: int,
                    kind: str) -> PackedSASPWeight:
    """Slice-and-pad one packed matrix to ``tp`` shards: live visits are
    re-binned by output-column (col) / input-row (row) block shard with
    coordinates remapped shard-local, empty output columns get their
    zero flush visit, and per-(layer, shard) lists re-pad to one shared
    nnz — no dense/BSR rebuild. Bit-identical to ``pack_weight`` on the
    sliced dense weight."""
    K, N = pw.shape
    bk, bn = pw.block
    KB, NB = K // bk, N // bn
    quant = pw.scale is not None
    assert kind in ("col", "row"), kind
    assert tp == 1 or kind == "col" or pw.act is None

    vals = np.asarray(pw.vals)
    kn = np.asarray(pw.kn)
    sc = np.asarray(pw.scale) if quant else None
    stacked = vals.ndim == (5 if pw.shards > 1 else 4)
    if not stacked:
        vals, kn = vals[None], kn[None]
        sc = None if sc is None else sc[None]
    if pw.shards == 1:
        vals, kn = vals[:, None], kn[:, None]
        sc = None if sc is None else sc[:, None]
    L = vals.shape[0]

    # 1) merge shards back to per-layer GLOBAL live-visit lists (zero
    #    blocks are padding or empty-column flush entries — both get
    #    rebuilt below, so dropping every zero block is lossless)
    layers = []
    for li in range(L):
        ks, ns, vs, ss = [], [], [], []
        for s in range(pw.shards):
            v = vals[li, s]
            live = np.any(v != 0, axis=(1, 2))
            k = kn[li, s][0].astype(np.int64)
            n = kn[li, s][1].astype(np.int64)
            if pw.shards > 1:
                if (pw.shard_kind or kind) == "col":
                    n = n + s * (NB // pw.shards)
                else:
                    k = k + s * (KB // pw.shards)
            ks.append(k[live])
            ns.append(n[live])
            vs.append(v[live])
            if quant:
                ss.append(sc[li, s][live])
        layers.append((np.concatenate(ks), np.concatenate(ns),
                       np.concatenate(vs),
                       np.concatenate(ss) if quant else None))

    # 2) re-bin to the new shards, exactly as build_kernel_weight would
    #    pack the sliced dense weight
    NB_s = NB // tp if kind == "col" else NB
    KB_s = KB if kind == "col" else KB // tp
    assert (NB % tp == 0) if kind == "col" else (KB % tp == 0), (
        kind, pw.shape, pw.block, tp)
    packs = []                             # [L][tp] of (vals, kn, scale)
    for ks, ns, v, s_ in layers:
        row = []
        for s in range(tp):
            if kind == "col":
                sel = (ns >= s * NB_s) & (ns < (s + 1) * NB_s)
                k_loc, n_loc = ks[sel], ns[sel] - s * NB_s
            else:
                sel = (ks >= s * KB_s) & (ks < (s + 1) * KB_s)
                k_loc, n_loc = ks[sel] - s * KB_s, ns[sel]
            v_loc = v[sel]
            s_loc = s_[sel] if quant else None
            # zero flush visit per empty output column + (n, k) sort —
            # the one shared convention (ops.flush_sorted_order)
            k_loc, n_loc, order, n_flush = sasp_ops.flush_sorted_order(
                k_loc, n_loc, NB_s)
            if n_flush:
                v_loc = np.concatenate(
                    [v_loc, np.zeros((n_flush, bk, bn), v_loc.dtype)])
                if quant:
                    s_loc = np.concatenate(
                        [s_loc, np.full((n_flush,), _zero_block_scale(),
                                        np.float32)])
            row.append((v_loc[order],
                        np.stack([k_loc[order], n_loc[order]])
                        .astype(np.int32),
                        s_loc[order] if quant else None))
        packs.append(row)

    # 3) shared-nnz padding + stacking (mirror of pack_weight)
    nnz = max(p[0].shape[0] for lp in packs for p in lp)
    per_layer = []
    for lp in packs:
        vs, kks, sss = [], [], []
        for v, kkn, s_ in lp:
            v, kkn, s_ = sasp_ops.pad_block_list(v, kkn, s_, nnz)
            vs.append(v)
            kks.append(kkn)
            sss.append(s_)
        if tp == 1:
            per_layer.append((vs[0], kks[0], sss[0]))
        else:
            per_layer.append((np.stack(vs), np.stack(kks),
                              None if sss[0] is None else np.stack(sss)))
    new_vals = jnp.asarray(np.stack([p[0] for p in per_layer]))
    new_kn = jnp.asarray(np.stack([p[1] for p in per_layer]))
    new_sc = None if not quant else jnp.asarray(
        np.stack([p[2] for p in per_layer]).astype(np.float32))

    bias = None
    if pw.bias is not None:
        b = np.asarray(pw.bias, np.float32)
        b = b.reshape(b.shape[:-2] + (-1,)) \
            if pw.shards > 1 and pw.shard_kind == "col" else b
        if not stacked:
            b = b[None]
        if tp > 1 and kind == "col":
            b = b.reshape(L, tp, N // tp)
        bias = jnp.asarray(b)
    if not stacked:
        new_vals, new_kn = new_vals[0], new_kn[0]
        new_sc = None if new_sc is None else new_sc[0]
        bias = None if bias is None else bias[0]
    return PackedSASPWeight(new_vals, new_kn, (K, N), (bk, bn),
                            scale=new_sc, bias=bias, act=pw.act,
                            shards=tp,
                            shard_kind=kind if tp > 1 else None)


def _reshard_ffn(pf: PackedFFN, tp: int) -> PackedFFN:
    """Slice-and-pad the fused gated-FFN schedule to ``tp`` d_ff shards
    using the stored global visit indices ``jv`` (so no dense rebuild
    and exact agreement with ``pack_ffn`` on the sliced weights)."""
    assert pf.jv is not None, \
        "container predates jv visit indices; rebuild via deploy_packed"
    d, bf = pf.d_model, pf.block_f
    FB = pf.d_ff // bf
    assert tp == 1 or FB % tp == 0, (pf.d_ff, bf, tp)
    quant = pf.s1 is not None

    names = ["w1v", "w3v", "w2v", "b1", "b3", "jv"] + (
        ["s1", "s3", "s2"] if quant else [])
    base = {"w1v": 3, "w3v": 3, "w2v": 3, "b1": 2, "b3": 2, "jv": 1,
            "s1": 1, "s3": 1, "s2": 1}
    arrs = {n: np.asarray(getattr(pf, n)) for n in names}
    stacked = arrs["w1v"].ndim == base["w1v"] + (
        2 if pf.shards > 1 else 1)

    def norm(n):
        a = arrs[n]
        if not stacked:
            a = a[None]
        if pf.shards == 1:
            a = a[:, None]
        return a

    A = {n: norm(n) for n in names}
    L = A["w1v"].shape[0]
    b2 = np.asarray(pf.b2, np.float32)
    if not stacked:
        b2 = b2[None]

    def zero_visit():
        z = {"w1v": np.zeros((1, d, bf), np.float32),
             "w3v": np.zeros((1, d, bf), np.float32),
             "w2v": np.zeros((1, bf, d), np.float32),
             "b1": np.zeros((1, bf), np.float32),
             "b3": np.zeros((1, bf), np.float32),
             "jv": np.full((1,), -1, np.int32)}
        for n in ("w1v", "w3v", "w2v"):     # the container's dtype
            z[n] = z[n].astype(arrs[n].dtype)
        if quant:
            zs = _zero_block_scale()
            for n in ("s1", "s3", "s2"):
                z[n] = np.full((1,), zs, np.float32)
        return z

    packs = []                              # [L][tp] dicts
    FBs = FB // tp
    for li in range(L):
        live_parts = {n: [] for n in names}
        for s in range(pf.shards):
            live = A["jv"][li, s] >= 0
            for n in names:
                live_parts[n].append(A[n][li, s][live])
        cat = {n: np.concatenate(live_parts[n]) for n in names}
        order = np.argsort(cat["jv"], kind="stable")
        cat = {n: a[order] for n, a in cat.items()}
        row = []
        for s in range(tp):
            sel = (cat["jv"] >= s * FBs) & (cat["jv"] < (s + 1) * FBs)
            if not sel.any():               # all-pruned shard: one zero
                row.append(zero_visit())    # visit so output flushes b2
                continue
            row.append({n: a[sel] for n, a in cat.items()})
        packs.append(row)

    nv = max(p["jv"].shape[0] for lp in packs for p in lp)

    def pad(p):
        n_pad = nv - p["jv"].shape[0]
        if not n_pad:
            return p
        out = {}
        for n, a in p.items():
            if n == "jv":
                out[n] = np.concatenate(
                    [a, np.full((n_pad,), -1, np.int32)])
            else:
                out[n] = np.concatenate(
                    [a, np.zeros((n_pad,) + a.shape[1:], a.dtype)])
        return out

    packs = [[pad(p) for p in lp] for lp in packs]

    def stack(n):
        a = np.stack([np.stack([p[n] for p in lp]) for lp in packs])
        if tp == 1:
            a = a[:, 0]
        if not stacked:
            a = a[0]
        return jnp.asarray(a)

    if not stacked:
        b2 = b2[0]
    return PackedFFN(
        stack("w1v"), stack("w3v"), stack("w2v"), stack("b1"),
        stack("b3"), jnp.asarray(b2), d_model=d, d_ff=pf.d_ff,
        block_f=bf, act=pf.act,
        s1=stack("s1") if quant else None,
        s3=stack("s3") if quant else None,
        s2=stack("s2") if quant else None,
        shards=tp, jv=stack("jv"))


def reshard_packed(params: Params, cfg: ModelConfig, *, mesh=None,
                   tp: Optional[int] = None) -> Params:
    """Elastic re-deploy fast path: re-partition every packed container
    for a NEW mesh shape by slicing and padding the existing visit
    lists — cheap numpy at load time, no dense/BSR rebuild and no
    pruning-mask recovery. The result is bit-identical to a
    from-scratch ``deploy_packed(pruned, cfg, tp=tp)`` of the same
    weights (the per-shard padding, empty-column flush visits, and int8
    epsilon scales use the same arithmetic). Containers whose block
    grid (or, for attention, head count) does not divide the new ``tp``
    fall back to unsharded — the same rule as ``deploy_packed``.
    Accepts containers at ANY current shard count, so mesh shape
    changes go sharded→sharded without keeping the unsharded pack
    around."""
    if tp is None:
        tp = mesh.shape.get("model", 1) if mesh is not None else 1

    def fits(pw: PackedSASPWeight, kind: str) -> bool:
        K, N = pw.shape
        bk, bn = pw.block
        return _shard_blocks(kind, K, N, bk, bn) % tp == 0

    if "segments" not in params:
        raise ValueError("reshard_packed expects a deployed param tree "
                         "with a 'segments' entry (see deploy_packed)")
    out = dict(params)
    segs = []
    for seg in params["segments"]:
        new_seg = {}
        for slot_name, slot in seg.items():
            slot = dict(slot)
            ffn = slot.get("ffn")
            if isinstance(ffn, dict):
                ffn = dict(ffn)
                pf = ffn.get("sasp_fused")
                if isinstance(pf, PackedFFN):
                    ffn["sasp_fused"] = _reshard_ffn(
                        pf, _fused_tp(pf.d_ff, pf.block_f, tp))
                grp = ffn.get("sasp_packed")
                if isinstance(grp, dict):
                    tp_g = tp if tp > 1 and all(
                        fits(w, _FFN_KINDS.get(n, "col"))
                        for n, w in grp.items()) else 1
                    ffn["sasp_packed"] = {
                        n: _reshard_weight(w, tp_g,
                                           _FFN_KINDS.get(n, "col"))
                        for n, w in grp.items()}
                slot["ffn"] = ffn
            mixer = slot.get("mixer")
            if isinstance(mixer, dict) and isinstance(
                    mixer.get("sasp_packed"), dict):
                mixer = dict(mixer)
                grp = mixer["sasp_packed"]
                tp_a = _attn_tp(cfg, tp)
                if not all(fits(w, _ATTN_KINDS.get(n, "col"))
                           for n, w in grp.items()):
                    tp_a = 1
                mixer["sasp_packed"] = {
                    n: _reshard_weight(w, tp_a,
                                       _ATTN_KINDS.get(n, "col"))
                    for n, w in grp.items()}
                slot["mixer"] = mixer
            new_seg[slot_name] = slot
        segs.append(new_seg)
    out["segments"] = tuple(segs)
    return out


def packed_summary(params: Params) -> Dict[str, float]:
    """Deployment report: container counts + compression vs dense."""
    n_packed = n_fused = 0
    packed_bytes = dense_bytes = 0

    def visit(node):
        nonlocal n_packed, n_fused, packed_bytes, dense_bytes
        if isinstance(node, PackedSASPWeight):
            n_packed += 1
            packed_bytes += node.nbytes()
            K, N = node.shape
            lead = node.vals.shape[:-3]     # (L?, tp?) — tp spans ONE
            dense_bytes += int(np.prod(lead, dtype=np.int64)) \
                // node.shards * K * N * 4  # dense matrix, not tp of them
        elif isinstance(node, PackedFFN):
            n_fused += 1
            for a in (node.w1v, node.w3v, node.w2v):
                packed_bytes += a.size * a.dtype.itemsize
            lead = node.w1v.shape[:-3]
            dense_bytes += int(np.prod(lead, dtype=np.int64)) \
                // node.shards * 3 * node.d_model * node.d_ff * 4
        elif isinstance(node, dict):
            for v in node.values():
                visit(v)
        elif isinstance(node, (tuple, list)):
            for v in node:
                visit(v)

    visit(params)
    return {
        "n_packed_matrices": n_packed,
        "n_fused_ffns": n_fused,
        "packed_bytes": packed_bytes,
        "dense_bytes": dense_bytes,
        "compression": packed_bytes / max(dense_bytes, 1),
    }
