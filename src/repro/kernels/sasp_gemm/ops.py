"""Jit'd wrappers + block-list builders for the SASP tile-skip kernel."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparse import BlockSparseWeight
from repro.kernels.sasp_gemm.kernel import (
    sasp_fused_ffn,
    sasp_gemm,
    sasp_gemm_masked,
)


def flush_sorted_order(ks: np.ndarray, ns: np.ndarray, nb: int):
    """THE visit-order convention, in one place: append a k=0 flush
    entry for every output column in [0, nb) with no visit (so every
    output block initializes/flushes exactly once), then sort by
    (n, k). Returns (ks', ns', order, n_flush) — callers append
    ``n_flush`` zero-valued blocks/scales before applying ``order``.
    Shared by :func:`kernel_block_list` (mask path) and the elastic
    re-deploy slice path (``core.deploy._reshard_weight``), whose
    bit-identity contract depends on the two never diverging."""
    empty = np.setdiff1d(np.arange(nb), np.unique(ns))
    if empty.size:
        ks = np.concatenate([ks, np.zeros_like(empty)])
        ns = np.concatenate([ns, empty])
    return ks, ns, np.lexsort((ks, ns)), int(empty.size)


def kernel_block_list(mask: np.ndarray) -> np.ndarray:
    """(2, nnz') visit list sorted by (n, k). Output column-blocks with no
    surviving weight block get one zero-value padding entry (k=0) so every
    output block is initialized; callers must zero the corresponding
    w_vals entry (``build_kernel_weight`` does)."""
    mask = np.asarray(mask, dtype=bool)
    KB, NB = mask.shape
    ks, ns = np.nonzero(mask)
    ks, ns, order, _ = flush_sorted_order(ks, ns, NB)
    return np.stack([ks[order], ns[order]]).astype(np.int32)


def build_kernel_weight(w: np.ndarray, mask: np.ndarray, bk: int, bn: int,
                        *, quantize: bool = False):
    """Offline packing: (w_vals, block_kn[, scales]) for ``sasp_matmul``,
    as host numpy arrays (a load-time deployment never stages float32
    copies on the device). Padding entries (empty output columns) carry
    zero blocks."""
    w = np.asarray(w, np.float32)
    mask = np.asarray(mask, bool)
    K, N = w.shape
    KB, NB = K // bk, N // bn
    kn = kernel_block_list(mask)
    wb = w.reshape(KB, bk, NB, bn)
    vals = np.stack([
        wb[k, :, n, :] if mask[k, n] else np.zeros((bk, bn), np.float32)
        for k, n in kn.T
    ]) if kn.shape[1] else np.zeros((1, bk, bn), np.float32)

    if not quantize:
        return vals, kn, None
    amax = np.abs(vals).max(axis=(1, 2))
    scales = (np.maximum(amax, 1e-12) / 127.0).astype(np.float32)
    q = np.clip(np.round(vals / scales[:, None, None]), -127, 127
                ).astype(np.int8)
    return q, kn, scales


def pad_block_list(vals: np.ndarray, kn: np.ndarray,
                   scales: Optional[np.ndarray], nnz_to: int):
    """Pad a compact (vals, kn, scales) visit list to ``nnz_to`` entries by
    repeating the LAST visit's (k, n) coordinates with zero-valued blocks.

    Duplicating the last coordinate keeps the n-major visit order intact
    (the appended visits share the final n-block, so the accumulator is
    neither re-initialized nor flushed early — it just accumulates zeros
    and flushes the same value once more). This is what lets per-layer
    packs of different true nnz share one static nnz under
    ``lax.scan`` over stacked layers.
    """
    nnz = vals.shape[0]
    assert nnz_to >= nnz, (nnz_to, nnz)
    if nnz_to == nnz:
        return vals, kn, scales
    pad = nnz_to - nnz
    vals = np.concatenate(
        [vals, np.zeros((pad,) + vals.shape[1:], vals.dtype)])
    kn = np.concatenate([kn, np.repeat(kn[:, -1:], pad, axis=1)], axis=1)
    if scales is not None:
        scales = np.concatenate(
            [scales, np.zeros((pad,), scales.dtype)])
    return vals, kn, scales


def build_fused_ffn(w1: np.ndarray, w3: np.ndarray, w2: np.ndarray, *,
                    block_f: int, b1=None, b3=None, b2=None,
                    quantize: bool = False, nv_pad: Optional[int] = None,
                    return_visits: bool = False):
    """Offline packing for the fused gated-FFN kernel, as host numpy
    arrays.

    w1/w3: (d, F) up-projections with pruned tiles already zeroed; w2:
    (F, d) down-projection likewise. A d_ff column-block j (width
    ``block_f``) is VISITED iff it can contribute to the output:

        any(w2[j·bf:(j+1)·bf, :] != 0)            # down row survives
        and (any(w1[:, j·bf:…] != 0) or any(b1_j))  # act(0 + 0) == 0
        and (any(w3[:, j·bf:…] != 0) or any(b3_j))  # 0 * anything == 0

    so fully pruned d_ff columns cost zero FLOPs AND zero weight bytes.
    Returns (w1v, w3v, w2v, b1v, b3v, b2, scales) — scales is None for fp
    or (s1, s3, s2) per-visit int8 scales. ``nv_pad`` pads the visit list
    with zero-w2v entries (for layer-stacked sharing of one static nv).
    ``return_visits`` appends jv, the (nv,) int32 d_ff block index of
    each visit (-1 for padding/empty entries) — consumed by
    ``core.deploy`` so packed containers stay re-shardable.
    """
    w1 = np.asarray(w1, np.float32)
    w3 = np.asarray(w3, np.float32)
    w2 = np.asarray(w2, np.float32)
    d, F = w1.shape
    assert w3.shape == (d, F) and w2.shape == (F, d), (
        w1.shape, w3.shape, w2.shape)
    bf = block_f
    assert F % bf == 0, (F, bf)
    FB = F // bf
    b1 = np.zeros((F,), np.float32) if b1 is None else np.asarray(
        b1, np.float32)
    b3 = np.zeros((F,), np.float32) if b3 is None else np.asarray(
        b3, np.float32)
    b2 = np.zeros((d,), np.float32) if b2 is None else np.asarray(
        b2, np.float32)

    keep = []
    for j in range(FB):
        sl = slice(j * bf, (j + 1) * bf)
        if not np.any(w2[sl]):
            continue
        if not (np.any(w1[:, sl]) or np.any(b1[sl])):
            continue
        if not (np.any(w3[:, sl]) or np.any(b3[sl])):
            continue
        keep.append(j)

    jv = np.asarray(keep if keep else [-1], np.int32)
    if keep:
        w1v = np.stack([w1[:, j * bf:(j + 1) * bf] for j in keep])
        w3v = np.stack([w3[:, j * bf:(j + 1) * bf] for j in keep])
        w2v = np.stack([w2[j * bf:(j + 1) * bf] for j in keep])
        b1v = np.stack([b1[j * bf:(j + 1) * bf] for j in keep])
        b3v = np.stack([b3[j * bf:(j + 1) * bf] for j in keep])
    else:
        # all of d_ff pruned: one zero visit so the output block still
        # initializes/flushes (result is exactly b2)
        w1v = np.zeros((1, d, bf), np.float32)
        w3v = np.zeros((1, d, bf), np.float32)
        w2v = np.zeros((1, bf, d), np.float32)
        b1v = np.zeros((1, bf), np.float32)
        b3v = np.zeros((1, bf), np.float32)

    if nv_pad is not None:
        nv = w1v.shape[0]
        assert nv_pad >= nv, (nv_pad, nv)
        if nv_pad > nv:
            pad = nv_pad - nv
            # zero w2v => padded visits contribute exactly nothing
            w1v = np.concatenate(
                [w1v, np.zeros((pad, d, bf), np.float32)])
            w3v = np.concatenate(
                [w3v, np.zeros((pad, d, bf), np.float32)])
            w2v = np.concatenate(
                [w2v, np.zeros((pad, bf, d), np.float32)])
            b1v = np.concatenate([b1v, np.zeros((pad, bf), np.float32)])
            b3v = np.concatenate([b3v, np.zeros((pad, bf), np.float32)])
            jv = np.concatenate([jv, np.full((pad,), -1, np.int32)])

    scales = None
    if quantize:
        def q(v):
            amax = np.abs(v).max(axis=tuple(range(1, v.ndim)))
            s = (np.maximum(amax, 1e-12) / 127.0).astype(np.float32)
            qv = np.clip(np.round(v / s.reshape((-1,) + (1,) * (v.ndim - 1))),
                         -127, 127).astype(np.int8)
            return qv, s
        w1v, s1 = q(w1v)
        w3v, s3 = q(w3v)
        w2v, s2 = q(w2v)
        scales = (s1, s3, s2)

    out = (w1v, w3v, w2v, b1v, b3v, b2, scales)
    if return_visits:
        out = out + (jv,)
    return out


@functools.partial(jax.jit, static_argnames=("act", "block_m"))
def _fused_ffn_jit(x, w1v, w3v, w2v, b1, b3, b2, scales, layer, *, act,
                   block_m):
    return sasp_fused_ffn(x, w1v, w3v, w2v, b1, b3, b2, layer=layer,
                          act=act, block_m=block_m, scales=scales)


def fused_ffn_matmul(x: jnp.ndarray, w1v, w3v, w2v, b1, b3, b2, *,
                     scales=None, layer=None, act: str = "silu",
                     block_m: int = 128) -> jnp.ndarray:
    """(…, d) -> (…, d) gated FFN through the single fused kernel.
    ``layer``: for layer-stacked operands, the layer to run (see
    ``kernel.sasp_fused_ffn``)."""
    *lead, d = x.shape
    y = _fused_ffn_jit(x.reshape(-1, d), w1v, w3v, w2v, b1, b3, b2,
                       scales, layer, act=act, block_m=block_m)
    return y.reshape(*lead, d).astype(x.dtype)


def _kn_from_bsr(w: BlockSparseWeight) -> Tuple:
    """Flatten a BSR container to the kernel's flat-block-list form."""
    K, N = w.shape
    bk, bn = w.block
    KB, NB = K // bk, N // bn
    idx = np.asarray(w.idx)                       # (k_max, NB)
    vals = np.asarray(w.vals)                     # (k_max, NB, bk, bn)
    scale = None if w.scale is None else np.asarray(w.scale)
    kn_list, v_list, s_list = [], [], []
    for n in range(NB):
        seen = set()
        wrote = False
        for j in range(w.k_max):
            k = int(idx[j, n])
            vb = vals[j, n]
            if (k in seen) and not np.any(vb):
                continue                          # padding duplicate
            seen.add(k)
            kn_list.append((k, n))
            v_list.append(vb)
            s_list.append(1.0 if scale is None else float(scale[j, n]))
            wrote = True
        if not wrote:
            kn_list.append((0, n))
            v_list.append(np.zeros_like(vals[0, 0]))
            s_list.append(1.0)
    kn = np.asarray(kn_list, np.int32).T
    v = np.stack(v_list)
    s = None if scale is None else np.asarray(s_list, np.float32)
    return jnp.asarray(v), jnp.asarray(kn), \
        None if s is None else jnp.asarray(s)


@functools.partial(jax.jit, static_argnames=("n", "block_m", "act"))
def _sasp_matmul_jit(x, w_vals, block_kn, scales, bias=None, *, n,
                     block_m, act=None):
    return sasp_gemm(x, w_vals, block_kn, n=n, block_m=block_m,
                     scales=scales, bias=bias, act=act)


def _kn_from_bsr_traced(w: BlockSparseWeight):
    """Trace-compatible BSR→flat-list: every padded (j, n) slot becomes a
    visit in n-major order (consecutive visits share the output block, as
    the kernel requires); padding slots carry zero values and contribute
    nothing. nnz = k_max × NB is static; the coordinates are runtime
    arrays (scalar-prefetch operands may be traced)."""
    K, N = w.shape
    bk, bn = w.block
    k_max, NB = w.idx.shape
    vals = jnp.moveaxis(w.vals, 0, 1).reshape(k_max * NB, bk, bn)
    kn = jnp.stack([
        jnp.moveaxis(w.idx, 0, 1).reshape(-1),
        jnp.repeat(jnp.arange(NB, dtype=jnp.int32), k_max),
    ]).astype(jnp.int32)
    scales = None
    if w.scale is not None:
        scales = jnp.moveaxis(w.scale, 0, 1).reshape(-1)
    return vals, kn, scales


def sasp_matmul(x: jnp.ndarray, w: BlockSparseWeight, *,
                block_m: int = 128) -> jnp.ndarray:
    """(…, K) @ BSR weight -> (…, N) through the Pallas tile-skip kernel.
    Works under tracing (scan-over-layers) via the padded flat list;
    serving engines should pre-pack the compact form with
    ``build_kernel_weight`` + ``sasp_matmul_packed``."""
    *lead, K = x.shape
    x2 = x.reshape(-1, K)
    w_vals, block_kn, scales = _kn_from_bsr_traced(w)
    y = _sasp_matmul_jit(x2, w_vals, block_kn, scales, n=w.shape[1],
                         block_m=block_m)
    return y.reshape(*lead, w.shape[1]).astype(x.dtype)


def sasp_matmul_packed(x: jnp.ndarray, w_vals, block_kn, scales=None, *,
                       n: int, block_m: int = 128, bias=None,
                       act: Optional[str] = None) -> jnp.ndarray:
    """Pre-packed fast path (serving): inputs from build_kernel_weight.
    ``bias``/``act`` run as flush-time epilogues inside the kernel."""
    *lead, K = x.shape
    y = _sasp_matmul_jit(x.reshape(-1, K), w_vals, block_kn, scales, bias,
                         n=n, block_m=block_m, act=act)
    return y.reshape(*lead, n).astype(x.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_k", "block_n"))
def masked_matmul(x, w, mask, *, block_m: int = 128, block_k: int = 128,
                  block_n: int = 128):
    """Dense-grid compute-skip variant (ablation)."""
    return sasp_gemm_masked(x, w, mask, block_m=block_m, block_k=block_k,
                            block_n=block_n)
