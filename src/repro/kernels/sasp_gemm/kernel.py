"""SASP tile-skip GEMM — the paper's systolic-array tile skipping (Fig 3)
as a TPU Pallas kernel.

TPU adaptation (DESIGN.md §2): instead of skipping weight-programming +
streaming on an edge array, the kernel's grid enumerates ONLY the surviving
weight blocks. The grid is (M-blocks × nnz): scalar-prefetched (k, n) block
coordinates drive the BlockSpec index maps, so pruned blocks are never
DMA'd from HBM and never enter the MXU — both the FLOP term and the
weight-byte term drop ∝ sparsity, exactly the paper's saving.

Visit order is sorted by (n, k) (see ops.kernel_block_list): all surviving
K-blocks of an output column-block are consecutive, so the output block
stays VMEM-resident; a float32 VMEM scratch accumulator re-initializes
when the n-coordinate changes and flushes on its last visit. Output
column-blocks with zero surviving weight blocks get one zero-valued
padding entry so every output block is written.

Variants (one kernel body, ``_sasp_kernel``):
  * fp32/bf16 values;
  * fused INT8 dequant: int8 blocks ride HBM→VMEM at 1 byte/weight (the
    paper's 4-per-bus-word), and the per-block scale, scalar-prefetched
    into SMEM, is applied as an epilogue after the MXU dot — the TPU
    analogue of the paper's hybrid FP32×INT8 multiplier (§3.3).

Fused epilogues (DESIGN.md §9): every variant optionally applies a
per-output-column bias and an elementwise activation inside the
``last``-visit flush — the (M, N) pre-activation never round-trips to
HBM, so serving-side bias+act costs zero extra memory traffic.

``sasp_fused_ffn`` goes one level further: the whole gated FFN
(w1/w3 up-projections, gate product, w2 down-projection) runs through a
single visit schedule over surviving d_ff column-blocks; the (M, d_ff)
intermediate lives only as one (bm, bf) VMEM tile per visit and is never
materialized in HBM.

Block shapes default to MXU-aligned 128 multiples. Every call goes
through ``kernels.dispatch.pallas_call``: Mosaic on a TPU, the Pallas
interpreter elsewhere (the CPU tests check the kernels against ref.py
that way); tests/test_tpu_compile.py compiles them for v5e.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import pallas_call

# Activations legal as flush-time epilogues. All map 0 -> 0 (except the
# identity), which the fused-FFN visit-skip rule relies on.
_ACTS = {
    None: lambda x: x,
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": lambda x: jnp.maximum(x, 0.0),
}


def _flags(kn_ref, nnz: int):
    s = pl.program_id(1)
    n_cur = kn_ref[1, s]
    n_prev = kn_ref[1, jnp.maximum(s, 1) - 1]
    first = jnp.logical_or(s == 0, n_cur != n_prev)
    n_next = kn_ref[1, jnp.minimum(s + 1, nnz - 1)]
    last = jnp.logical_or(s == nnz - 1, n_cur != n_next)
    return first, last


def _row_blocks(M: int, block_m: int):
    """(bm, Mp): rows per M-block and the row count padded to whole
    blocks. M ≤ block_m is one block of all M rows (a block equal to the
    whole dimension is always legal for Mosaic). Larger M splits into
    equal blocks whose row count is a multiple of 8, the sublane tile, so
    that no prompt length makes an illegal block; the Mp - M pad rows
    are zeros and are sliced off the output."""
    if M <= block_m:
        return M, M
    nb = pl.cdiv(M, block_m)
    bm = pl.cdiv(pl.cdiv(M, nb), 8) * 8
    return bm, nb * bm


def _pad_rows(x: jnp.ndarray, Mp: int) -> jnp.ndarray:
    M = x.shape[0]
    return x if Mp == M else jnp.pad(x, ((0, Mp - M), (0, 0)))


# Scoped VMEM a kernel may use. The compiler's default (16 MiB) is
# about what the fused FFN holds at d 5120 with 128-row blocks in bf16
# (x, three double-buffered weight tiles, output, f32 accumulator),
# and float32 tiles need twice that; v5e has 128 MiB per core, the rest
# left to the compiler's own scratch.
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=96 * 2 ** 20)


def _sasp_kernel(kn_ref, *refs, nnz: int, act: Optional[str],
                 scaled: bool, biased: bool):
    """One visit: acc += x_blk @ w_blk (× the block's int8 dequant scale
    when ``scaled``); on an output block's last visit, flush
    act(acc + bias)."""
    refs = list(refs)
    s_ref = refs.pop(0) if scaled else None      # (nnz,) f32, SMEM
    x_ref, w_ref = refs.pop(0), refs.pop(0)
    b_ref = refs.pop(0) if biased else None
    o_ref, acc_ref = refs
    first, last = _flags(kn_ref, nnz)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    part = jnp.dot(x, w_ref[0].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    if scaled:
        part = part * s_ref[pl.program_id(1)]    # dequant epilogue
    acc_ref[...] += part

    @pl.when(last)
    def _flush():
        y = acc_ref[...]
        if biased:
            y = y + b_ref[...]
        o_ref[...] = _ACTS[act](y).astype(o_ref.dtype)


def sasp_gemm(x: jnp.ndarray, w_vals: jnp.ndarray, block_kn: jnp.ndarray,
              *, n: int, block_m: int = 128,
              scales: Optional[jnp.ndarray] = None,
              bias: Optional[jnp.ndarray] = None,
              act: Optional[str] = None,
              out_dtype=None) -> jnp.ndarray:
    """x: (M, K) @ block-sparse weight -> (M, n), skipping pruned tiles.

    w_vals: (nnz, bk, bn) surviving blocks (fp, or int8 with ``scales``);
    block_kn: (2, nnz) int32 [k_block; n_block] sorted by (n, k), every
    n-block present ≥ once (ops.kernel_block_list guarantees this);
    scales: (nnz,) fp32 per-block dequant scales for the int8 variant,
    scalar-prefetched into SMEM beside ``block_kn``;
    bias: (n,) fp32 fused into the last-visit flush;
    act: None|"silu"|"gelu"|"relu" flush-time activation epilogue
    (applied after bias). Empty output columns flush ``act(bias)`` —
    exactly the masked-dense semantics ``act(x @ (w ⊙ mask) + b)``.
    Int8 blocks are widened to x's dtype for the MXU (exact: |q| ≤ 127).
    """
    M, K = x.shape
    nnz, bk, bn = w_vals.shape
    assert n % bn == 0 and K % bk == 0, (K, n, bk, bn)
    assert act in _ACTS, act
    bm, Mp = _row_blocks(M, block_m)
    out_dtype = out_dtype or x.dtype
    scaled, biased = scales is not None, bias is not None

    # index maps see (grid indices…, *scalar-prefetch refs)
    x_spec = pl.BlockSpec((bm, bk), lambda i, s, kn, *_: (i, kn[0, s]))
    w_spec = pl.BlockSpec((1, bk, bn), lambda i, s, *_: (s, 0, 0))
    o_spec = pl.BlockSpec((bm, bn), lambda i, s, kn, *_: (i, kn[1, s]))
    b_spec = pl.BlockSpec((1, bn), lambda i, s, kn, *_: (0, kn[1, s]))

    prefetch = [block_kn] + ([scales.astype(jnp.float32)] if scaled
                             else [])
    in_specs, operands = [x_spec, w_spec], [_pad_rows(x, Mp), w_vals]
    if biased:
        in_specs.append(b_spec)
        operands.append(bias.astype(jnp.float32).reshape(1, n))

    y = pallas_call(
        functools.partial(_sasp_kernel, nnz=nnz, act=act, scaled=scaled,
                          biased=biased),
        name="sasp_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(Mp // bm, nnz),
            in_specs=in_specs,
            out_specs=o_spec,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, n), out_dtype),
        compiler_params=_PARAMS,
    )(*prefetch, *operands)
    return y[:M]


# ---------------------------------------------------------------------------
# Fused gated FFN: act(x@W1 + b1) * (x@W3 + b3) @ W2 + b2 in ONE visit
# schedule over surviving d_ff column-blocks. The (M, d_ff) intermediate
# exists only as a (bm, bf) VMEM tile per visit — never in HBM — and the
# three kernel launches of the unfused path collapse to one.
# ---------------------------------------------------------------------------


def _fused_ffn_kernel(*refs, nv: int, act: str, scaled: bool):
    refs = list(refs)
    sc_ref = refs.pop(0) if scaled else None     # (3, nv) f32, SMEM
    (x_ref, w1_ref, w3_ref, w2_ref, b1_ref, b3_ref, b2_ref, o_ref,
     acc_ref) = refs
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def mm(a, w_ref, j):
        y = jnp.dot(a, w_ref[0].astype(a.dtype),
                    preferred_element_type=jnp.float32)
        return y * sc_ref[j, s] if scaled else y

    x = x_ref[...]
    u = mm(x, w1_ref, 0) + b1_ref[0]
    g = mm(x, w3_ref, 1) + b3_ref[0]
    h = (_ACTS[act](u) * g).astype(x.dtype)
    acc_ref[...] += mm(h, w2_ref, 2)

    @pl.when(s == nv - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] + b2_ref[...]).astype(o_ref.dtype)


def sasp_fused_ffn(x: jnp.ndarray, w1v: jnp.ndarray, w3v: jnp.ndarray,
                   w2v: jnp.ndarray, b1: jnp.ndarray, b3: jnp.ndarray,
                   b2: jnp.ndarray, *, act: str = "silu",
                   block_m: int = 128, scales=None,
                   out_dtype=None) -> jnp.ndarray:
    """Gated FFN through one Pallas visit schedule.

    x: (M, d); w1v/w3v: (nv, d, bf) surviving d_ff column-blocks of the
    up-projections (masked tiles zeroed in place); w2v: (nv, bf, d)
    matching down-projection row-blocks; b1/b3: (nv, bf) per-visit bias
    slices; b2: (d,). ``scales``: optional (s1, s3, s2) each (nv,) fp32
    for int8 w1v/w3v/w2v, scalar-prefetched into SMEM. Pruned d_ff
    column-blocks (zero up-column with zero bias, or zero w2 row) are
    simply absent from the visit list — the skip criterion in
    ops.build_fused_ffn relies on act(0) == 0. The gate product h is
    rounded to x's dtype before the down-projection, as a dense FFN in
    that dtype rounds it.
    Returns (M, d) = act(x@W1+b1) * (x@W3+b3) @ W2 + b2.
    """
    M, d = x.shape
    nv, d2, bf = w1v.shape
    assert d2 == d and w2v.shape == (nv, bf, d), (w1v.shape, w2v.shape)
    assert act in _ACTS and act is not None
    bm, Mp = _row_blocks(M, block_m)
    out_dtype = out_dtype or x.dtype
    scaled = scales is not None

    x_spec = pl.BlockSpec((bm, d), lambda i, s, *_: (i, 0))
    up_spec = pl.BlockSpec((1, d, bf), lambda i, s, *_: (s, 0, 0))
    dn_spec = pl.BlockSpec((1, bf, d), lambda i, s, *_: (s, 0, 0))
    # (1, 1, bf): the last two block dims equal the array's (1, bf)
    bu_spec = pl.BlockSpec((1, 1, bf), lambda i, s, *_: (s, 0, 0))
    b2_spec = pl.BlockSpec((1, d), lambda i, s, *_: (0, 0))
    o_spec = pl.BlockSpec((bm, d), lambda i, s, *_: (i, 0))

    prefetch = [jnp.stack(scales).astype(jnp.float32)] if scaled else []
    return pallas_call(
        functools.partial(_fused_ffn_kernel, nv=nv, act=act,
                          scaled=scaled),
        name="sasp_ffn_fused",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(Mp // bm, nv),
            in_specs=[x_spec, up_spec, up_spec, dn_spec, bu_spec, bu_spec,
                      b2_spec],
            out_specs=o_spec,
            scratch_shapes=[pltpu.VMEM((bm, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, d), out_dtype),
        compiler_params=_PARAMS,
    )(*prefetch, _pad_rows(x, Mp), w1v, w3v, w2v,
      b1.astype(jnp.float32).reshape(nv, 1, bf),
      b3.astype(jnp.float32).reshape(nv, 1, bf),
      b2.astype(jnp.float32).reshape(1, d))[:M]


# ---------------------------------------------------------------------------
# Dense-grid masked variant (ablation): visits every (k, n) block and
# predicates the MXU issue on the mask — saves FLOPs but not DMA bytes,
# mirroring clock-gating designs the paper cites ([18]) as the inferior
# alternative to full tile skipping.
# ---------------------------------------------------------------------------


def _masked_kernel(mask_ref, x_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    j = pl.program_id(1)

    @pl.when(mask_ref[k, j] > 0)
    def _mac():
        x = x_ref[...]
        acc_ref[...] += jnp.dot(x, w_ref[...].astype(x.dtype),
                                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def sasp_gemm_masked(x: jnp.ndarray, w: jnp.ndarray, mask: jnp.ndarray,
                     *, block_m: int = 128, block_k: int = 128,
                     block_n: int = 128, out_dtype=None) -> jnp.ndarray:
    """x: (M, K) @ w (K, N) with mask (KB, NB) int32; compute-skip only."""
    M, K = x.shape
    K2, N = w.shape
    KB, NB = mask.shape
    bk, bn = K // KB, N // NB
    bm, Mp = _row_blocks(M, block_m)
    out_dtype = out_dtype or x.dtype
    return pallas_call(
        _masked_kernel, name="sasp_matmul_masked",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Mp // bm, NB, KB),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k, m: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k, m: (k, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, m: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
    )(mask.astype(jnp.int32), _pad_rows(x, Mp), w)[:M]
