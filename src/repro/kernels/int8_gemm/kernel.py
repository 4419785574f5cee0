"""Dense weight-INT8 GEMM with fused per-block dequantization — the
paper's FP32_INT8 configuration (§3.3) without pruning.

Weights stay int8 through HBM→VMEM (4× fewer weight bytes: the paper's
four-weights-per-bus-word), are widened in-register and the per-(k,n)-block
scale is applied as an epilogue on the MXU partial — functionally the
paper's hybrid FP32×INT8 multiplier (sign ⊕, magnitude multiply, exponent
fixup ≡ scale multiply). NaN/Inf/subnormal weights are not special-cased,
matching the paper's design choice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import pallas_call


def _int8_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32) \
        * s_ref[0, 0]

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def int8_gemm(x: jnp.ndarray, w_q: jnp.ndarray, scale: jnp.ndarray, *,
              block_m: int = 128, block_k: int = 128, block_n: int = 128,
              out_dtype=None) -> jnp.ndarray:
    """x: (M, K) fp; w_q: (K, N) int8; scale: (KB, NB) fp32 per-block.
    Kernel blocks must align with quant blocks (bk | quant_bk etc.); here
    we require the quant grid to equal the kernel grid for a scale to be
    constant per kernel block."""
    M, K = x.shape
    K2, N = w_q.shape
    KB, NB = scale.shape
    bk, bn = K // KB, N // NB
    bm = min(block_m, M)
    while M % bm:
        bm -= 1
    out_dtype = out_dtype or x.dtype
    return pallas_call(
        _int8_kernel, name="int8_gemm",
        grid=(M // bm, NB, KB),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
    )(x, w_q, scale)
