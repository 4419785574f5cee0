"""Flash-attention Pallas kernel (TPU target; interpreted on CPU).

Complements the SASP GEMM kernels: attention is the other compute
hot-spot of every assigned transformer. Grid = (batch·kv-heads·groups,
Q-blocks); the kernel walks KV blocks with a VMEM-resident online-softmax
accumulator (m, l, acc) — the jnp chunked attention in models/attention.py
is the oracle-equivalent reference structure.

Supports causal masking and sliding windows (gemma3's local layers) via
absolute-position operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import pallas_call

NEG_INF = -1.0e30


def _flash_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, kv_blocks: int, block_k: int,
                  window: int, scale: float):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                  # (bq, d)
    k = k_ref[0]                                  # (bk, d)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (bq, bk)

    qp = qpos_ref[...]                            # (bq,)
    kp = kpos_ref[...]                            # (bk,)
    delta = qp[:, None] - kp[None, :]
    mask = (delta >= 0) & (delta < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == kv_blocks - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-20)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, q_pos, kv_pos, *, window: int,
                    block_q: int = 128, block_k: int = 128):
    """q: (H, Sq, D); k/v: (H, Sk, D); positions absolute int32.
    Key j visible to query i iff 0 <= q_pos[i] - kv_pos[j] < window
    (window >= Sk => plain causal). Returns (H, Sq, D).

    Batch/GQA layouts fold into H upstream (ops.py)."""
    H, Sq, D = q.shape
    Sk = k.shape[1]
    bq = min(block_q, Sq)
    while Sq % bq:
        bq -= 1
    bk = min(block_k, Sk)
    while Sk % bk:
        bk -= 1
    grid = (H, Sq // bq, Sk // bk)
    scale = D ** -0.5

    return pallas_call(
        functools.partial(_flash_kernel, kv_blocks=Sk // bk, block_k=bk,
                          window=window, scale=scale),
        name="flash_attn",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq,), lambda h, i, j: (i,)),       # q positions
            pl.BlockSpec((bk,), lambda h, i, j: (j,)),       # kv positions
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # m
            pltpu.VMEM((bq,), jnp.float32),       # l
            pltpu.VMEM((bq, D), jnp.float32),     # acc
        ],
        out_shape=jax.ShapeDtypeStruct((H, Sq, D), q.dtype),
    )(q_pos.astype(jnp.int32), kv_pos.astype(jnp.int32), q, k, v)
