"""Jit'd wrapper: fold (B, S, KH, G, D) GQA layouts into the kernel's
(H, S, D) form."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attn.kernel import flash_attention


@functools.partial(jax.jit, static_argnames=("window",))
def mha(q, k, v, q_pos, kv_pos, *, window: int):
    """q: (B, Sq, H, D); k/v: (B, Sk, KH, D) with H % KH == 0 (GQA).
    Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    kr = jnp.repeat(k, G, axis=2)
    vr = jnp.repeat(v, G, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = kr.transpose(0, 2, 1, 3).reshape(B * H, -1, D)
    vf = vr.transpose(0, 2, 1, 3).reshape(B * H, -1, D)
    o = flash_attention(qf, kf, vf, q_pos, kv_pos, window=window)
    return o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
