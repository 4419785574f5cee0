"""The plain reference: a float32 forward pass in straightforward
``jax.numpy``, with no kernel, cache, batching or scan, under
``default_matmul_precision("highest")``.

It follows the published Qwen2/Qwen3 decoder: RMSNorm, grouped-query
attention with RoPE (rotate-half), qk-norm (Qwen3) or qkv bias
(Qwen2.5), causal softmax, SwiGLU FFN, final RMSNorm and an untied (or
tied) head. Pruned FFN tiles are zero. Weights come from
``bench/weights.py`` for the same seed; nothing is taken from the
program.

``gaps`` runs one sequence layer by layer (one layer's float32 weights
live at a time) and returns, at each position asked for, how far the
logit of a given token lies below the reference's largest logit there.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from weights import TILE, Spec

PAD = 512           # sequences are right-padded to a multiple of this
HEAD_ROWS = 512     # logits are made this many rows at a time


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """x: (S, H, D); rotate-half RoPE at absolute positions ``pos``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def zero_pruned(w, keep):
    """w: (K, N); keep: (K/128, N/128) bool."""
    K, N = w.shape
    wb = w.reshape(K // TILE, TILE, N // TILE, TILE)
    return (wb * keep[:, None, :, None]).reshape(K, N)


def quantize_int8(w):
    """Symmetric int8 per output column (the control's precision)."""
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 127.0
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _f32(tree, int8: bool):
    out = {}
    for k, v in tree.items():
        v = v.astype(jnp.float32)
        if int8 and v.ndim == 2:
            v = quantize_int8(v)
        out[k] = v
    return out


@functools.partial(jax.jit, static_argnums=(0, 5))
def layer(spec: Spec, x, w: Dict, keep: Optional[Dict], n_valid,
          int8: bool = False):
    """One decoder layer over x (S, d) float32; positions 0..S-1, keys
    beyond ``n_valid`` (right padding) masked."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w, int8)
        if keep is not None:
            for name in ("w1", "w3", "w2"):
                w[name] = zero_pruned(w[name], keep[name])
        S = x.shape[0]
        H, KH, D = spec.heads, spec.kv_heads, spec.head_dim
        pos = jnp.arange(S)
        h = rmsnorm(x, w["norm1"], spec.eps)
        q, k, v = h @ w["wq"], h @ w["wk"], h @ w["wv"]
        if spec.qkv_bias:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        q = q.reshape(S, H, D)
        k = k.reshape(S, KH, D)
        v = v.reshape(S, KH, D)
        if spec.qk_norm:
            q = rmsnorm(q, w["q_norm"], spec.eps)
            k = rmsnorm(k, w["k_norm"], spec.eps)
        q, k = rope(q, pos, spec.theta), rope(k, pos, spec.theta)
        G = H // KH
        q = q.reshape(S, KH, G, D)
        mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)

        def one_kv_head(args):          # bounds the (G, S, S) scores
            qh, kh, vh = args           # (S, G, D), (S, D), (S, D)
            s = jnp.einsum("qgd,kd->gqk", qh, kh) * D ** -0.5
            s = jnp.where(mask[None], s, -jnp.inf)
            return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, -1), vh)

        o = jax.lax.map(one_kv_head, (jnp.moveaxis(q, 1, 0),
                                      jnp.moveaxis(k, 1, 0),
                                      jnp.moveaxis(v, 1, 0)))
        o = jnp.moveaxis(o, 0, 1).reshape(S, H * D)
        x = x + o @ w["wo"]
        h = rmsnorm(x, w["norm2"], spec.eps)
        y = (jax.nn.silu(h @ w["w1"]) * (h @ w["w3"])) @ w["w2"]
        return x + y


@functools.partial(jax.jit, static_argnums=(0, 6))
def head_gaps(spec: Spec, x, final_norm, table, tokens, alt, int8=False):
    """For rows x (R, d): the largest logit minus the logit of ``tokens``
    (R,), the same for ``alt`` (R,), and the argmax (R,)."""
    with jax.default_matmul_precision("highest"):
        t = table.astype(jnp.float32)
        if int8:
            t = quantize_int8(t.T).T
        lg = rmsnorm(x, final_norm, spec.eps) @ t.T
        best = jnp.max(lg, axis=-1)
        at = jnp.take_along_axis(lg, tokens[:, None], -1)[:, 0]
        alt_at = jnp.take_along_axis(lg, alt[:, None], -1)[:, 0]
        return best - at, best - alt_at, jnp.argmax(lg, -1).astype(
            jnp.int32)


def _layer_weights(W: Dict, i: int):
    return {k: v[i] for k, v in W["layers"].items()}


def forward_rows(spec: Spec, W: Dict, keep: Optional[Dict],
                 seq: np.ndarray, int8: bool = False):
    """Hidden states (S_pad, d) float32 of one right-padded sequence."""
    S = len(seq)
    S_pad = -(-S // PAD) * PAD
    toks = np.zeros((S_pad,), np.int32)
    toks[:S] = seq
    x = jnp.take(W["embed"], jnp.asarray(toks), axis=0).astype(
        jnp.float32)
    for i in range(spec.layers):
        kp = None if keep is None else {k: v[i] for k, v in keep.items()}
        x = layer(spec, x, _layer_weights(W, i), kp, jnp.int32(S), int8)
    return x


def gaps(spec: Spec, W: Dict, keep: Optional[Dict], prompt: np.ndarray,
         served: Sequence[int], alt: Optional[Sequence[int]] = None,
         int8: bool = False) -> Dict[str, np.ndarray]:
    """Reference over ``prompt + served[:-1]``. At the position that
    predicts each served token: ``served`` = best logit minus the served
    token's logit (>= 0), ``alt`` = the same for ``alt`` tokens, ``top``
    = the reference's own argmax. ``int8`` runs the control precision."""
    served = np.asarray(served, np.int32)
    alt = served if alt is None else np.asarray(alt, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    x = forward_rows(spec, W, keep, seq, int8)
    first = len(prompt) - 1
    n = len(served)
    table = W["embed"] if spec.tied else W["head"]
    out: Dict[str, List[np.ndarray]] = {"served": [], "alt": [], "top": []}
    for r0 in range(0, n, HEAD_ROWS):
        r1 = min(n, r0 + HEAD_ROWS)
        rows = np.zeros((HEAD_ROWS,), np.int32)
        rows[:r1 - r0] = np.arange(first + r0, first + r1)
        tk = np.zeros((HEAD_ROWS,), np.int32)
        tk[:r1 - r0] = served[r0:r1]
        ak = np.zeros((HEAD_ROWS,), np.int32)
        ak[:r1 - r0] = alt[r0:r1]
        g, ga, top = head_gaps(spec, x[jnp.asarray(rows)], W["final_norm"],
                               table, jnp.asarray(tk), jnp.asarray(ak),
                               int8)
        out["served"].append(np.asarray(g)[:r1 - r0])
        out["alt"].append(np.asarray(ga)[:r1 - r0])
        out["top"].append(np.asarray(top)[:r1 - r0])
    return {k: np.concatenate(v) for k, v in out.items()}


def logits(spec: Spec, W: Dict, keep: Optional[Dict],
           seq: np.ndarray) -> np.ndarray:
    """All (S, V) float32 logits of one sequence (small sizes: tests)."""
    x = forward_rows(spec, W, keep, np.asarray(seq, np.int32))[:len(seq)]
    table = W["embed"] if spec.tied else W["head"]
    with jax.default_matmul_precision("highest"):
        return np.asarray(rmsnorm(x, W["final_norm"], spec.eps)
                          @ table.astype(jnp.float32).T)
