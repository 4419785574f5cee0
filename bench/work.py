"""The work a step has to do, counted from shapes and from the deployed
non-zero weight tiles, never from what a kernel chooses to visit: a
kernel that skips zero tiles and one that reads them are held to the
same work.

``Model`` holds the sizes and the count of non-zero 128×128 tiles of
each FFN matrix per layer (from :func:`count_nonzero_tiles` over the
deployed weights). Counts are in operations (a multiply-add is 2) and
bytes.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Sequence

import numpy as np

TILE = 128
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> Dict:
    """The peak table row of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(PEAKS, encoding="utf-8") as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS} (known: {sorted(table)})")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Model:
    d: int
    f: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    layers: int
    weight_bytes: int               # bytes of one served weight
    ffn_tiles: Sequence[int]        # non-zero FFN tiles, per layer

    @property
    def attn_weights(self) -> int:
        """Weights of one layer's q, k, v and o projections."""
        hq = self.heads * self.head_dim
        hk = self.kv_heads * self.head_dim
        return self.d * (hq + 2 * hk) + hq * self.d

    def ffn_weights(self, layer: int) -> int:
        return int(self.ffn_tiles[layer]) * TILE * TILE

    def token_flops(self, ctx: int, head: bool) -> float:
        """Operations to process one token that attends to ``ctx``
        positions (itself included), with the LM head if ``head``."""
        per_layer_attn = 4 * self.heads * self.head_dim * ctx
        w = sum(self.attn_weights + self.ffn_weights(i)
                for i in range(self.layers))
        return (2.0 * w + self.layers * per_layer_attn
                + (2.0 * self.vocab * self.d if head else 0.0))

    def prefill_flops(self, prompt_len: int) -> float:
        """A prompt of ``prompt_len`` tokens, head on its last token."""
        n = prompt_len
        ctx_sum = n * (n + 1) // 2
        w = sum(self.attn_weights + self.ffn_weights(i)
                for i in range(self.layers))
        return (2.0 * w * n
                + self.layers * 4 * self.heads * self.head_dim * ctx_sum
                + 2.0 * self.vocab * self.d)

    def ffn_call(self, layer: int, rows: int) -> Dict[str, float]:
        """One gated FFN call over ``rows`` rows: operations, and bytes
        (non-zero weights once, rows in and out)."""
        w = self.ffn_weights(layer)
        return {"flops": 2.0 * rows * w,
                "bytes": float(w * self.weight_bytes
                               + 2 * rows * self.d * self.weight_bytes)}


def least_time(flops: float, nbytes: float, peak: Dict) -> Dict:
    """The larger of operations over peak rate and bytes over peak
    bandwidth, and which of the two bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return {"s": max(t_c, t_m), "bound": "compute" if t_c >= t_m
            else "memory"}


def count_nonzero_tiles(w) -> np.ndarray:
    """(L, K, N) weights -> (L,) count of 128×128 tiles holding any
    non-zero value."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def count(a):
        L, K, N = a.shape
        t = a.reshape(L, K // TILE, TILE, N // TILE, TILE)
        return jnp.sum(jnp.any(t != 0, axis=(2, 4)), axis=(1, 2))

    return np.asarray(count(w))
