"""Seeded model weights, made by the benchmark and not by the program.

One jitted call makes every weight of a configuration on the device, in
the dtype it is served in, from ``--seed``. The layout is the
benchmark's own (``embed``, ``head``, ``layers/<name>`` stacked over
layers); ``bench/run.py`` hands the same arrays to the program in its
param tree, and ``bench/reference.py`` makes them again from the seed.

For a pruned configuration every FFN matrix gets exactly ``sparsity`` of
its 128×128 tiles drawn at ``pruned_tile_scale`` times the others'
scale, at positions drawn from the seed. Random Gaussian tiles of one
matrix have L1 norms within about 1% of each other, so a global
lowest-L1 selection over them would turn on rounding; the scaled tiles
make the selection the program must find unambiguous, and the reference
zeroes exactly those tiles (:func:`keep_masks`).

Imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

TILE = 128


@dataclasses.dataclass(frozen=True)
class Spec:
    """The sizes and the weight law of one configuration file."""
    d: int
    f: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    layers: int
    qk_norm: bool
    qkv_bias: bool
    tied: bool
    eps: float
    theta: float
    sparsity: float
    dtype: str
    std: float
    out_std: float
    bias_std: float
    norm_scale_std: float
    pruned_tile_scale: float

    @staticmethod
    def from_config(c: Dict) -> "Spec":
        w = c["weights"]
        return Spec(
            d=c["hidden_size"], f=c["intermediate_size"],
            heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or
            c["hidden_size"] // c["num_attention_heads"],
            vocab=c["vocab_size"], layers=c["num_hidden_layers"],
            qk_norm=bool(c["serve"]["qk_norm"]),
            qkv_bias=bool(c["serve"]["qkv_bias"]),
            tied=bool(c["tie_word_embeddings"]),
            eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
            sparsity=float(c["serve"]["sparsity"]),
            dtype=c["torch_dtype"], std=w["std"], out_std=w["out_std"],
            bias_std=w["bias_std"], norm_scale_std=w["norm_scale_std"],
            pruned_tile_scale=w["pruned_tile_scale"])

    @property
    def jdtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            self.dtype]


FFN_SHAPES = {"w1": lambda s: (s.d, s.f), "w3": lambda s: (s.d, s.f),
              "w2": lambda s: (s.f, s.d)}


def root_key(seed: int):
    """A PRNG key from any whole seed (two 32-bit words of its hash)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _keep(key, layers: int, kb: int, nb: int, sparsity: float):
    """(layers, kb, nb) bool, exactly round(sparsity·kb·nb) False per
    layer, at positions drawn from ``key``."""
    n = kb * nb
    n_prune = int(round(sparsity * n))
    ranks = jax.vmap(lambda k: jax.random.permutation(k, n))(
        jax.random.split(key, layers))
    return (ranks >= n_prune).reshape(layers, kb, nb)


@functools.partial(jax.jit, static_argnums=(1,))
def keep_masks(key, spec: Spec) -> Dict[str, jnp.ndarray]:
    """Per FFN matrix, (layers, K/128, N/128) bool: True = tile kept."""
    out = {}
    for i, (name, shape) in enumerate(FFN_SHAPES.items()):
        K, N = shape(spec)
        out[name] = _keep(jax.random.fold_in(key, 100 + i), spec.layers,
                          K // TILE, N // TILE, spec.sparsity)
    return out


def _tile_scale(w, keep, low: float):
    """Scale the tiles where ``keep`` is False by ``low``."""
    L, K, N = w.shape
    s = jnp.where(keep, 1.0, low).astype(w.dtype)
    wb = w.reshape(L, K // TILE, TILE, N // TILE, TILE)
    return (wb * s[:, :, None, :, None]).reshape(L, K, N)


@functools.partial(jax.jit, static_argnums=(1,))
def make(key, spec: Spec) -> Dict:
    """Every weight of ``spec`` from ``key``, in the served dtype."""
    dt = spec.jdtype
    L, d, f = spec.layers, spec.d, spec.f
    hq, hk = spec.heads * spec.head_dim, spec.kv_heads * spec.head_dim
    ks = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dt)

    def norm_scale(shape):
        return (1.0 + jax.random.normal(next(ks), shape, jnp.float32)
                * spec.norm_scale_std).astype(dt)

    lay = {
        "norm1": norm_scale((L, d)), "norm2": norm_scale((L, d)),
        "wq": normal((L, d, hq), spec.std),
        "wk": normal((L, d, hk), spec.std),
        "wv": normal((L, d, hk), spec.std),
        "wo": normal((L, hq, d), spec.out_std),
    }
    if spec.qkv_bias:
        lay["bq"] = normal((L, hq), spec.bias_std)
        lay["bk"] = normal((L, hk), spec.bias_std)
        lay["bv"] = normal((L, hk), spec.bias_std)
    if spec.qk_norm:
        lay["q_norm"] = norm_scale((L, spec.head_dim))
        lay["k_norm"] = norm_scale((L, spec.head_dim))
    keep = keep_masks(key, spec) if spec.sparsity > 0 else None
    for name, shape in FFN_SHAPES.items():
        w = normal((L,) + shape(spec), spec.std)
        if keep is not None:
            w = _tile_scale(w, keep[name], spec.pruned_tile_scale)
        lay[name] = w
    out = {"embed": normal((spec.vocab, d), spec.std),
           "final_norm": norm_scale((d,)), "layers": lay}
    if not spec.tied:
        out["head"] = normal((spec.vocab, d), spec.std)
    return out


def layer_names(spec: Spec) -> Tuple[str, ...]:
    names = ["norm1", "norm2", "wq", "wk", "wv", "wo", "w1", "w3", "w2"]
    if spec.qkv_bias:
        names += ["bq", "bk", "bv"]
    if spec.qk_norm:
        names += ["q_norm", "k_norm"]
    return tuple(names)
