"""SASP structured pruning (paper §3.1).

Weights are viewed as grids of (block_k × block_n) tiles — the tile matched
to the accelerator (paper: systolic array size; here: the Pallas kernel /
MXU block). Tiles are scored by L1 norm and the lowest-scoring fraction is
zeroed **globally across the model**, which prunes layers heterogeneously
according to sensitivity (reproducing paper Fig 8: early FF layers lose far
more tiles than late ones).

The mask representation is per-weight: bool (KB, NB) with True = keep.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SASPConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Tiling helpers
# ---------------------------------------------------------------------------


def block_grid(shape: Tuple[int, int], bk: int, bn: int) -> Tuple[int, int]:
    K, N = shape
    if K % bk or N % bn:
        raise ValueError(f"weight {shape} not divisible by block ({bk},{bn})")
    return K // bk, N // bn


def effective_blocks(shape: Tuple[int, int], bk: int, bn: int
                     ) -> Tuple[int, int]:
    """Clamp the tile to the matrix dims (small MoE experts: a 512-wide
    expert with block 512 degenerates to whole-matrix granularity)."""
    K, N = shape
    return min(bk, K), min(bn, N)


@functools.partial(jax.jit, static_argnums=(1, 2))
def tile_l1(w: jnp.ndarray, bk: int, bn: int) -> jnp.ndarray:
    """L1 norm per (bk × bn) tile. w: (..., K, N) -> (..., KB, NB).
    Jitted so |w| in float32 is reduced as it is made, never stored: a
    stack of full-width layers would otherwise hold a float32 copy."""
    *lead, K, N = w.shape
    KB, NB = K // bk, N // bn
    wb = jnp.abs(w.reshape(*lead, KB, bk, NB, bn).astype(jnp.float32))
    return wb.sum(axis=(-3, -1))


def upsample_mask(mask: jnp.ndarray, bk: int, bn: int) -> jnp.ndarray:
    """(..., KB, NB) bool -> (..., KB*bk, NB*bn)."""
    *lead, KB, NB = mask.shape
    m = jnp.broadcast_to(mask[..., :, None, :, None],
                         (*lead, KB, bk, NB, bn))
    return m.reshape(*lead, KB * bk, NB * bn)


def apply_block_mask(w: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """w: (..., K, N); mask: (..., KB, NB) bool. Zero pruned tiles without
    materializing an upsampled mask the size of w twice."""
    *lead, K, N = w.shape
    KB, NB = mask.shape[-2], mask.shape[-1]
    bk, bn = K // KB, N // NB
    wb = w.reshape(*lead, KB, bk, NB, bn)
    wb = wb * mask[..., :, None, :, None].astype(w.dtype)
    return wb.reshape(*lead, K, N)


# ---------------------------------------------------------------------------
# Global L1 tile selection
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PrunableLeaf:
    """One prunable weight matrix inside the model pytree."""

    path: Tuple                      # jax.tree_util key path
    shape: Tuple[int, ...]           # (..., K, N); leading dims = stacking
    bk: int                          # effective block (clamped to dims)
    bn: int


def find_prunable(params: Params, sasp: SASPConfig,
                  is_prunable: Callable[[Tuple], bool]) -> List[PrunableLeaf]:
    leaves = []
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        if not hasattr(leaf, "ndim") or leaf.ndim < 2:
            continue
        if not is_prunable(path):
            continue
        K, N = leaf.shape[-2], leaf.shape[-1]
        bk, bn = effective_blocks((K, N), sasp.block_k, sasp.block_n)
        if K % bk or N % bn:
            continue
        leaves.append(PrunableLeaf(path, leaf.shape, bk, bn))
    return leaves


def default_ffn_predicate(path: Tuple) -> bool:
    """Paper scope: feed-forward GEMMs only (attention is brittle)."""
    keys = "/".join(str(getattr(k, "key", k)) for k in path)
    return ("ffn" in keys or "moe" in keys) and keys.endswith("/w")


def all_gemm_predicate(path: Tuple) -> bool:
    keys = "/".join(str(getattr(k, "key", k)) for k in path)
    if "emb" in keys or "norm" in keys or "router" in keys:
        return False
    return keys.endswith("/w") or any(
        keys.endswith(s) for s in ("wq/w", "wk/w", "wv/w", "wo/w"))


def scope_predicate(sasp: SASPConfig) -> Callable[[Tuple], bool]:
    return default_ffn_predicate if sasp.scope == "ffn" else \
        all_gemm_predicate


def compute_sasp_masks(params: Params, sasp: SASPConfig,
                       is_prunable: Optional[Callable] = None
                       ) -> Dict[Tuple, jnp.ndarray]:
    """Global-L1 tile selection. Returns {tree-path: bool mask (..., KB, NB)}
    with exactly ``floor(sparsity × total_tiles)`` tiles zeroed model-wide
    (ties broken by flat order, deterministic)."""
    pred = is_prunable or scope_predicate(sasp)
    leaves = find_prunable(params, sasp, pred)
    if not leaves:
        return {}
    flat = dict(jax.tree_util.tree_flatten_with_path(params)[0])

    scores, sizes = [], []
    for leaf in leaves:
        w = flat[leaf.path]
        s = tile_l1(w, leaf.bk, leaf.bn)
        scores.append(s.reshape(-1))
        sizes.append(s.size)
    all_scores = jnp.concatenate(scores)
    total = all_scores.size
    n_prune = int(np.floor(sasp.sparsity * total))

    if n_prune == 0:
        keep_flat = jnp.ones((total,), dtype=bool)
    else:
        # threshold = n_prune-th smallest score; prune strictly-below plus
        # enough ties to hit the budget exactly (deterministic by index).
        order = jnp.argsort(all_scores, stable=True)
        keep_flat = jnp.ones((total,), dtype=bool).at[order[:n_prune]].set(
            False)

    masks: Dict[Tuple, jnp.ndarray] = {}
    off = 0
    for leaf, s, size in zip(leaves, scores, sizes):
        m = keep_flat[off:off + size]
        off += size
        w = flat[leaf.path]
        KB = w.shape[-2] // leaf.bk
        NB = w.shape[-1] // leaf.bn
        masks[leaf.path] = m.reshape(*w.shape[:-2], KB, NB)
    return masks


def prune_params(params: Params, sasp: SASPConfig,
                 is_prunable: Optional[Callable] = None
                 ) -> Tuple[Params, Dict[Tuple, jnp.ndarray]]:
    """Zero pruned tiles in-place (masked-dense path) and return the masks.
    Masks are also what the BSR/kernel paths compile from."""
    masks = compute_sasp_masks(params, sasp, is_prunable)
    if not masks:
        return params, masks

    def maybe_prune(path, leaf):
        if path in masks:
            return apply_block_mask(leaf, masks[path].astype(leaf.dtype)
                                    .astype(bool))
        return leaf

    pruned = jax.tree_util.tree_map_with_path(maybe_prune, params)
    return pruned, masks


def mask_sparsity(masks: Dict[Tuple, jnp.ndarray]) -> float:
    total = sum(int(np.prod(m.shape)) for m in masks.values())
    kept = sum(int(jnp.sum(m)) for m in masks.values())
    return 1.0 - kept / max(total, 1)


def per_matrix_sparsity(masks: Dict[Tuple, jnp.ndarray]
                        ) -> Dict[str, float]:
    """Heterogeneous per-weight pruning rates (paper Fig 8 evidence)."""
    out = {}
    for path, m in masks.items():
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        out[name] = 1.0 - float(jnp.mean(m.astype(jnp.float32)))
    return out


# ---------------------------------------------------------------------------
# Pruning schedule (gradual magnitude pruning for train-time SASP)
# ---------------------------------------------------------------------------


def cubic_sparsity_schedule(step: int, *, start_step: int, end_step: int,
                            final_sparsity: float) -> float:
    """Zhu & Gupta cubic ramp: s(t) = s_f (1 - (1 - t)^3)."""
    if step <= start_step:
        return 0.0
    if step >= end_step:
        return final_sparsity
    t = (step - start_step) / max(1, end_step - start_step)
    return final_sparsity * (1.0 - (1.0 - t) ** 3)
