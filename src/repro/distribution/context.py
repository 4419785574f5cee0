"""Active-mesh context: lets model code place sharding constraints and
select distributed implementations (EP MoE, SP attention) without
threading the mesh through every call signature. No mesh set → every
helper is a no-op and models run single-process (smoke tests, QoS tier).

Also home of :func:`shard_map`, the one entry to ``jax.shard_map``
(DESIGN.md §10): every call site routes through it, which the static
analyzer's shim pass enforces.

How the packed containers engage (DESIGN.md §9–§10 — format spec in
``core/sparse.py``): a TP-sharded ``PackedSASPWeight`` / ``PackedFFN``
carries one shard-LOCAL visit list per rank (an extra shard axis right
before the visit dims, every (layer × shard) list padded to one shared
static nnz via dup-last-visit). The drivers in ``models/ffn.py`` /
``models/attention.py`` check ``active_mesh()`` at trace time: when
the mesh's 'model' axis size equals the container's ``shards``, they
wrap the kernel in :func:`shard_map` with the shard axis mapped onto
'model', so each rank DMAs and visits only its own blocks —
``shard_kind="col"`` outputs concatenate in place, ``"row"``/fused
partials reduce (psum or rs+int8-ag). No mesh (or a mismatched one) →
a sequential per-shard loop reproduces the same math on one device.
This is why serving code never threads the mesh through call
signatures: ``Engine``/``ShardedScheduler`` enter ``use_mesh`` (each
scheduler rank its own submesh) and the same model code routes
itself.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, *, mesh: Mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with ``check_vma=check``. The repo's bodies use
    untracked collectives (psum_scatter epilogues, all_to_all dispatch),
    so they pass False everywhere."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


_ACTIVE_MESH: Optional[Mesh] = None
# 'tp' (default): weights TP-sharded over 'model'. 'dp_only': weights
# replicated, batch sharded over EVERY mesh axis — the right profile for
# small models where TP collectives dominate (EXPERIMENTS.md §Perf C).
_PROFILE: str = "tp"


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


def sharding_profile() -> str:
    return _PROFILE


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], profile: str = "tp"):
    global _ACTIVE_MESH, _PROFILE
    prev, prev_p = _ACTIVE_MESH, _PROFILE
    _ACTIVE_MESH = mesh
    _PROFILE = profile
    try:
        yield mesh
    finally:
        _ACTIVE_MESH = prev
        _PROFILE = prev_p


def dp_axes() -> Tuple[str, ...]:
    if _ACTIVE_MESH is None:
        return ()
    if _PROFILE == "dp_only":
        return tuple(_ACTIVE_MESH.axis_names)
    return tuple(a for a in _ACTIVE_MESH.axis_names
                 if a in ("pod", "data"))


def axis_size(name) -> int:
    if _ACTIVE_MESH is None:
        return 1
    if isinstance(name, tuple):
        n = 1
        for a in name:
            n *= axis_size(a)
        return n
    return _ACTIVE_MESH.shape.get(name, 1)


def batch_axes(m: int) -> Optional[Tuple[str, ...]]:
    """DP axes safe for the batch dim of a shard_map whose WEIGHTS
    shard over 'model'. Excludes 'model' (under the dp_only profile
    ``dp_axes()`` folds every axis in, and splitting the batch over the
    axis that carries the weight shards makes the cross-shard psum mix
    DIFFERENT batch rows — silently wrong) and requires divisibility.
    Returns None when the batch should stay unsharded."""
    dp = tuple(a for a in dp_axes() if a != "model")
    if dp and m % axis_size(dp) == 0 and m > 1:
        return dp
    return None


def maybe_shard(x, *spec):
    """with_sharding_constraint if a mesh is active and every named dim
    divides; otherwise identity."""
    mesh = _ACTIVE_MESH
    if mesh is None:
        return x
    fixed = []
    used = set()
    for i, ax in enumerate(spec):
        if ax is None:
            fixed.append(None)
            continue
        names = ax if isinstance(ax, tuple) else (ax,)
        if used & set(names):          # axis already consumed (dp_only)
            fixed.append(None)
            continue
        size = axis_size(ax)
        if i < x.ndim and size > 1 and x.shape[i] % size == 0:
            fixed.append(ax)
            used |= set(names)
        else:
            fixed.append(None)
    fixed += [None] * (x.ndim - len(fixed))
    if not any(a is not None for a in fixed):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*fixed)))


def shard_batch(x):
    """Shard dim 0 (batch) over the DP axes."""
    dp = dp_axes()
    if not dp:
        return x
    return maybe_shard(x, dp, *([None] * (x.ndim - 1)))
