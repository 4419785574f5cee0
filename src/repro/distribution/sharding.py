"""Sharding rules: path-pattern → PartitionSpec.

Mesh axes (launch/mesh.py): ``pod`` (slow inter-pod links), ``data``
(DP; also EP for MoE experts and SP for long-context KV), ``model`` (TP).

Rules operate on jax key-paths of the param pytree. Stacked leading dims
(the scan-over-layers ``repeat`` dim; the MoE expert dim) are detected from
rank and padded with None / mapped to EP. Dims that do not divide the axis
size fall back to replication (never silently uneven — see `_fits`).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.sparse import PackedFFN, PackedSASPWeight


def dp_axes(mesh: Mesh, profile: str = "tp") -> Tuple[str, ...]:
    if profile == "dp_only":
        return tuple(mesh.axis_names)
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_submeshes(mesh: Mesh, profile: str = "tp"):
    """One submesh per DP rank — the per-rank placement for the sharded
    scheduler (``serve/scheduler.py``, DESIGN.md §11). Each submesh
    keeps every axis name but collapses the DP axes ('pod'/'data'; ALL
    axes under the dp_only profile) to size 1, so rank r's engine shard
    puts its params, KV-cache slots, and decode state on exactly its
    slice of devices while the 'model' axis — and with it the TP
    shard_map packed drivers — keeps working inside the rank."""
    import itertools

    names = mesh.axis_names
    dp = dp_axes(mesh, profile)
    dims = [i for i, a in enumerate(names) if a in dp]
    if not dims or all(mesh.shape[a] == 1 for a in dp):
        return [mesh]
    subs = []
    for idx in itertools.product(*(range(mesh.devices.shape[d])
                                   for d in dims)):
        slicer = [slice(None)] * mesh.devices.ndim
        for d, i in zip(dims, idx):
            slicer[d] = slice(i, i + 1)
        subs.append(Mesh(mesh.devices[tuple(slicer)], names))
    return subs


def prefill_bucket_table(cache_len: int, n_buckets: int = 4,
                         min_len: int = 16) -> Tuple[int, ...]:
    """Prefill-length buckets for the jitted admission (DESIGN.md §12):
    geometric halving down from ``cache_len`` so the longest bucket
    always covers every cacheable prompt. Padding a prompt of length L
    to the smallest bucket ≥ L bounds the admission jit cache at
    O(n_buckets) programs (vs one per distinct padded length) at the
    cost of ≤ 2× extra masked prefill columns."""
    out = []
    b = int(cache_len)
    while len(out) < n_buckets and b >= min_len:
        out.append(b)
        b //= 2
    return tuple(sorted(out)) if out else (int(cache_len),)


def rank_bucket_tables(ranks: int, cache_len: int, n_buckets: int = 4,
                       min_len: int = 16) -> Tuple[Tuple[int, ...], ...]:
    """One bucket table per DP-rank engine shard (``serve/scheduler.py``
    pairs these with ``dp_submeshes``). Every rank gets the same table —
    a request must compile the same admission program no matter which
    rank serves it, so re-routing (failover, load) never pays a fresh
    compile — but the table rides per-rank so a heterogeneous-rank
    policy has one place to diverge."""
    table = prefill_bucket_table(cache_len, n_buckets, min_len)
    return tuple(table for _ in range(ranks))


def axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        n = 1
        for a in name:
            n *= axis_size(mesh, a)
        return n
    return mesh.shape[name] if name in mesh.axis_names else 1


def _fits(dim: int, mesh: Mesh, axis) -> bool:
    return axis is not None and dim % axis_size(mesh, axis) == 0


def _maybe(dim: int, mesh: Mesh, axis):
    """axis if it divides dim, else None (replicate)."""
    return axis if _fits(dim, mesh, axis) else None


def path_str(path: Tuple) -> str:
    return "/".join(
        str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))
        for k in path)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# (regex on path, spec builder fn(shape, mesh) -> P). First match wins.
def param_rules(cfg: ModelConfig):
    def col(shape, mesh):     # (..., d_in, d_out) -> shard d_out on model
        lead = (None,) * (len(shape) - 2)
        return P(*lead, None, _maybe(shape[-1], mesh, "model"))

    def row(shape, mesh):     # (..., d_in, d_out) -> shard d_in on model
        lead = (None,) * (len(shape) - 2)
        return P(*lead, _maybe(shape[-2], mesh, "model"), None)

    def vocab(shape, mesh):   # (V, d) embedding
        return P(_maybe(shape[-2], mesh, "model"), None)

    def expert_col(shape, mesh):   # (..., E, d_in, d_out)
        lead = (None,) * (len(shape) - 3)
        return P(*lead, _maybe(shape[-3], mesh, "data"), None,
                 _maybe(shape[-1], mesh, "model"))

    def expert_row(shape, mesh):
        lead = (None,) * (len(shape) - 3)
        return P(*lead, _maybe(shape[-3], mesh, "data"),
                 _maybe(shape[-2], mesh, "model"), None)

    def conv(shape, mesh):         # (..., K, conv_dim) depthwise taps
        lead = (None,) * (len(shape) - 2)
        return P(*lead, None, _maybe(shape[-1], mesh, "model"))

    def vec_model(shape, mesh):    # (..., conv_dim)-like per-channel vec
        lead = (None,) * (len(shape) - 1)
        return P(*lead, _maybe(shape[-1], mesh, "model"))

    def repl(shape, mesh):
        return P()

    def bsr_vals(shape, mesh):     # (L, k_max, NB, bk, bn)
        lead = (None,) * (len(shape) - 4)
        return P(*lead, None, _maybe(shape[-3], mesh, "model"), None,
                 None)

    def bsr_idx(shape, mesh):      # (L, k_max, NB) / scale same
        lead = (None,) * (len(shape) - 2)
        return P(*lead, None, _maybe(shape[-1], mesh, "model"))

    return [
        (r"sasp_bsr/w\d/vals$", bsr_vals),
        (r"sasp_bsr/w\d/(idx|scale)$", bsr_idx),
        (r"sasp_bsr/", repl),
        (r"(embed|lm_head)/emb$", vocab),
        # attention
        (r"mixer/(wq|wk|wv)/w$", col),
        (r"mixer/(wq|wk|wv)/b$", vec_model),
        (r"mixer/wo/w$", row),
        (r"mixer/(q_norm|k_norm)$", repl),
        # MoE experts (E-leading stacks) — EP on data, TP on model
        (r"ffn/w1/w$", expert_col if cfg.moe else col),
        (r"ffn/w3/w$", expert_col if cfg.moe else col),
        (r"ffn/w2/w$", expert_row if cfg.moe else row),
        (r"ffn/router/w$", repl),
        (r"ffn/shared/w(1|3)/w$", col),
        (r"ffn/shared/w2/w$", row),
        # mamba
        (r"mixer/(in_z|in_xbc)/w$", col),
        (r"mixer/in_dt/w$", col),
        (r"mixer/conv_w$", conv),
        (r"mixer/conv_b$", vec_model),
        (r"mixer/norm$", vec_model),
        (r"mixer/out_proj/w$", row),
        (r"mixer/(A_log|D|dt_bias)$", repl),
        # norms / everything else
        (r".*", repl),
    ]


def spec_for_param(cfg: ModelConfig, path: Tuple, shape: Tuple[int, ...],
                   mesh: Mesh) -> P:
    # jamba dense-FFN slots inside a MoE config have 2-D ffn mats: treat
    # per-rank, not per-config: a (…, d, f) under ffn/w1 with rank-2 core.
    s = path_str(path)
    for pat, fn in param_rules(cfg):
        if re.search(pat, s):
            spec = fn(shape, mesh)
            # rank-correct: pattern fns assume canonical rank; a MoE rule
            # applied to a dense 2-D slot falls back to col/row semantics.
            if len(spec) != len(shape):
                spec = _rerank(spec, shape)
            return spec
    return P()


def _rerank(spec: P, shape: Tuple[int, ...]) -> P:
    names = [a for a in spec if a is not None]
    n = len(shape)
    if not names:
        return P(*(None,) * n)
    # keep trailing alignment
    tail = list(spec)[-n:] if len(spec) > n else list(spec)
    while len(tail) < n:
        tail.insert(0, None)
    return P(*tail)


# ---------------------------------------------------------------------------
# Packed deployment containers (core.deploy, DESIGN.md §10)
# ---------------------------------------------------------------------------


def axis_at(rank: int, from_end: int, axis) -> P:
    """P with ``axis`` at position rank-from_end, None elsewhere — the
    one place that encodes 'the shard axis sits from_end dims before the
    trailing visit dims' for packed containers (also used by the
    shard_map drivers in models/ffn.py)."""
    spec = [None] * rank
    spec[rank - from_end] = axis
    return P(*spec)


def packed_sharding(node, mesh: Mesh):
    """Sharding pytree (same container type, NamedSharding leaves) for a
    TP-sharded PackedSASPWeight / PackedFFN: the shard axis maps onto the
    mesh 'model' axis so each TP rank holds exactly its shard-local visit
    list. Containers whose ``shards`` does not match the mesh replicate
    (the drivers fall back to a per-shard loop there)."""
    repl = NamedSharding(mesh, P())
    t = node.shards
    if t <= 1 or axis_size(mesh, "model") != t:
        return jax.tree.map(lambda _: repl, node)

    def at(arr, from_end):
        if arr is None:
            return None
        return NamedSharding(mesh, axis_at(arr.ndim, from_end, "model"))

    if isinstance(node, PackedSASPWeight):
        return PackedSASPWeight(
            vals=at(node.vals, 4),          # (…, tp, nnz, bk, bn)
            kn=at(node.kn, 3),              # (…, tp, 2, nnz)
            shape=node.shape, block=node.block,
            scale=at(node.scale, 2),        # (…, tp, nnz)
            bias=(at(node.bias, 2)          # col: (…, tp, N/tp)
                  if node.shard_kind == "col" else
                  None if node.bias is None else repl),  # row: whole (…, N)
            act=node.act, shards=node.shards,
            shard_kind=node.shard_kind)
    assert isinstance(node, PackedFFN), type(node)
    return PackedFFN(
        w1v=at(node.w1v, 4), w3v=at(node.w3v, 4),   # (…, tp, nv, d|bf, …)
        w2v=at(node.w2v, 4),
        b1=at(node.b1, 3), b3=at(node.b3, 3),       # (…, tp, nv, bf)
        b2=None if node.b2 is None else repl,       # whole (…, d)
        d_model=node.d_model, d_ff=node.d_ff, block_f=node.block_f,
        act=node.act, s1=at(node.s1, 2), s3=at(node.s3, 2),
        s2=at(node.s2, 2), shards=node.shards, jv=at(node.jv, 2))


_PACKED_TYPES = (PackedSASPWeight, PackedFFN)


def param_shardings(cfg: ModelConfig, params_shape, mesh: Mesh,
                    profile: str = "tp"):
    """Map a params eval_shape pytree -> NamedSharding pytree.
    profile='dp_only': replicate everything (pure data parallelism —
    the small-model profile; see EXPERIMENTS.md §Perf C). Packed
    deployment containers (``sasp_packed`` / ``sasp_fused``) are handled
    whole by :func:`packed_sharding` — their shard axis carries the
    shard-local visit lists onto 'model'."""
    if profile == "dp_only":
        return jax.tree.map(lambda _: NamedSharding(mesh, P()),
                            params_shape)

    def fn(path, leaf):
        if isinstance(leaf, _PACKED_TYPES):
            return packed_sharding(leaf, mesh)
        spec = spec_for_param(cfg, path, leaf.shape, mesh)
        # drop axes that don't divide (safety)
        fixed = []
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * (
                len(leaf.shape) - len(spec))):
            fixed.append(ax if _fits(dim, mesh, ax) else None)
        return NamedSharding(mesh, P(*fixed))

    return jax.tree_util.tree_map_with_path(
        fn, params_shape,
        is_leaf=lambda x: isinstance(x, _PACKED_TYPES))


# ---------------------------------------------------------------------------
# Activation / batch / cache rules
# ---------------------------------------------------------------------------


def batch_spec(mesh: Mesh, batch: int) -> P:
    dp = dp_axes(mesh)
    if batch % axis_size(mesh, dp) == 0:
        return P(dp, None)
    return P(None, None)


def data_shardings(mesh: Mesh, batch: int, with_embeds: bool,
                   d_model: int = 0):
    dp = dp_axes(mesh)
    ok = batch % axis_size(mesh, dp) == 0
    tok = NamedSharding(mesh, P(dp, None) if ok else P())
    out = {"tokens": tok}
    if with_embeds:
        out["embeds"] = NamedSharding(
            mesh, P(dp, None, None) if ok else P())
    return out


def cache_shardings(cfg: ModelConfig, mesh: Mesh, batch: int,
                    caches_shape):
    """KV ring caches: batch over DP when it divides, else capacity over
    (data×model) — the sequence-parallel long-context layout. SSM states:
    heads over model."""
    dp = dp_axes(mesh)
    big_batch = batch % axis_size(mesh, dp) == 0 and batch > 1

    def fn(path, leaf):
        s = path_str(path)
        shape = leaf.shape
        if "conv" in s:                       # (R, B, K-1, conv_dim)
            spec = [None] * len(shape)
            if big_batch:
                spec[1] = dp
            if _fits(shape[-1], mesh, "model"):
                spec[-1] = "model"
            return NamedSharding(mesh, P(*spec))
        if "state" in s:                      # (R, B, H, P, N)
            spec = [None] * len(shape)
            if big_batch:
                spec[1] = dp
            if _fits(shape[2], mesh, "model"):
                spec[2] = "model"
            return NamedSharding(mesh, P(*spec))
        # KVCache fields: k/v (R, B, C, KH, D); pos (R, B, C)
        spec = [None] * len(shape)
        if big_batch:
            spec[1] = dp
            if _fits(shape[2], mesh, "model"):
                spec[2] = "model"
        else:
            seq_axes = ("data", "model") if _fits(
                shape[2], mesh, ("data", "model")) else (
                "data",) if _fits(shape[2], mesh, "data") else None
            if seq_axes:
                spec[2] = seq_axes
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(fn, caches_shape)


def pool_shardings(cfg: ModelConfig, mesh: Mesh, pool_shape):
    """Paged KV page-pool placement (serve/memory.py, DESIGN.md §13).

    Pool leaves are (R, P, page_len, …): the physical page dim P shards
    over the DP axes when it divides them — each DP rank's engine owns
    its OWN pool, so on a scheduler rank's submesh (DP collapsed to 1)
    this degrades to replication and only the trailing dims shard — and
    KV heads (axis 3 of k/v/scale leaves) shard over 'model' when they
    divide it, matching the contiguous cache layout so the TP SDPA path
    sees the same head placement with paging on or off."""
    dp = dp_axes(mesh)

    def fn(path, leaf):
        shape = leaf.shape
        spec = [None] * len(shape)
        if axis_size(mesh, dp) > 1 and _fits(shape[1], mesh, dp):
            spec[1] = dp
        if len(shape) >= 4 and _fits(shape[3], mesh, "model"):
            spec[3] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(fn, pool_shape)
