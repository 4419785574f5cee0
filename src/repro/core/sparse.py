"""Weight containers for the SASP "skip" paths — and the reference for
the packed-container FORMAT (DESIGN.md §9–§10), so the layout is
discoverable without reading kernel code.

All containers are built offline from a concrete pruning mask (masks
are static by deployment time — pruning happens before the serving
graph is jitted), so every shape below is static.

**BlockSparseWeight (BSR-style, training/reference paths).** Per
output-column-block list of surviving K-blocks, padded to the
per-matrix max (`k_max`). Padding entries point at block 0 with zero
values, so no masking is needed in the inner loop. Consumers: the
pure-jnp gathered matmul (`bsr_matmul`) — FLOPs/bytes drop ∝ sparsity
*inside the compiled HLO*, which is how the dry-run roofline exhibits
the paper's saving without hardware — and the Pallas tile-skip kernel
path, which re-flattens it per call (why serving uses packed instead).

**Visit lists (the packed format's core idea).** A "visit" is one
surviving weight block the kernel will touch, in a fixed precomputed
order. `PackedSASPWeight` stores visits sorted by (n, k): all visits
of output-column block n are consecutive, so the kernel keeps one
VMEM-resident accumulator per output block and flushes it exactly once
(bias + activation fold into that flush). Every output column gets at
least one visit — a column with no surviving block carries one
zero-valued visit so its accumulator still initializes and flushes
`act(bias)`. `PackedFFN` visits are whole d_ff column-blocks of the
gated FFN (w1/w3 columns + the matching w2 row + bias slices), ordered
by d_ff block index; `jv` records that index per visit.

**Dup-last-visit nnz padding.** Containers stack per layer (the
`lax.scan`-over-layers layout) and per TP shard, which forces ONE
static visit count across all (layer × shard) lists. Shorter lists are
padded by REPEATING the last visit's coordinates with zero-valued
blocks: the appended visits share the final n-block, so the visit
order stays n-major, the accumulator neither re-initializes nor
flushes early — it just adds zeros and flushes the same value once
more. (`PackedFFN` pads with zero-w2v visits, `jv = -1`: a zero down-
projection contributes exactly nothing.) Padding visits are
recognizable as all-zero blocks / `jv < 0`, which is what the elastic
re-deploy fast path (`core.deploy.reshard_packed`) keys on.

**Shard kinds (TP partitioning of the visit schedule, DESIGN.md §10).**
`shard_kind="col"` splits visits by output-column block: each shard's
kn n-coordinates are shard-LOCAL, bias is reshaped per shard and stays
fused, outputs concatenate. `shard_kind="row"` splits by input-row
block (down-projections whose input is already column-sharded): kn
k-coordinates are shard-local, outputs are PARTIAL and need a
cross-shard reduction, so bias stays whole and is added after it — and
a row shard never carries `act` (a nonlinear epilogue on a partial sum
would be wrong). `PackedFFN` shards the d_ff visit schedule
contiguously (always row-like: partials + one post-reduction b2).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class BlockSparseWeight:
    """vals: (k_max, NB, bk, bn) blocks (padded); idx: (k_max, NB) int32
    source K-block index; shape/block are static aux data. Optional int8:
    vals int8 + scale (k_max, NB) fp32."""

    def __init__(self, vals, idx, shape: Tuple[int, int],
                 block: Tuple[int, int], scale=None):
        self.vals = vals
        self.idx = idx
        self.shape = tuple(shape)
        self.block = tuple(block)
        self.scale = scale

    def tree_flatten(self):
        return (self.vals, self.idx, self.scale), (self.shape, self.block)

    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        return ((ga("vals"), self.vals), (ga("idx"), self.idx),
                (ga("scale"), self.scale)), (self.shape, self.block)

    @classmethod
    def tree_unflatten(cls, aux, children):
        vals, idx, scale = children
        shape, block = aux
        return cls(vals, idx, shape, block, scale)

    def __repr__(self):
        return (f"BlockSparseWeight(shape={self.shape}, "
                f"block={self.block}, k_max={self.k_max})")

    @property
    def k_max(self) -> int:
        return self.vals.shape[0]

    @property
    def density(self) -> float:
        K, N = self.shape
        bk, bn = self.block
        return self.k_max / (K // bk)   # upper bound incl. padding

    def nbytes(self) -> int:
        b = self.vals.size * self.vals.dtype.itemsize + self.idx.size * 4
        if self.scale is not None:
            b += self.scale.size * 4
        return b


jax.tree_util.register_pytree_with_keys(
    BlockSparseWeight,
    lambda b: b.tree_flatten_with_keys(),
    lambda aux, ch: BlockSparseWeight.tree_unflatten(aux, ch),
    flatten_func=lambda b: b.tree_flatten(),
)


class PackedSASPWeight:
    """Serving-time container: the COMPACT sorted block list the Pallas
    tile-skip kernel consumes directly (DESIGN.md §9), built once at load
    time by ``core.deploy``. Unlike :class:`BlockSparseWeight`, whose
    trace-compatible flattening re-emits the padded k_max × NB visit list
    on every call, this pytree stores the final (nnz, bk, bn) values +
    (2, nnz) coordinates — zero per-call repacking.

    vals: (…, nnz, bk, bn) surviving blocks (fp32/bf16, or int8 with
    ``scale``); kn: (…, 2, nnz) int32 visit coordinates sorted by (n, k);
    scale: optional (…, nnz) fp32 per-block dequant scales; bias:
    optional (…, N) fused into the kernel's flush epilogue. A leading
    layer axis (…) makes the container sliceable under ``lax.scan`` —
    per-layer packs are padded to one shared static nnz by
    ``kernels.sasp_gemm.ops.pad_block_list``.

    Static aux: shape (K, N), block (bk, bn), act (epilogue activation,
    folded into the last-visit flush; None = identity).

    TP sharding (DESIGN.md §10): ``shards > 1`` means every array carries
    an extra shard axis right before the visit dims — vals
    (…, tp, nnz_s, bk, bn), kn (…, tp, 2, nnz_s) — holding one
    shard-LOCAL visit list per TP rank. ``shard_kind`` says how the block
    list was partitioned: ``"col"`` by output-column block (kn n-coords
    are shard-local; bias reshaped to (…, tp, N/tp) and still fused),
    ``"row"`` by input-row block (kn k-coords shard-local; partial
    outputs need a cross-shard reduction, so bias stays (…, N) and is
    added after it). Per-shard lists are padded to one shared static
    nnz_s with the same dup-last-visit trick as the layer stacking.
    """

    def __init__(self, vals, kn, shape: Tuple[int, int],
                 block: Tuple[int, int], scale=None, bias=None,
                 act: Optional[str] = None, shards: int = 1,
                 shard_kind: Optional[str] = None):
        self.vals = vals
        self.kn = kn
        self.shape = tuple(shape)
        self.block = tuple(block)
        self.scale = scale
        self.bias = bias
        self.act = act
        self.shards = shards
        self.shard_kind = shard_kind

    def tree_flatten(self):
        return ((self.vals, self.kn, self.scale, self.bias),
                (self.shape, self.block, self.act, self.shards,
                 self.shard_kind))

    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        return ((ga("vals"), self.vals), (ga("kn"), self.kn),
                (ga("scale"), self.scale), (ga("bias"), self.bias)), \
            (self.shape, self.block, self.act, self.shards,
             self.shard_kind)

    @classmethod
    def tree_unflatten(cls, aux, children):
        vals, kn, scale, bias = children
        shape, block, act, shards, shard_kind = aux
        return cls(vals, kn, shape, block, scale, bias, act, shards,
                   shard_kind)

    @property
    def nnz(self) -> int:
        return self.vals.shape[-3]

    def nbytes(self) -> int:
        b = self.vals.size * self.vals.dtype.itemsize + self.kn.size * 4
        if self.scale is not None:
            b += self.scale.size * 4
        if self.bias is not None:
            b += self.bias.size * 4
        return b

    def __repr__(self):
        sh = f", shards={self.shards}:{self.shard_kind}" \
            if self.shards > 1 else ""
        return (f"PackedSASPWeight(shape={self.shape}, "
                f"block={self.block}, nnz={self.nnz}, act={self.act}"
                f"{sh})")


jax.tree_util.register_pytree_with_keys(
    PackedSASPWeight,
    lambda p: p.tree_flatten_with_keys(),
    lambda aux, ch: PackedSASPWeight.tree_unflatten(aux, ch),
    flatten_func=lambda p: p.tree_flatten(),
)


class PackedFFN:
    """Whole-FFN deployment container for the fused gated-FFN kernel:
    surviving d_ff column-blocks of w1/w3 + matching w2 row-blocks +
    per-visit bias slices, one visit schedule, zero HBM intermediate.

    w1v/w3v: (…, nv, d, bf); w2v: (…, nv, bf, d); b1/b3: (…, nv, bf);
    b2: (…, d); s1/s3/s2: optional (…, nv) int8 scales. A leading layer
    axis stacks the layers (per-layer packs padded to one shared nv with
    zero-w2v visits); the layer scan does not slice an unsharded stack
    but has the kernel read it in place by layer index
    (``models.lm._scan_layers``). Static aux: d_model, d_ff, block_f,
    act.

    TP sharding (DESIGN.md §10): ``shards > 1`` adds a shard axis before
    the visit dims — w1v (…, tp, nv_s, d, bf) — partitioning the d_ff
    visit schedule contiguously by d_ff column-block shard. Each shard's
    w2 down-projection yields a PARTIAL (M, d); drivers reduce across
    shards (psum or reduce-scatter + int8 all-gather). b2 stays (…, d)
    and is added once, after the reduction.

    ``jv`` (…, nv) int32 records each visit's GLOBAL d_ff block index
    (-1 for padding/empty-shard visits). The kernels never read it — it
    exists so the container is self-describing: the elastic re-deploy
    fast path (``core.deploy.reshard_packed``) re-partitions the visit
    schedule for a new mesh shape by slicing on ``jv`` instead of
    rebuilding from the dense weights.
    """

    def __init__(self, w1v, w3v, w2v, b1, b3, b2, d_model: int,
                 d_ff: int, block_f: int, act: str, s1=None, s3=None,
                 s2=None, shards: int = 1, jv=None):
        self.w1v, self.w3v, self.w2v = w1v, w3v, w2v
        self.b1, self.b3, self.b2 = b1, b3, b2
        self.s1, self.s3, self.s2 = s1, s3, s2
        self.jv = jv
        self.d_model = d_model
        self.d_ff = d_ff
        self.block_f = block_f
        self.act = act
        self.shards = shards

    def tree_flatten(self):
        return ((self.w1v, self.w3v, self.w2v, self.b1, self.b3, self.b2,
                 self.s1, self.s3, self.s2, self.jv),
                (self.d_model, self.d_ff, self.block_f, self.act,
                 self.shards))

    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        names = ("w1v", "w3v", "w2v", "b1", "b3", "b2", "s1", "s3",
                 "s2", "jv")
        return tuple((ga(n), getattr(self, n)) for n in names), \
            (self.d_model, self.d_ff, self.block_f, self.act,
             self.shards)

    @classmethod
    def tree_unflatten(cls, aux, children):
        w1v, w3v, w2v, b1, b3, b2, s1, s3, s2, jv = children
        d_model, d_ff, block_f, act, shards = aux
        return cls(w1v, w3v, w2v, b1, b3, b2, d_model, d_ff, block_f,
                   act, s1, s3, s2, shards, jv)

    @property
    def nv(self) -> int:
        return self.w1v.shape[-3]

    def __repr__(self):
        sh = f", shards={self.shards}" if self.shards > 1 else ""
        return (f"PackedFFN(d={self.d_model}, d_ff={self.d_ff}, "
                f"bf={self.block_f}, nv={self.nv}, act={self.act!r}"
                f"{sh})")


jax.tree_util.register_pytree_with_keys(
    PackedFFN,
    lambda p: p.tree_flatten_with_keys(),
    lambda aux, ch: PackedFFN.tree_unflatten(aux, ch),
    flatten_func=lambda p: p.tree_flatten(),
)


def bsr_from_mask(w: np.ndarray, mask: np.ndarray, bk: int, bn: int,
                  *, quantize: bool = False,
                  k_max: Optional[int] = None) -> BlockSparseWeight:
    """w: (K, N); mask: (KB, NB) bool (True = keep). Offline (numpy).
    ``k_max`` forces the padded depth (stacked per-layer BSRs must share
    one k_max so ``lax.scan`` can slice them)."""
    K, N = w.shape
    KB, NB = K // bk, N // bn
    assert mask.shape == (KB, NB), (mask.shape, (KB, NB))
    w = np.asarray(w, dtype=np.float32)
    mask = np.asarray(mask, dtype=bool)

    counts = mask.sum(axis=0)                       # kept K-blocks per col
    needed = int(counts.max()) if counts.size else 0
    k_max = max(needed, 1) if k_max is None else k_max
    assert k_max >= needed, (k_max, needed)

    vals = np.zeros((k_max, NB, bk, bn), dtype=np.float32)
    idx = np.zeros((k_max, NB), dtype=np.int32)
    wb = w.reshape(KB, bk, NB, bn)
    for n in range(NB):
        kept = np.nonzero(mask[:, n])[0]
        for j, kb in enumerate(kept):
            vals[j, n] = wb[kb, :, n, :]
            idx[j, n] = kb

    scale = None
    if quantize:
        amax = np.abs(vals).max(axis=(2, 3))        # (k_max, NB)
        scale = np.maximum(amax, 1e-12) / 127.0
        vals = np.clip(np.round(vals / scale[:, :, None, None]),
                       -127, 127).astype(np.int8)

    return BlockSparseWeight(
        vals=jnp.asarray(vals), idx=jnp.asarray(idx), shape=(K, N),
        block=(bk, bn),
        scale=None if scale is None else jnp.asarray(scale),
    )


def stack_bsr(bsrs) -> BlockSparseWeight:
    """Stack per-layer BSRs (same shape/block/k_max) along a new leading
    axis — the scan-over-layers layout."""
    b0 = bsrs[0]
    return BlockSparseWeight(
        vals=jnp.stack([b.vals for b in bsrs]),
        idx=jnp.stack([b.idx for b in bsrs]),
        shape=b0.shape, block=b0.block,
        scale=None if b0.scale is None else
        jnp.stack([b.scale for b in bsrs]),
    )


def flat_block_list(mask: np.ndarray) -> np.ndarray:
    """(nnz, 2) [k_block, n_block] pairs sorted by (n, k) — the visit order
    of the Pallas tile-skip kernel (accumulator re-inits when n changes)."""
    mask = np.asarray(mask, dtype=bool)
    ks, ns = np.nonzero(mask)
    order = np.lexsort((ks, ns))
    return np.stack([ks[order], ns[order]], axis=1).astype(np.int32)


def bsr_matmul(x: jnp.ndarray, w: BlockSparseWeight,
               *, compute_dtype=None) -> jnp.ndarray:
    """x: (M, K) @ block-sparse (K, N) -> (M, N), skipping pruned tiles.

    scan over k_max steps; each step gathers one K-block of x per output
    column-block and does a batched (M, bk) @ (bk, bn) — total FLOPs
    = 2·M·bk·bn·NB·k_max, i.e. dense FLOPs × (k_max / KB).
    """
    K, N = w.shape
    bk, bn = w.block
    KB, NB = K // bk, N // bn
    M = x.shape[0]
    dt = compute_dtype or x.dtype
    xb = jnp.moveaxis(x.reshape(M, KB, bk), 1, 0).astype(dt)   # (KB, M, bk)

    vals = w.vals
    if w.scale is not None:
        # fused dequant: int8 blocks × per-block scale
        vals = vals.astype(jnp.float32) * w.scale[:, :, None, None]
    vals = vals.astype(dt)

    def body(acc, step):
        v_j, idx_j = step                      # (NB, bk, bn), (NB,)
        xg = xb[idx_j]                         # (NB, M, bk)
        acc = acc + jnp.einsum("nmk,nkb->nmb", xg, v_j,
                               preferred_element_type=jnp.float32)
        return acc, None

    acc0 = jnp.zeros((NB, M, bn), dtype=jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (vals, w.idx))
    return jnp.moveaxis(acc, 0, 1).reshape(M, N).astype(x.dtype)


def bsr_to_dense(w: BlockSparseWeight) -> jnp.ndarray:
    """Reference reconstruction (tests)."""
    K, N = w.shape
    bk, bn = w.block
    KB, NB = K // bk, N // bn
    vals = w.vals
    if w.scale is not None:
        vals = vals.astype(jnp.float32) * w.scale[:, :, None, None]
    dense = jnp.zeros((KB, bk, NB, bn), dtype=jnp.float32)
    # padding entries have zero vals, so scatter-add is safe
    nb = jnp.arange(NB)
    for j in range(w.k_max):
        dense = dense.at[w.idx[j], :, nb, :].add(
            vals[j].astype(jnp.float32))
    return dense.reshape(K, N)
