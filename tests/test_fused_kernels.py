"""Fused epilogue (bias + activation in the last-visit flush) and fused
gated-FFN kernel vs the pure-jnp oracles in kernels/sasp_gemm/ref.py and
the masked-dense path, across fp32/bf16/int8 — including the
all-pruned-column padding case."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.sasp_gemm import ops as sasp_ops
from repro.kernels.sasp_gemm.ref import (
    epilogue_ref,
    fused_ffn_ref,
    masked_dense_ref,
)

RNG = np.random.default_rng(0)


def _case(M, K, N, bk, bn, sparsity, dtype=np.float32):
    x = jnp.asarray(RNG.normal(size=(M, K)).astype(dtype))
    w = RNG.normal(size=(K, N)).astype(np.float32)
    mask = RNG.random((K // bk, N // bn)) > sparsity
    return x, w, mask


def _mask_dense(w, mask, bk, bn):
    KB, NB = mask.shape
    wb = w.reshape(KB, bk, NB, bn) * mask[:, None, :, None]
    return wb.reshape(w.shape).astype(np.float32)


SWEEP = [
    (8, 16, 16, 8, 8, 0.0),
    (16, 32, 64, 8, 16, 0.3),
    (64, 128, 128, 32, 32, 0.5),
    (32, 64, 96, 16, 16, 0.9),
    (7, 16, 32, 8, 8, 0.4),          # ragged M
]


# ---------------------------------------------------------------------------
# GEMM epilogue: bias + activation in the flush
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,K,N,bk,bn,sp", SWEEP)
@pytest.mark.parametrize("act", [None, "silu", "relu"])
def test_epilogue_fp32_vs_masked_dense(M, K, N, bk, bn, sp, act):
    x, w, mask = _case(M, K, N, bk, bn, sp)
    bias = RNG.normal(size=(N,)).astype(np.float32)
    ref = epilogue_ref(masked_dense_ref(x, jnp.asarray(w),
                                        jnp.asarray(mask)), bias, act)
    wv, kn, _ = sasp_ops.build_kernel_weight(w, mask, bk, bn)
    y = sasp_ops.sasp_matmul_packed(x, wv, kn, n=N, block_m=min(M, 128),
                                    bias=jnp.asarray(bias), act=act)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("act", ["silu", "gelu"])
def test_epilogue_act_only(act):
    M, K, N, bk, bn, sp = 16, 32, 64, 8, 16, 0.5
    x, w, mask = _case(M, K, N, bk, bn, sp)
    ref = epilogue_ref(masked_dense_ref(x, jnp.asarray(w),
                                        jnp.asarray(mask)), None, act)
    wv, kn, _ = sasp_ops.build_kernel_weight(w, mask, bk, bn)
    y = sasp_ops.sasp_matmul_packed(x, wv, kn, n=N, act=act)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_epilogue_int8_vs_oracle():
    M, K, N, bk, bn = 32, 64, 64, 16, 16
    x, w, mask = _case(M, K, N, bk, bn, 0.4)
    bias = RNG.normal(size=(N,)).astype(np.float32)
    wv, kn, sc = sasp_ops.build_kernel_weight(w, mask, bk, bn,
                                              quantize=True)
    y = sasp_ops.sasp_matmul_packed(x, wv, kn, sc, n=N,
                                    bias=jnp.asarray(bias), act="silu")
    ref = epilogue_ref(masked_dense_ref(x, jnp.asarray(w),
                                        jnp.asarray(mask)), bias, "silu")
    scale = float(jnp.max(jnp.abs(ref))) + 1e-9
    assert float(jnp.max(jnp.abs(y - ref))) / scale < 2e-2
    # against the oracle consuming the SAME int8 inputs: tight
    from repro.kernels.sasp_gemm.ref import block_list_ref
    ref2 = epilogue_ref(jnp.asarray(block_list_ref(x, wv, kn, N,
                                                   scales=sc)),
                        bias, "silu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref2),
                               rtol=1e-4, atol=1e-4)


def test_epilogue_bf16():
    M, K, N, bk, bn = 32, 64, 64, 16, 16
    x, w, mask = _case(M, K, N, bk, bn, 0.5)
    bias = RNG.normal(size=(N,)).astype(np.float32)
    x16 = x.astype(jnp.bfloat16)
    wv, kn, _ = sasp_ops.build_kernel_weight(w, mask, bk, bn)
    y = sasp_ops.sasp_matmul_packed(
        x16, wv.astype(jnp.bfloat16), kn, n=N, bias=jnp.asarray(bias),
        act="relu").astype(jnp.float32)
    ref = epilogue_ref(masked_dense_ref(x16, jnp.asarray(w, jnp.bfloat16),
                                        jnp.asarray(mask)), bias,
                       "relu").astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(ref))) + 1e-9
    assert float(jnp.max(jnp.abs(y - ref))) / scale < 3e-2


def test_epilogue_fully_pruned_column_gets_act_bias():
    """Empty output columns must flush act(bias), matching the
    masked-dense semantics act(x @ (w ⊙ mask) + b)."""
    M, K, N, bk, bn = 16, 32, 32, 8, 8
    x, w, _ = _case(M, K, N, bk, bn, 0.0)
    mask = np.zeros((4, 4), bool)
    mask[:, 0] = True                # only first column block survives
    bias = RNG.normal(size=(N,)).astype(np.float32)
    wv, kn, _ = sasp_ops.build_kernel_weight(w, mask, bk, bn)
    y = np.asarray(sasp_ops.sasp_matmul_packed(
        x, wv, kn, n=N, bias=jnp.asarray(bias), act="silu"))
    ref = np.asarray(epilogue_ref(
        masked_dense_ref(x, jnp.asarray(w), jnp.asarray(mask)), bias,
        "silu"))
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)
    # pruned columns carry exactly act(bias), broadcast across rows
    want = np.asarray(epilogue_ref(jnp.zeros((1, N)), bias, "silu"))
    np.testing.assert_allclose(y[:, bn:], np.broadcast_to(
        want[:, bn:], (M, N - bn)), rtol=1e-5, atol=1e-5)


def test_padded_visit_list_matches_compact():
    """Dup-last-visit zero padding (layer-stack sharing of one static
    nnz) must not change the result."""
    M, K, N, bk, bn = 16, 32, 64, 8, 16
    x, w, mask = _case(M, K, N, bk, bn, 0.5)
    wv, kn, _ = sasp_ops.build_kernel_weight(w, mask, bk, bn)
    y0 = np.asarray(sasp_ops.sasp_matmul_packed(x, wv, kn, n=N))
    vp, kp, _ = sasp_ops.pad_block_list(np.asarray(wv), np.asarray(kn),
                                        None, np.asarray(wv).shape[0] + 3)
    y1 = np.asarray(sasp_ops.sasp_matmul_packed(
        x, jnp.asarray(vp), jnp.asarray(kp), n=N))
    np.testing.assert_allclose(y0, y1, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Fused gated FFN
# ---------------------------------------------------------------------------


def _ffn_case(M, d, F, bk, bf, sp1, sp2):
    x = jnp.asarray(RNG.normal(size=(M, d)), jnp.float32)
    w1 = RNG.normal(size=(d, F)).astype(np.float32)
    w3 = RNG.normal(size=(d, F)).astype(np.float32)
    w2 = RNG.normal(size=(F, d)).astype(np.float32) * 0.1
    m1 = RNG.random((d // bk, F // bf)) > sp1
    m3 = RNG.random((d // bk, F // bf)) > sp1
    m2 = RNG.random((F // bf, d // bk)) > sp2
    return (x, _mask_dense(w1, m1, bk, bf), _mask_dense(w3, m3, bk, bf),
            _mask_dense(w2, m2, bf, bk))


@pytest.mark.parametrize("M,d,F,bk,bf,sp1,sp2", [
    (16, 32, 64, 8, 16, 0.0, 0.0),
    (32, 64, 128, 16, 16, 0.4, 0.4),
    (8, 32, 96, 8, 16, 0.7, 0.3),
    (7, 16, 32, 8, 8, 0.5, 0.5),     # ragged M
    (130, 16, 32, 8, 8, 0.5, 0.5),   # > block_m: padded row blocks
])
@pytest.mark.parametrize("act", ["silu", "gelu"])
def test_fused_ffn_fp32_vs_masked_dense(M, d, F, bk, bf, sp1, sp2, act):
    x, w1m, w3m, w2m = _ffn_case(M, d, F, bk, bf, sp1, sp2)
    ref = fused_ffn_ref(x, w1m, w3m, w2m, act=act)
    w1v, w3v, w2v, b1, b3, b2, _ = sasp_ops.build_fused_ffn(
        w1m, w3m, w2m, block_f=bf)
    y = sasp_ops.fused_ffn_matmul(x, w1v, w3v, w2v, b1, b3, b2, act=act,
                                  block_m=min(M, 128))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_ffn_with_biases():
    M, d, F, bk, bf = 16, 32, 64, 8, 16
    x, w1m, w3m, w2m = _ffn_case(M, d, F, bk, bf, 0.5, 0.5)
    b1 = RNG.normal(size=(F,)).astype(np.float32)
    b3 = RNG.normal(size=(F,)).astype(np.float32)
    b2 = RNG.normal(size=(d,)).astype(np.float32)
    ref = fused_ffn_ref(x, w1m, w3m, w2m, b1, b3, b2, act="silu")
    w1v, w3v, w2v, b1v, b3v, b2v, _ = sasp_ops.build_fused_ffn(
        w1m, w3m, w2m, block_f=bf, b1=b1, b3=b3, b2=b2)
    y = sasp_ops.fused_ffn_matmul(x, w1v, w3v, w2v, b1v, b3v, b2v,
                                  act="silu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_ffn_bf16():
    M, d, F, bk, bf = 16, 32, 64, 8, 16
    x, w1m, w3m, w2m = _ffn_case(M, d, F, bk, bf, 0.4, 0.4)
    ref = fused_ffn_ref(x, w1m, w3m, w2m, act="silu")
    w1v, w3v, w2v, b1, b3, b2, _ = sasp_ops.build_fused_ffn(
        w1m, w3m, w2m, block_f=bf)
    y = sasp_ops.fused_ffn_matmul(
        x.astype(jnp.bfloat16), w1v.astype(jnp.bfloat16),
        w3v.astype(jnp.bfloat16), w2v.astype(jnp.bfloat16), b1, b3, b2,
        act="silu").astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(ref))) + 1e-9
    assert float(jnp.max(jnp.abs(y - ref))) / scale < 3e-2


def test_fused_ffn_int8():
    M, d, F, bk, bf = 32, 64, 128, 16, 16
    x, w1m, w3m, w2m = _ffn_case(M, d, F, bk, bf, 0.4, 0.4)
    ref = fused_ffn_ref(x, w1m, w3m, w2m, act="silu")
    w1v, w3v, w2v, b1, b3, b2, scales = sasp_ops.build_fused_ffn(
        w1m, w3m, w2m, block_f=bf, quantize=True)
    assert scales is not None
    y = sasp_ops.fused_ffn_matmul(x, w1v, w3v, w2v, b1, b3, b2,
                                  scales=scales, act="silu")
    scale = float(jnp.max(jnp.abs(ref))) + 1e-9
    assert float(jnp.max(jnp.abs(y - ref))) / scale < 5e-2


def test_fused_ffn_skips_pruned_columns():
    """Fully pruned d_ff column-blocks must be absent from the visit
    list (FLOPs AND bytes drop) without changing the output."""
    M, d, F, bk, bf = 16, 32, 64, 8, 16
    x, w1m, w3m, w2m = _ffn_case(M, d, F, bk, bf, 0.0, 0.0)
    w1m[:, :2 * bf] = 0.0              # kill d_ff columns 0..1 in w1
    w2m[3 * bf:] = 0.0                 # kill d_ff row-block 3 in w2
    w1v, _, _, _, _, _, _ = sasp_ops.build_fused_ffn(
        w1m, w3m, w2m, block_f=bf)
    assert w1v.shape[0] == 1           # only column-block 2 survives
    ref = fused_ffn_ref(x, w1m, w3m, w2m, act="silu")
    w1v, w3v, w2v, b1, b3, b2, _ = sasp_ops.build_fused_ffn(
        w1m, w3m, w2m, block_f=bf)
    y = sasp_ops.fused_ffn_matmul(x, w1v, w3v, w2v, b1, b3, b2,
                                  act="silu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_ffn_all_pruned():
    """Everything pruned -> one zero padding visit -> output == b2."""
    M, d, F, bf = 8, 16, 32, 8
    x = jnp.asarray(RNG.normal(size=(M, d)), jnp.float32)
    z = np.zeros((d, F), np.float32)
    b2 = RNG.normal(size=(d,)).astype(np.float32)
    w1v, w3v, w2v, b1, b3, b2v, _ = sasp_ops.build_fused_ffn(
        z, z, z.T.copy(), block_f=bf, b2=b2)
    assert w1v.shape[0] == 1
    y = np.asarray(sasp_ops.fused_ffn_matmul(x, w1v, w3v, w2v, b1, b3,
                                             b2v, act="silu"))
    np.testing.assert_allclose(y, np.broadcast_to(b2, (M, d)),
                               rtol=1e-6, atol=1e-6)


def test_fused_ffn_visit_padding_matches():
    """Zero-w2v visit padding (layer-stack sharing) is a no-op."""
    M, d, F, bk, bf = 16, 32, 64, 8, 16
    x, w1m, w3m, w2m = _ffn_case(M, d, F, bk, bf, 0.5, 0.5)
    a = sasp_ops.build_fused_ffn(w1m, w3m, w2m, block_f=bf)
    b = sasp_ops.build_fused_ffn(w1m, w3m, w2m, block_f=bf,
                                 nv_pad=np.asarray(a[0]).shape[0] + 2)
    ya = sasp_ops.fused_ffn_matmul(x, *a[:6], act="silu")
    yb = sasp_ops.fused_ffn_matmul(x, *b[:6], act="silu")
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yb),
                               rtol=1e-6, atol=1e-6)
