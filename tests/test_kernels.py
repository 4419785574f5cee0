"""Per-kernel shape/dtype/sparsity sweeps vs the pure-jnp oracles
(interpret mode executes the kernel body on CPU)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.quantization import quantize_int8
from repro.core.sparse import bsr_from_mask
from repro.kernels.int8_gemm.ops import int8_matmul
from repro.kernels.int8_gemm.ref import int8_gemm_ref
from repro.kernels.sasp_gemm import ops as sasp_ops
from repro.kernels.sasp_gemm.ref import block_list_ref, masked_dense_ref

RNG = np.random.default_rng(0)


def _case(M, K, N, bk, bn, sparsity, dtype=np.float32):
    x = jnp.asarray(RNG.normal(size=(M, K)).astype(dtype))
    w = RNG.normal(size=(K, N)).astype(np.float32)
    mask = RNG.random((K // bk, N // bn)) > sparsity
    return x, w, mask


SWEEP = [
    (8, 16, 16, 8, 8, 0.0),
    (16, 32, 64, 8, 16, 0.3),
    (64, 128, 128, 32, 32, 0.5),
    (32, 64, 96, 16, 16, 0.9),
    (128, 256, 128, 64, 64, 0.6),
    (7, 16, 32, 8, 8, 0.4),          # ragged M
    (130, 32, 32, 8, 8, 0.4),        # > block_m: padded row blocks
]


@pytest.mark.parametrize("M,K,N,bk,bn,sp", SWEEP)
def test_sasp_gemm_fp_vs_oracle(M, K, N, bk, bn, sp):
    x, w, mask = _case(M, K, N, bk, bn, sp)
    ref = masked_dense_ref(x, jnp.asarray(w), jnp.asarray(mask))
    wv, kn, _ = sasp_ops.build_kernel_weight(w, mask, bk, bn)
    y = sasp_ops.sasp_matmul_packed(x, wv, kn, n=N, block_m=min(M, 128))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # the kernel's own input view agrees with the independent oracle
    ref2 = block_list_ref(x, wv, kn, N)
    np.testing.assert_allclose(np.asarray(y), ref2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("M,K,N,bk,bn,sp", SWEEP[:4])
def test_sasp_gemm_int8_vs_oracle(M, K, N, bk, bn, sp):
    x, w, mask = _case(M, K, N, bk, bn, sp)
    ref = masked_dense_ref(x, jnp.asarray(w), jnp.asarray(mask))
    wv, kn, sc = sasp_ops.build_kernel_weight(w, mask, bk, bn,
                                              quantize=True)
    y = sasp_ops.sasp_matmul_packed(x, wv, kn, sc, n=N)
    scale = float(jnp.max(jnp.abs(ref))) + 1e-9
    assert float(jnp.max(jnp.abs(y - ref))) / scale < 2e-2
    # against the oracle that consumes the SAME int8 inputs: tight
    ref2 = block_list_ref(x, wv, kn, N, scales=sc)
    np.testing.assert_allclose(np.asarray(y), ref2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("M,K,N,bk,bn,sp", SWEEP[:4])
def test_sasp_gemm_masked_grid_variant(M, K, N, bk, bn, sp):
    x, w, mask = _case(M, K, N, bk, bn, sp)
    ref = masked_dense_ref(x, jnp.asarray(w), jnp.asarray(mask))
    y = sasp_ops.masked_matmul(x, jnp.asarray(w),
                               jnp.asarray(mask, jnp.int32),
                               block_m=min(M, 128), block_k=bk, block_n=bn)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_sasp_gemm_bf16():
    x, w, mask = _case(32, 64, 64, 16, 16, 0.5, dtype=np.float32)
    x16 = x.astype(jnp.bfloat16)
    ref = masked_dense_ref(x16, jnp.asarray(w, jnp.bfloat16),
                           jnp.asarray(mask)).astype(jnp.float32)
    wv, kn, _ = sasp_ops.build_kernel_weight(w, mask, 16, 16)
    y = sasp_ops.sasp_matmul_packed(
        x16, wv.astype(jnp.bfloat16), kn, n=64).astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(ref))) + 1e-9
    assert float(jnp.max(jnp.abs(y - ref))) / scale < 3e-2


def test_sasp_gemm_fully_pruned_column():
    # output columns with zero surviving blocks must be exactly zero
    x, w, _ = _case(16, 32, 32, 8, 8, 0.0)
    mask = np.zeros((4, 4), bool)
    mask[:, 0] = True                # only first column block survives
    wv, kn, _ = sasp_ops.build_kernel_weight(w, mask, 8, 8)
    y = np.asarray(sasp_ops.sasp_matmul_packed(x, wv, kn, n=32))
    assert np.allclose(y[:, 8:], 0.0)
    ref = masked_dense_ref(x, jnp.asarray(w), jnp.asarray(mask))
    np.testing.assert_allclose(y, np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_sasp_gemm_via_bsr_container():
    x, w, mask = _case(16, 64, 96, 16, 16, 0.5)
    bsr = bsr_from_mask(w, mask, 16, 16)
    y = sasp_ops.sasp_matmul(x, bsr)
    ref = masked_dense_ref(x, jnp.asarray(w), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("M,K,N,bk,bn", [
    (16, 32, 64, 8, 16), (64, 128, 128, 32, 32), (7, 16, 16, 8, 8),
    (32, 64, 64, 64, 64),
])
def test_int8_gemm_vs_oracle(M, K, N, bk, bn):
    x = jnp.asarray(RNG.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(K, N)), jnp.float32)
    qw = quantize_int8(w, bk, bn)
    y = int8_matmul(x, qw)
    ref = int8_gemm_ref(x, qw.q, qw.scale)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # and close to the unquantized product
    full = x @ w
    rel = float(jnp.max(jnp.abs(y - full)) / jnp.max(jnp.abs(full)))
    assert rel < 2e-2
