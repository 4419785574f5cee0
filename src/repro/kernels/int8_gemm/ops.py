"""Jit'd wrapper for the dense weight-INT8 GEMM."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantization import QuantizedWeight
from repro.kernels.int8_gemm.kernel import int8_gemm


@jax.jit
def _int8_matmul_jit(x, w_q, scale):
    return int8_gemm(x, w_q, scale)


def int8_matmul(x: jnp.ndarray, qw: QuantizedWeight) -> jnp.ndarray:
    """(…, K) @ QuantizedWeight -> (…, N), dequant fused in the kernel."""
    *lead, K = x.shape
    y = _int8_matmul_jit(x.reshape(-1, K), qw.q, qw.scale)
    return y.reshape(*lead, qw.q.shape[-1]).astype(x.dtype)
