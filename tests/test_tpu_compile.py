"""Compile rehearsals for TPU v5e: the served path's Pallas kernels at
qwen3-32b widths, compiled by the TPU compiler for a described (not
attached) v5e chip. Nothing runs, so these say nothing about results or
times; they catch what interpret mode cannot — block shapes Mosaic
refuses, SMEM/VMEM overflows, a kernel that fell back to interpretation.

The topology is described inside a module fixture, never at import
time: only one process may load the TPU library, and under pytest-xdist
each worker imports every test file, so only the worker that runs this
file may load it. Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.sasp_gemm.kernel import sasp_fused_ffn, sasp_gemm

D_MODEL, D_FF_BLOCK = 5120, 128          # qwen3-32b d_model; MXU tile
NV = 100                                 # surviving d_ff blocks (of 200)
K, N, NNZ = 5120, 8192, 1280             # wq: half of its 40 × 64 tiles
ROWS = (4, 128, 130)                     # decode slots, prefill, ragged


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                # noqa: BLE001 — skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    # Mosaic kernel, not the interpreter's while loop over the grid
    assert "tpu_custom_call" in text
    assert " while(" not in text
    return compiled


@pytest.mark.parametrize("M", ROWS)
@pytest.mark.parametrize("wdt", [jnp.bfloat16, jnp.int8])
def test_fused_ffn_compiles_for_v5e(one_chip, wdt, M):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    d, bf = D_MODEL, D_FF_BLOCK
    args = [s((M, d), jnp.bfloat16), s((NV, d, bf), wdt),
            s((NV, d, bf), wdt), s((NV, bf, d), wdt),
            s((NV, bf), jnp.float32), s((NV, bf), jnp.float32),
            s((d,), jnp.float32)]
    if wdt == jnp.int8:
        args += [s((NV,), jnp.float32)] * 3

        def fn(x, w1, w3, w2, b1, b3, b2, s1, s3, s2):
            return sasp_fused_ffn(x, w1, w3, w2, b1, b3, b2,
                                  scales=(s1, s3, s2))
    else:
        fn = sasp_fused_ffn
    out = _compile(fn, *args).out_info
    assert out.shape == (M, d) and out.dtype == jnp.bfloat16


@pytest.mark.parametrize("M", ROWS)
@pytest.mark.parametrize("wdt", [jnp.bfloat16, jnp.int8])
def test_sasp_gemm_compiles_for_v5e(one_chip, wdt, M):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = [s((M, K), jnp.bfloat16), s((NNZ, 128, 128), wdt),
            s((2, NNZ), jnp.int32)]
    if wdt == jnp.int8:
        args.append(s((NNZ,), jnp.float32))

        def fn(x, w, kn, sc):
            return sasp_gemm(x, w, kn, n=N, scales=sc)
    else:
        def fn(x, w, kn):
            return sasp_gemm(x, w, kn, n=N)
    out = _compile(fn, *args).out_info
    assert out.shape == (M, N) and out.dtype == jnp.bfloat16
