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
import re

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


def _kernel_args(one_chip, kernel, M=4):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    d, bf = D_MODEL, D_FF_BLOCK
    if kernel == "sasp_ffn_fused":
        return sasp_fused_ffn, [
            s((M, d), jnp.bfloat16), s((NV, d, bf), jnp.bfloat16),
            s((NV, d, bf), jnp.bfloat16), s((NV, bf, d), jnp.bfloat16),
            s((NV, bf), jnp.float32), s((NV, bf), jnp.float32),
            s((d,), jnp.float32)]

    def fn(x, w, kn):
        return sasp_gemm(x, w, kn, n=N)
    return fn, [s((M, K), jnp.bfloat16), s((NNZ, 128, 128), jnp.bfloat16),
                s((2, NNZ), jnp.int32)]


@pytest.mark.parametrize("kernel", ["sasp_ffn_fused", "sasp_matmul"])
def test_served_kernels_carry_their_names_for_v5e(one_chip, kernel):
    """The Mosaic custom call names its kernel: ``kernel_name``, and the
    ``kernel_metadata`` frontend attribute that the op text of a
    profiler trace carries."""
    fn, args = _kernel_args(one_chip, kernel)
    lowered = jax.jit(fn).lower(*args)
    assert f'kernel_name = "{kernel}"' in lowered.as_text()
    # one instruction per chunk (kernel_metadata's JSON holds newlines)
    text = _compile(fn, *args).as_text()
    calls = [op for op in re.split(r"\n\s+(?:ROOT )?%", text)
             if 'custom_call_target="tpu_custom_call"' in op]
    assert len(calls) == 1
    assert f'"kernel":"{kernel}"' in calls[0]
    assert "frontend_attributes={kernel_metadata={" in calls[0]


LAYERS, NV_L = 4, 8                      # a 4-layer stack, 8 visits each


def _stacked_ffn(one_chip, wdt):
    from repro.core.sparse import PackedFFN

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    L, nv, d, bf = LAYERS, NV_L, D_MODEL, D_FF_BLOCK
    sc = s((L, nv), jnp.float32) if wdt == jnp.int8 else None
    return PackedFFN(s((L, nv, d, bf), wdt), s((L, nv, d, bf), wdt),
                     s((L, nv, bf, d), wdt), s((L, nv, bf), jnp.float32),
                     s((L, nv, bf), jnp.float32), s((L, d), jnp.float32),
                     d_model=d, d_ff=25600, block_f=bf, act="silu",
                     s1=sc, s3=sc, s2=sc)


def _result_shapes(text):
    """Each instruction's result dims, by the instruction's text."""
    return re.findall(r"^\s*(?:ROOT )?%\S+ = [a-z0-9]+\[([0-9,]*)\]",
                      text, re.M)


@pytest.mark.parametrize("scan", ["in_place", "sliced"])
@pytest.mark.parametrize("wdt", [jnp.bfloat16, jnp.int8])
def test_layer_scan_reads_fused_ffn_in_place_for_v5e(one_chip, wdt, scan,
                                                     monkeypatch):
    """A 4-layer scan of the fused FFN through the model step's layer
    scan (models/lm.py ``_scan_layers``) hands the kernel the whole
    stacked weights: no op makes a single layer's (nv, d, bf) or
    (nv, bf, d) copy, and the kernel's operands are the stacks, layer
    and visit axes merged. The scan that slices every param (the
    ``sliced`` control) makes those copies, which shows the check sees
    them."""
    from repro.configs import get_config
    from repro.models import ffn as ffn_mod
    from repro.models import lm

    if scan == "sliced":
        monkeypatch.setattr(
            lm, "_scan_layers", lambda body, carry, seg, xs=None:
            jax.lax.scan(body, carry, (seg, xs)))
    cfg = get_config("qwen3-32b")

    def step(x, seg):
        def body(xc, inp):
            return ffn_mod.ffn_apply(inp[0]["slot0"]["ffn"], cfg, xc), None
        return lm._scan_layers(body, x, seg)[0]

    seg = {"slot0": {"ffn": {"sasp_fused": _stacked_ffn(one_chip, wdt)}}}
    x = jax.ShapeDtypeStruct((12, D_MODEL), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(step).lower(x, seg).compile().as_text()
    assert text.count(" while(") == 1    # the layer scan; Mosaic kernel
    one = {f"{NV_L},{D_MODEL},{D_FF_BLOCK}", f"{NV_L},{D_FF_BLOCK},{D_MODEL}"}
    sliced = one & set(_result_shapes(text))
    calls = [op for op in re.split(r"\n\s+(?:ROOT )?%", text)
             if 'custom_call_target="tpu_custom_call"' in op]
    assert len(calls) == 1
    stacked = (f"[{LAYERS * NV_L},{D_MODEL},{D_FF_BLOCK}]",
               f"[{LAYERS * NV_L},{D_FF_BLOCK},{D_MODEL}]")
    if scan == "sliced":
        assert sliced
        return
    assert not sliced
    operands = calls[0].split("operand_layout_constraints=")[1]
    assert all(sh in operands for sh in stacked)
