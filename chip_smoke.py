#!/usr/bin/env python3
"""Smoke run: the SASP serving path end to end on one TPU chip.

Serves qwen3-32b at its published widths (d_model 5120, d_ff 25600, 64
query / 8 KV heads of 128, vocab 151 936), cut to 4 layers, with random
bf16 weights from seed 0, through the serving launcher
(``repro.launch.serve``), all in this one process, in three phases:

  dense   the unpruned model;
  packed  0.5 tile sparsity, 128×128 blocks (the MXU tile), served by
          the fused tile-skip FFN kernel;
  int8    the same with int8 weights.

Each phase serves 4 requests on 4 slots, 16 new tokens each, and every
request must finish. Each packed phase then runs one prefill of a
(4, 32) prompt batch (M = 128 rows through every FFN) through the packed
model and through the masked one (the same pruned weights, dense
matmuls) and compares their last-token logits.

The launcher draws w2 at a 2.8× smaller scale than w1/w3 (residual
scaling for training). Global L1 pruning at 0.5 would then remove every
w2 tile, the fused kernel would visit one zero block per layer, and the
comparison would hold trivially. So the smoke run rescales w2 to w1's
mean magnitude before deploying: pruning then spreads over all three
matrices and the kernel walks a full visit list.

``--chips 4`` runs only the tensor-parallel path and what it is
compared with: the packed model served without a mesh and under
``--mesh 1,4`` in the same process; it compares their logits and token
streams.

Every line before the last is a smoke run's diagnostics, not a
benchmark result. The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
With no TPU, or when a phase fails, the script exits non-zero and
prints no such line.

  python3 chip_smoke.py             # one chip
  python3 chip_smoke.py --chips 4   # four chips: the TP path only
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
COMMON = ["--arch", "qwen3-32b", "--no-reduce", "--layers", "4",
          "--param-dtype", "bfloat16", "--requests", "4", "--slots", "4",
          "--max-new", "16", "--cache-len", "128"]
PACKED = ["--sasp", "0.5", "--path", "packed"]
PHASES = {"dense": ["--path", "dense"],
          "packed": PACKED,
          "int8": PACKED + ["--int8-weights"]}
PREFILL_SHAPE = (4, 32)         # M = 128 rows into every FFN
# Tolerances on max|packed - reference| / max|reference| over the
# last-token logits. bf16 keeps an 8-bit significand (unit roundoff
# u = 2^-8). Both paths accumulate in float32 but round intermediates
# to bf16 at different points (the fused kernel holds act(u)·g in f32
# up to the down-projection; the dense path rounds u, g and h), each
# rounding moving a value by up to u of itself, over 4 layers and the
# final norm: 16u = 2^-4 bounds that with room (a CPU rehearsal at
# d_model 512-1024 measured about 2^-6.7). The int8 phase adds weight
# rounding against the bf16 masked weights: up to amax/254 per weight,
# amax taken over a (5120, 128) visit block (about 5σ), so up to 2% of
# σ per weight; 2^-3 bounds it (the rehearsal measured about 2^-5.1).
TOL = {"packed": 2.0 ** -4, "int8": 2.0 ** -3, "mesh": 2.0 ** -4}
# XLA's compile of each program; tracing and lowering events are left
# out because they nest (an inner jit is traced inside an outer one)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    pass


def say(msg: str):
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Seconds XLA spent compiling programs, from JAX's own monitoring
    events."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.total += duration


def even_ffn_scale(params):
    """Rescale every w2 stack to its w1's mean magnitude (see the module
    docstring), in one program so no float32 copy of a stack is held."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rescale(w1, w2):
        r = (jnp.mean(jnp.abs(w1.astype(jnp.float32)))
             / jnp.mean(jnp.abs(w2.astype(jnp.float32))))
        return (w2.astype(jnp.float32) * r).astype(w2.dtype)

    segs = []
    for seg in params["segments"]:
        new_seg = {}
        for name, slot in seg.items():
            ffn = dict(slot["ffn"])
            ffn["w2"] = dict(ffn["w2"], w=rescale(ffn["w1"]["w"],
                                                  ffn["w2"]["w"]))
            new_seg[name] = dict(slot, ffn=ffn)
        segs.append(new_seg)
    return dict(params, segments=tuple(segs))


def serve_phase(name, argv, clock, dev):
    """One launcher run; checks that every request finished."""
    import jax

    from repro.launch import serve

    say(f"--- phase {name}: repro.launch.serve {' '.join(argv)}")
    c0, t0 = clock.total, time.perf_counter()
    args = serve.build_parser().parse_args(argv)
    res = serve.main(argv, model=smoke_model(serve, args))
    wall = time.perf_counter() - t0
    n_new = args.max_new
    bad = [r for r in res.requests
           if r.status != "done" or len(r.out_tokens) != n_new]
    if bad:
        raise SmokeFailure(
            f"phase {name}: {len(bad)} of {len(res.requests)} requests "
            f"did not finish with {n_new} tokens (first: req "
            f"{bad[0].rid} status={bad[0].status} error={bad[0].error})")
    toks = sum(len(r.out_tokens) for r in res.requests)
    say(f"phase {name} on {dev.platform} {dev.device_kind} x"
        f"{len(jax.devices())}: {len(res.requests)} requests served, "
        f"{toks} tokens; XLA compile {clock.total - c0:.1f} s of "
        f"{wall:.1f} s wall; peak_bytes_in_use {peak_bytes(dev)}")
    return res


def smoke_model(serve, args):
    """The launcher's model with w2 rescaled. Held by no caller frame
    while the launcher runs, so deployment can free the dense FFN."""
    params, cfg = serve.load_model(args)
    return even_ffn_scale(params), cfg


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return (f"{stats.get('peak_bytes_in_use', 'not reported')} "
            f"(in use now {stats.get('bytes_in_use', 'not reported')})")


def prefill_logits(params, cfg, mesh=None):
    """Last-token logits of one seeded (4, 32) prefill, as float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distribution import context as dctx
    from repro.distribution import sharding as shd
    from repro.models import lm

    toks = np.random.default_rng(1).integers(
        0, cfg.vocab_size, PREFILL_SHAPE).astype(np.int32)
    if mesh is not None:
        params = jax.device_put(params, shd.param_shardings(
            cfg, jax.eval_shape(lambda: params), mesh))
    with dctx.use_mesh(mesh):
        fn = jax.jit(lambda p, t: lm.prefill(
            p, cfg, tokens=t, cache_len=PREFILL_SHAPE[1])[0])
        out = fn(params, jnp.asarray(toks))
    return np.asarray(out.astype(jnp.float32))


def compare(label, got, ref, tol):
    import numpy as np

    diff = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    ok = bool(np.all(np.isfinite(got))) and diff <= tol * scale
    say(f"{label}: max|logits diff| {diff:.6g} vs max|ref| {scale:.6g} "
        f"= {diff / scale:.6g} (tolerance {tol:.6g}) -> "
        f"{'within' if ok else 'OUT OF'} tolerance")
    if not ok:
        raise SmokeFailure(f"{label}: logits differ beyond tolerance")


def masked_twin(params, cfg):
    """The masked model on the same pruned weights: the packed overlays
    stripped, dense matmuls on the zeroed tiles."""
    from repro.core.deploy import strip_packed
    return strip_packed(params), dataclasses.replace(
        cfg, sasp=dataclasses.replace(cfg.sasp, path="masked"))


def visits(params):
    pf = params["segments"][0]["slot0"]["ffn"]["sasp_fused"]
    nv = int(pf.w1v.shape[-3])
    return nv, pf.d_ff // pf.block_f, str(pf.w1v.dtype)


def one_chip(clock, dev):
    import gc

    serve_phase("dense", COMMON + PHASES["dense"], clock, dev)
    gc.collect()
    for name in ("packed", "int8"):
        res = serve_phase(name, COMMON + PHASES[name], clock, dev)
        nv, nblk, dt = visits(res.params)
        say(f"phase {name}: fused FFN visits {nv} of {nblk} d_ff blocks "
            f"per layer, values {dt}")
        if nv < 2:
            raise SmokeFailure(f"phase {name}: degenerate visit list")
        got = prefill_logits(res.params, res.cfg)
        ref = prefill_logits(*masked_twin(res.params, res.cfg))
        compare(f"phase {name}: packed vs masked prefill", got, ref,
                TOL[name])
        del res, got, ref
        gc.collect()
        say(f"phase {name}: peak_bytes_in_use {peak_bytes(dev)}")


def four_chips(clock, dev):
    import gc

    import numpy as np

    base = serve_phase("packed", COMMON + PACKED, clock, dev)
    ref = prefill_logits(base.params, base.cfg)
    ref_streams = {r.rid: list(r.out_tokens) for r in base.requests}
    del base
    gc.collect()
    mesh = serve_phase("packed-mesh1x4",
                       COMMON + PACKED + ["--mesh", "1,4"], clock, dev)
    got = prefill_logits(mesh.params, mesh.cfg, mesh.mesh)
    streams = {r.rid: list(r.out_tokens) for r in mesh.requests}
    same = sum(streams[k] == ref_streams[k] for k in ref_streams)
    toks = [(a == b) for k in ref_streams
            for a, b in zip(streams[k], ref_streams[k])]
    say(f"mesh 1x4 vs meshless: {same} of {len(ref_streams)} streams "
        f"identical, {int(np.sum(toks))} of {len(toks)} token positions "
        f"equal")
    compare("mesh 1x4 vs meshless packed prefill", got, ref, TOL["mesh"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not importable ({e}); "
              "run this script from a checkout of the repository",
              file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devs)} "
              f"{dev.platform} device(s)", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1
    say(f"smoke run, not a benchmark: platform={dev.platform} "
        f"device_kind={dev.device_kind} devices={len(devs)} "
        f"compile cache={enable_compile_cache()}")
    clock = CompileClock(jax)
    try:
        (four_chips if args.chips == 4 else one_chip)(clock, dev)
    except (Exception, SystemExit) as e:        # noqa: BLE001 — report
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        import traceback
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
