"""One benchmark run: set up a cell, measure a window, check the output
against the reference, and return the result line.

The system under test is the serving engine (``repro.serve.engine``):
params are built through ``repro.launch.serve`` (``serving_config``,
``build_serving_params``) with weights made by ``bench/weights.py`` from
the seed, and the window drives ``Engine.submit`` / ``Engine.step`` as
a client would. Everything a cell needs is found by name:

- ``BENCHMARK.json``: the cell's configuration, traffic mix and metrics;
- ``bench/configs/<config>.json``: sizes, precision, SASP settings;
- ``bench/traffic/<mix>.json``: lengths, loop, slots, buckets;
- ``bench/cells/<cell>.json``: the cell's own numbers (the offered rate
  of an open loop, the limits of the output check);
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SAMPLE_TOKENS = 1000        # served tokens the output check covers
SAMPLE_MAX = 8              # ... in at most this many requests


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what a cell is
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: object                     # traffic.Mix
    cell: Dict                      # bench/cells/<name>.json
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_json(path: str) -> Dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_cell(name: str, root: str = ROOT) -> Cell:
    import traffic
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(wl)})")
    w = wl[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(os.path.join(root, conf["file"])),
        mix=traffic.Mix.load(w["traffic"]),
        cell=load_json(os.path.join(HERE, "cells", f"{name}.json")),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)])


def check_devices(chips: int):
    """The devices JAX found, or SystemExit when there is no TPU or too
    few chips: the benchmark never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX found {len(devs)} "
                         f"{devs[0].platform} device(s)")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return devs


def enable_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one; every
    program is cached, however quick its compile."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def model_config(conf: Dict):
    """The program's ModelConfig for a configuration file: the
    launcher's ``serving_config`` for the file's architecture, with
    every size and setting taken from the file."""
    from repro.launch import serve
    s = conf["serve"]
    ns = argparse.Namespace(arch=s["arch"], reduce=False,
                            layers=conf["num_hidden_layers"],
                            param_dtype=conf["torch_dtype"],
                            int8_kv=False, ckpt_dir=None)
    cfg = serve.serving_config(ns)
    return dataclasses.replace(
        cfg, num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], d_ff=conf["intermediate_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or
        conf["hidden_size"] // conf["num_attention_heads"],
        vocab_size=conf["vocab_size"], qk_norm=bool(s["qk_norm"]),
        qkv_bias=bool(s["qkv_bias"]), rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        compute_dtype=s.get("compute_dtype", "bfloat16"))


def program_tree(W: Dict, cfg) -> Dict:
    """The benchmark's weights in the program's param tree (the same
    arrays, no copy), checked against ``lm.init_params``' shapes."""
    import jax
    from repro.models import lm
    L = W["layers"]
    mixer = {n: {"w": L[n]} for n in ("wq", "wk", "wv", "wo")}
    if cfg.qkv_bias:
        for n in ("q", "k", "v"):
            mixer[f"w{n}"]["b"] = L[f"b{n}"]
    if cfg.qk_norm:
        mixer["q_norm"], mixer["k_norm"] = L["q_norm"], L["k_norm"]
    slot = {"norm1": {"scale": L["norm1"]}, "norm2": {"scale": L["norm2"]},
            "mixer": mixer,
            "ffn": {n: {"w": L[n]} for n in ("w1", "w2", "w3")}}
    params = {"embed": {"emb": W["embed"]},
              "final_norm": {"scale": W["final_norm"]},
              "segments": ({"slot0": slot},)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"emb": W["head"]}
    want = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                 cfg))
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not fit the program's "
                         "param tree")
    return params


def build_engine(conf: Dict, mix, seed: int, int8_weights: bool = False,
                 phases: Optional[Dict[str, float]] = None):
    """(engine, cfg, ffn non-zero tiles per layer) for one seed."""
    import weights
    import work
    from repro.launch import serve
    from repro.serve.engine import Engine
    spec = weights.Spec.from_config(conf)
    cfg = model_config(conf)
    clock = Phases(phases)
    params = program_tree(weights.make(weights.root_key(seed), spec), cfg)
    clock.mark("weights", params)
    s = conf["serve"]
    params, cfg = serve.build_serving_params(
        params, cfg, path=s["path"], sparsity=float(s["sparsity"]),
        int8_weights=int8_weights, block_k=s["block"], block_n=s["block"],
        scope=s["scope"], verbose=False)
    ffn = params["segments"][0]["slot0"]["ffn"]
    tiles = sum(work.count_nonzero_tiles(ffn[n]["w"])
                for n in ("w1", "w2", "w3"))
    clock.mark("deploy", params)
    eng = Engine(params, cfg, batch_slots=mix.slots,
                 cache_len=mix.cache_len, buckets=mix.buckets)
    clock.mark("engine", eng.caches)
    return eng, cfg, tiles


class Phases:
    """Seconds of each phase of set-up, into ``into`` when it is given;
    ``mark`` waits for the phase's arrays, so their device work counts
    where it was started."""

    def __init__(self, into: Optional[Dict[str, float]]):
        self.into, self.t = into, time.monotonic()

    def mark(self, name: str, tree=None):
        if self.into is None:
            return
        if tree is not None:
            import jax
            jax.block_until_ready(tree)
        now = time.monotonic()
        self.into[name] = now - self.t
        self.t = now


def warm_up(eng, mix):
    """One admission per prefill bucket, each followed by a decode step:
    every program the window runs is compiled here."""
    from repro.serve.engine import Request
    for i, b in enumerate(mix.buckets):
        r = Request(rid=-1 - i, prompt=np.ones((b,), np.int32),
                    max_new_tokens=2)
        eng.submit(r)
        while not r.done:
            eng.step()


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


class Window:
    """Drives the engine as the mix's clients would and keeps the host
    stamps of every request and token. The clients start together, so
    the loop runs the mix's ``lead_s`` before the measured window opens:
    a closed loop's first admission, all clients at once, falls there
    and not among the window's first tokens."""

    def __init__(self, eng, mix, seed: int, rate: Optional[float],
                 vocab: int):
        import traffic
        self.eng, self.mix, self.rate = eng, mix, rate
        self.gen = traffic.requests(mix, seed, vocab)
        self.gaps = (traffic.arrival_gaps(mix, seed, rate)
                     if mix.loop == "open" else None)
        self.reqs: List = []
        self.due: Dict[int, float] = {}
        self.tok_t: Dict[int, List[float]] = {}
        self.lateness: List[float] = []
        self.prefill_shapes: List = []
        self.decode_calls = 0
        eng.on_token = self._on_token
        pf, dc = eng._prefill, eng._decode

        def prefill(params, toks, *a):
            self.prefill_shapes.append(tuple(toks.shape))
            return pf(params, toks, *a)

        def decode(*a):
            self.decode_calls += 1
            return dc(*a)

        eng._prefill, eng._decode = prefill, decode

    def _on_token(self, req, tok):
        if req.rid >= 0:
            self.tok_t[req.rid].append(time.monotonic())

    def _submit(self, due: float):
        import jax
        from repro.serve.engine import Request
        g = next(self.gen)
        r = Request(rid=len(self.reqs), prompt=g["prompt"],
                    max_new_tokens=g["max_new"])
        r.t_submit = due                # timed from when it was due
        self.reqs.append(r)
        self.due[r.rid] = due
        self.tok_t[r.rid] = []
        with jax.profiler.TraceAnnotation("submit"):
            self.eng.submit(r)

    def run(self, seconds: float, on_open=None):
        """Run the lead-in, call ``on_open`` (set-up ends there), then
        measure ``seconds``; the window is [t0, t1]."""
        import jax
        eng = self.eng
        start = time.monotonic()
        opened, end = False, start + self.mix.lead_s + seconds
        if self.mix.loop == "closed":
            for _ in range(self.mix.clients):
                self._submit(start)
        else:
            nxt = start + next(self.gaps)
        while True:
            now = time.monotonic()
            if not opened and now >= start + self.mix.lead_s:
                if on_open is not None:
                    on_open()
                self.prefill_shapes, self.decode_calls = [], 0
                self.t0 = now = time.monotonic()
                end, opened = now + seconds, True
            if now >= end:
                break
            if self.mix.loop == "open":
                while nxt <= now:
                    self.lateness.append(now - nxt)
                    self._submit(nxt)
                    nxt += next(self.gaps)
            if not eng.has_work():
                with jax.profiler.TraceAnnotation("wait for arrival"):
                    time.sleep(max(0.0, min(nxt, end) - now))
                continue
            with jax.profiler.TraceAnnotation("step"):
                done = eng.step()
            if self.mix.loop == "closed":
                for r in done:
                    if r.rid >= 0:
                        self._submit(r.t_done)
        self.t1 = time.monotonic()

    # -- end-to-end numbers -------------------------------------------------
    def end_to_end(self) -> Dict[str, float]:
        from stats import nearest_rank
        t0, t1 = self.t0, self.t1
        toks = sum(1 for ts in self.tok_t.values() for t in ts
                   if t0 <= t <= t1)
        ttft = sorted(r.t_first - self.due[r.rid] for r in self.reqs
                      if r.t_first is not None and t0 <= r.t_first <= t1)
        itl = sorted(b - a for ts in self.tok_t.values()
                     for a, b in zip(ts, ts[1:]) if t0 <= b <= t1)
        out = {"tok_s": toks / (t1 - t0)}
        if ttft:
            out["ttft_p90_ms"] = nearest_rank(ttft, 0.9) * 1e3
        if itl:
            out["itl_p50_ms"] = nearest_rank(itl, 0.5) * 1e3
            out["itl_p95_ms"] = nearest_rank(itl, 0.95) * 1e3
        self.counts = {"tokens": toks, "ttft": len(ttft),
                       "itl": len(itl),
                       "widest_gap_ms": itl[-1] * 1e3 if itl else None}
        return out

    def finished(self) -> List:
        return [r for r in self.reqs if r.status == "done"]


# ---------------------------------------------------------------------------
# the output check
# ---------------------------------------------------------------------------


def sample(finished: List, seed: int,
           tokens: int = SAMPLE_TOKENS) -> List:
    """The longest finished request, then others drawn from the seed,
    until ``tokens`` served tokens or SAMPLE_MAX requests."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 3])
    by_len = sorted(finished, key=lambda r: (-len(r.out_tokens), r.rid))
    rest = [by_len[i + 1] for i in rng.permutation(len(by_len) - 1)]
    out, n = [], 0
    for r in [by_len[0]] + rest:
        if n >= tokens or len(out) >= SAMPLE_MAX:
            break
        out.append(r)
        n += len(r.out_tokens)
    return out


def reference_gaps(conf: Dict, seed: int, seqs: List, alt=None,
                   int8: bool = False) -> List[Dict]:
    """Reference gaps of each (prompt, served tokens) in ``seqs``, with
    the weights made again from the seed."""
    import reference
    import weights
    spec = weights.Spec.from_config(conf)
    key = weights.root_key(seed)
    W = weights.make(key, spec)
    keep = weights.keep_masks(key, spec) if spec.sparsity > 0 else None
    out = []
    for i, (prompt, served) in enumerate(seqs):
        out.append(reference.gaps(spec, W, keep, prompt, served,
                                  None if alt is None else alt[i], int8))
    del W
    return out


def judge(gaps: List[Dict], limits: Dict, failed: int,
          wrong_length: int, sample_tokens: int = SAMPLE_TOKENS):
    """``correct`` and the numbers compared, each with its limit.

    ``gap_mean``: the mean, over every sampled served token, of how far
    its logit lies below the reference's best at that position. Sound
    bf16 runs read about 1e-3, the int8 control about 6e-3 (PERF.md);
    the widest gap does not separate the two and is only reported.
    """
    served = np.concatenate([g["served"] for g in gaps]) if gaps else \
        np.zeros((0,))
    if served.size:
        log(f"widest gap {float(served.max())!r} over {served.size} "
            f"served tokens ({int(np.sum(served > 0))} not the "
            f"reference's best)")
    need = sample_tokens // 4
    checks = {
        "failed": [failed, 0],
        "wrong_length": [wrong_length, 0],
        "sampled_tokens_min": [int(served.size), need],
        "gap_mean": [float(served.mean()) if served.size else None,
                     limits["gap_mean"]],
    }
    ok = (failed == 0 and wrong_length == 0 and served.size >= need
          and checks["gap_mean"][0] <= limits["gap_mean"])
    return bool(ok), checks


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------


def read_metric(name: str, rec: Dict):
    """Run the reader ``bench/metrics/<name>.py`` over the record."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def layer_record(win: Window, eng, cfg, tiles, reduction, peak) -> Dict:
    import work
    t0, t1 = win.t0, win.t1
    admitted = [r for r in win.reqs
                if r.t_first is not None and t0 <= r.t_first <= t1]
    decode_ctx = [len(r.prompt) + k for r in win.reqs
                  for k, t in enumerate(win.tok_t[r.rid])
                  if k and t0 <= t <= t1]
    return {
        "trace": reduction, "peak": peak, "slots": eng.B,
        "counters": win.counter_delta,
        "prefill_shapes": list(win.prefill_shapes),
        "decode_calls": win.decode_calls,
        "prompt_lens": [len(r.prompt) for r in admitted],
        "decode_ctx": decode_ctx,
        "model": work.Model(
            d=cfg.d_model, f=cfg.d_ff, heads=cfg.num_heads,
            kv_heads=cfg.num_kv_heads, head_dim=cfg.attn_head_dim,
            vocab=cfg.vocab_size, layers=cfg.num_layers,
            weight_bytes=2, ffn_tiles=tuple(int(t) for t in tiles)),
    }


COUNTERS = ("decode_steps", "generated_tokens", "prefill_tokens",
            "admitted")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, runtime_s: float = 0.0, require_tpu: bool = True,
        cache: bool = True, sample_tokens: int = SAMPLE_TOKENS) -> Dict:
    """Set up, measure, check; returns the result line's object.

    ``setup_s`` runs from ``t_start`` to the window's opening, less
    ``runtime_s``, the seconds the TPU runtime took to start: nothing
    of the program runs then, and it varies by seconds from process to
    process. ``require_tpu`` and ``cache`` are off, and
    ``sample_tokens`` is smaller, only in the CPU tests."""
    import jax
    import work
    phases = {"runtime": runtime_s,
              "imports": time.monotonic() - t_start - runtime_s}
    devs = check_devices(cell.chips) if require_tpu else jax.devices()
    dev = devs[0]
    peak = work.peaks(dev.device_kind) if require_tpu else None
    log(f"cell {cell.name} seed {seed} on {dev.platform} "
        f"{dev.device_kind} x{len(devs)}; compile cache "
        f"{enable_cache() if cache else 'off'}")
    compiles = [0]

    def on_event(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    conf, mix = cell.config, cell.mix
    eng, cfg, tiles = build_engine(conf, mix, seed, phases=phases)
    clock = Phases(phases)
    warm_up(eng, mix)
    clock.mark("warm_up")
    win = Window(eng, mix, seed, cell.cell.get("rate_per_s"),
                 cfg.vocab_size)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    at_open = {}

    def on_open():
        clock.mark("lead_in")
        at_open["counters"] = {k: int(eng.stats[k]) for k in COUNTERS}
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(tdir, profiler_options=opts)
            at_open["span"] = jax.profiler.TraceAnnotation("window")
            at_open["span"].__enter__()
        at_open["setup_s"] = time.monotonic() - t_start - runtime_s
        at_open["compiles"] = compiles[0]

    win.run(seconds, on_open)
    if trace:
        at_open["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    setup_s, compiles_setup = at_open["setup_s"], at_open["compiles"]
    log("set-up phases (s): " + ", ".join(
        f"{k} {v:.3f}" for k, v in phases.items()) + f"; total {setup_s:.3f}")
    win.counter_delta = {k: int(eng.stats[k]) - at_open["counters"][k]
                         for k in COUNTERS}
    e2e = win.end_to_end()
    e2e["setup_s"] = setup_s
    in_window = compiles[0] - compiles_setup
    stats = dev.memory_stats() or {}
    mem_peak = max(int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in devs[:cell.chips])
    log(f"window {win.t1 - win.t0:.3f} s: {len(win.reqs)} requests "
        f"due, {len(win.finished())} finished, {win.counts}; "
        f"{in_window} compiles inside the window, {compiles_setup} in "
        f"set-up; peak_bytes_in_use {mem_peak} (in use now "
        f"{stats.get('bytes_in_use')})")
    if win.lateness:
        late = sorted(win.lateness)
        log(f"generator lateness: median {late[len(late) // 2] * 1e3:.3f}"
            f" ms, max {late[-1] * 1e3:.3f} ms over {len(late)} arrivals")
    out_layers, breakdown, device_extra = {}, None, {}
    if trace:
        import devtrace
        reduction = devtrace.reduce_file(tdir)
        rec = layer_record(win, eng, cfg, tiles, reduction, peak)
        for m in cell.per_layer:
            v = read_metric(m["name"], rec)
            if v is not None:
                out_layers[m["name"]] = {"value": float(v),
                                         "unit": m["unit"]}
        breakdown = {"device_ops": reduction["device_ops"],
                     "idle_gaps": reduction["idle_gaps"]}
        device_extra = {"busy_s": reduction["busy_s"],
                        "window_s": reduction["window_s"]}
        log(f"trace: busy {reduction['busy_s']:.4f} s of "
            f"{reduction['window_s']:.4f} s; programs "
            f"{json.dumps(reduction['programs'])}")
        import shutil
        shutil.rmtree(tdir, ignore_errors=True)

    finished = win.finished()
    attempted = len(win.reqs)
    failed = sum(1 for r in win.reqs if r.status == "failed")
    wrong = sum(1 for r in finished
                if len(r.out_tokens) != r.max_new_tokens)
    picked = [(np.asarray(r.prompt), list(r.out_tokens))
              for r in sample(finished, seed, sample_tokens)]
    # free the program's state before the reference runs
    eng.on_token = None
    del eng, win, finished
    gc.collect()
    t_ref = time.monotonic()
    gaps = reference_gaps(conf, seed, picked)
    ok, checks = judge(gaps, cell.cell["limits"], failed, wrong,
                       sample_tokens)
    log(f"reference over {len(picked)} requests took "
        f"{time.monotonic() - t_ref:.1f} s")
    if trace:
        metrics = out_layers
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": float(e2e[k]), "unit": units[k]}
                   for k in units if k in e2e}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    device.update(device_extra)
    res = {"correct": ok, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} limit {lim!r}")
    return res
