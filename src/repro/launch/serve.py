"""Serving launcher CLI: load (or init) a model, optionally deploy SASP
(prune + INT8 + int8-KV), pick an execution path, and serve synthetic
requests through the batched engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --reduce \
      --sasp 0.5 --path packed --int8-kv --requests 8

``--reduce`` (the default) shrinks widths for CPU runs; on a chip serve
published widths cut in depth only, in the compute dtype:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b \
      --no-reduce --layers 4 --param-dtype bfloat16 --sasp 0.5 \
      --path packed

Paths (DESIGN.md §4 + §9):
  dense   — unpruned baseline.
  masked  — pruned tiles zeroed in place, matmuls stay dense.
  bsr     — BlockSparseWeight containers, gathered jnp matmul.
  kernel  — same containers through the Pallas tile-skip kernel
            (re-flattens the padded k_max × NB list per call).
  packed  — `core.deploy.deploy_packed` compact containers: sorted block
            lists + fused bias/act epilogues + fused gated-FFN kernel.
            The serving fast path.
"""
from __future__ import annotations

import argparse
import dataclasses
import re
import threading
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np
import jax

from repro.configs import SASPConfig, get_config, reduced
from repro.core.pruning import prune_params
from repro.core.sasp import bsr_overlay_from_masks, merge_overlay, \
    quantize_params
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serve.engine import Engine, Request
from repro.serve.telemetry import Telemetry, pcts_ms
from repro.train.checkpoint import CheckpointManager

PATHS = ("dense", "masked", "bsr", "kernel", "packed")


def build_serving_params(params, cfg, *, path: str, sparsity: float,
                         int8_weights: bool = False,
                         block_k: int = 128, block_n: int = 128,
                         scope: str = "ffn", verbose: bool = True,
                         mesh=None):
    """Deploy `params` for serving along one execution path. Returns
    (params, cfg) ready for the Engine. ``mesh``: TP-shard the packed
    visit lists by the mesh's 'model' axis (packed path only,
    DESIGN.md §10)."""
    assert path in PATHS, path
    if path == "dense" or sparsity <= 0:
        return params, cfg
    sasp = SASPConfig(enabled=True, block_k=block_k, block_n=block_n,
                      sparsity=sparsity, scope=scope,
                      quantize=int8_weights)
    cfg = dataclasses.replace(cfg, sasp=sasp)
    params, masks = prune_params(params, sasp)
    if verbose:
        print(f"SASP deployed: {sparsity:.0%} tile sparsity, "
              f"{len(masks)} matrices, path={path}")
    if path == "masked":
        if int8_weights:
            params = quantize_params(params, sasp)
            if verbose:
                print("weights quantized to INT8 (per-block scales)")
        return params, cfg
    if path in ("bsr", "kernel"):
        overlay = bsr_overlay_from_masks(params, masks, sasp)
        params = merge_overlay(params, overlay)
        cfg = dataclasses.replace(
            cfg, sasp=dataclasses.replace(sasp, path=path))
        return params, cfg
    # packed: compact kernel containers, built once at load time
    from repro.core.deploy import deploy_packed, packed_summary
    params, cfg = deploy_packed(params, cfg, mesh=mesh)
    if verbose:
        s = packed_summary(params)
        tp = mesh.shape.get("model", 1) if mesh is not None else 1
        shard = f", {tp}-way shard-local visit lists" if tp > 1 else ""
        print(f"packed: {s['n_packed_matrices']} matrices + "
              f"{s['n_fused_ffns']} fused FFNs, "
              f"{s['compression']:.2f}x dense bytes{shard}")
    return params, cfg


def parse_mesh(spec: Optional[str]):
    """'dp,tp' -> a (data, model) Mesh, forcing enough fake CPU devices
    when the host platform would otherwise come up short (harmless on
    real accelerators: the flag only affects the CPU platform, and it
    must be set before JAX first initializes its backends). Malformed
    specs fail HERE with a usage message, not as a downstream
    make_mesh/submesh shape error."""
    if not spec:
        return None
    m = re.fullmatch(r"\s*(\d+)\s*,\s*(\d+)\s*", spec)
    if not m:
        raise SystemExit(
            f"--mesh expects 'DP,TP' — two comma-separated positive "
            f"integers, e.g. --mesh 2,2 — got {spec!r}")
    dp, tp = int(m.group(1)), int(m.group(2))
    if dp < 1 or tp < 1:
        raise SystemExit(
            f"--mesh axes must both be >= 1, got {spec!r}")
    from repro.launch.mesh import auto_mesh, ensure_fake_cpu_devices
    ensure_fake_cpu_devices(dp * tp)
    import jax
    if len(jax.devices()) < dp * tp:
        raise SystemExit(
            f"--mesh {spec} needs {dp * tp} devices, have "
            f"{len(jax.devices())} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={dp * tp} before "
            "any jax import initializes the backend)")
    return auto_mesh((dp, tp), ("data", "model"))


def parse_buckets(spec: Optional[str], cache_len: int
                  ) -> Optional[Tuple[int, ...]]:
    """--buckets 'N' -> geometric table of N lengths topping out at
    --cache-len (distribution.sharding.prefill_bucket_table);
    --buckets 'l1,l2,…' -> explicit lengths. None/'' -> exact shapes."""
    if not spec:
        return None
    try:
        if "," in spec:
            lens = tuple(int(v) for v in spec.split(","))
        else:
            lens = int(spec)
    except ValueError:
        raise SystemExit(
            f"--buckets expects an int count (e.g. --buckets 4) or "
            f"comma-separated lengths (e.g. --buckets 32,64,128), "
            f"got {spec!r}")
    if isinstance(lens, int):
        if lens < 1:
            raise SystemExit(f"--buckets count must be >= 1, got {spec!r}")
        from repro.distribution.sharding import prefill_bucket_table
        return prefill_bucket_table(cache_len, lens)
    if not lens or any(v < 1 for v in lens):
        raise SystemExit(
            f"--buckets lengths must all be >= 1, got {spec!r}")
    if any(v > cache_len for v in lens):
        raise SystemExit(
            f"--buckets lengths must not exceed --cache-len "
            f"({cache_len}): a bucket beyond the cache can never "
            f"admit — got {spec!r}")
    return lens


def validate_kv_flags(*, kv_pages: Optional[int], kv_watermark: float,
                      kv_share: bool, kv_share_min_pages: int,
                      int8_kv: bool, draft_sparsity: Optional[float],
                      draft_k: int = 4, draft_int8: bool = False,
                      kv_dedup_every: int = 0, cache_len: int = 256):
    """Single source of truth for cross-flag KV / speculative-decode
    validation. Every serving path (--hosts frontend, --scheduler,
    solo engine) builds its engines from the same flag set, so they
    must reject the same combinations identically — the checks used to
    be scattered per-branch and drifted (a bad combo that the solo
    path rejected sailed through the frontend until an engine deep in
    a host raised). Raises SystemExit with a usage message."""
    if not 0.0 < kv_watermark <= 1.0:
        raise SystemExit(
            f"--kv-watermark must lie in (0, 1], got {kv_watermark}")
    if kv_pages is not None and kv_pages < 1:
        raise SystemExit(f"--kv-pages must be >= 1, got {kv_pages}")
    if kv_share:
        if kv_pages is None:
            raise SystemExit("--kv-share requires --kv-pages (prefix "
                             "sharing lives on the paged pool)")
        if int8_kv:
            raise SystemExit("--kv-share is incompatible with "
                             "--int8-kv: suffix prefill would attend "
                             "dequantized prefix KV and break "
                             "bit-identity (DESIGN.md §16)")
    if kv_share_min_pages < 1:
        raise SystemExit(f"--kv-share-min-pages must be >= 1, got "
                         f"{kv_share_min_pages}")
    if draft_sparsity is not None:
        if kv_pages is None:
            raise SystemExit("--draft-sparsity requires --kv-pages: "
                             "speculative drafts live on scratch pages "
                             "of the paged pool (DESIGN.md §17)")
        if int8_kv:
            raise SystemExit("--draft-sparsity is incompatible with "
                             "--int8-kv: verification attends fresh "
                             "fp KV while sequential decode attends "
                             "dequantized KV, breaking bit-identity "
                             "(DESIGN.md §17)")
        if not 0.0 < draft_sparsity < 1.0:
            raise SystemExit(f"--draft-sparsity must lie in (0, 1), "
                             f"got {draft_sparsity}")
        if draft_k < 1:
            raise SystemExit(f"--draft-k must be >= 1, got {draft_k}")
        if draft_k + 1 > cache_len:
            raise SystemExit(
                f"--draft-k {draft_k} needs a draft+verify window of "
                f"{draft_k + 1} tokens inside --cache-len "
                f"({cache_len}); shrink --draft-k")
    elif draft_int8:
        raise SystemExit("--draft-int8 modifies the drafter pack; add "
                         "--draft-sparsity S")
    if kv_dedup_every < 0:
        raise SystemExit(f"--kv-dedup-every must be >= 0, got "
                         f"{kv_dedup_every}")
    if kv_dedup_every and not (kv_pages and kv_share):
        raise SystemExit("--kv-dedup-every requires --kv-pages and "
                         "--kv-share: the dedup sweep re-links "
                         "identical resident pages through the prefix "
                         "radix (DESIGN.md §16)")


def start_metrics_reporter(summary_fn: Callable[[], dict],
                           interval: float) -> threading.Event:
    """Print ``summary_fn()`` every ``interval`` seconds from a daemon
    thread until the returned event is set (--metrics-interval)."""
    stop = threading.Event()
    if interval <= 0:
        return stop

    def loop():
        while not stop.wait(interval):
            s = summary_fn()
            print(f"metrics: {s}")

    threading.Thread(target=loop, daemon=True).start()
    return stop


def check_ranks(ranks: Optional[int], mesh, profile: str = "tp"):
    """--ranks vs the mesh's DP size: a clear usage error instead of
    the cryptic submesh-count ValueError from the scheduler."""
    if ranks is None or mesh is None:
        return
    from repro.distribution import sharding as shd
    dp = 1
    for a in shd.dp_axes(mesh, profile):
        dp *= mesh.shape[a]
    if ranks > dp:
        raise SystemExit(
            f"--ranks {ranks} exceeds the mesh's DP size {dp} "
            f"(mesh {dict(mesh.shape)}): each scheduler rank needs its "
            f"own DP slice of the mesh; drop --ranks or grow the DP "
            f"axis to >= {ranks}")
    if ranks != dp:
        raise SystemExit(
            f"--ranks {ranks} conflicts with the mesh's DP size {dp}: "
            f"under a mesh the DP axis decides the rank count; drop "
            f"--ranks")


@dataclasses.dataclass
class ServeResult:
    """What one launcher run served: the deployed params and config, the
    mesh (None without ``--mesh``) and every submitted request (each
    ends ``done``, ``rejected`` or ``failed``)."""
    params: Any
    cfg: Any
    mesh: Any
    requests: List[Request]


def serving_config(args):
    """The architecture's config, cut as the flags say: ``--reduce``
    shrinks widths (CPU runs); otherwise only ``--layers`` cuts depth
    and the published widths stay."""
    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg, layers=args.layers or 4, d_model=128,
                      vocab=512)
    elif args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    if args.param_dtype:
        cfg = dataclasses.replace(cfg, param_dtype=args.param_dtype)
    if args.int8_kv:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    return cfg


def load_model(args):
    """(params, cfg) for ``args``: :func:`serving_config` with random
    weights from a fixed seed (or restored from ``--ckpt-dir``)."""
    cfg = serving_config(args)
    # one program: the weights are made in their own dtype on the
    # device, with no float32 copy of a whole stack in between
    params = jax.jit(lm.init_params, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        like = jax.eval_shape(lambda: {"params": params})
        state, _ = mgr.restore(like)
        params = state["params"]
        print(f"restored step {mgr.latest_step()} from {args.ckpt_dir}")
    return params, cfg


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--reduce", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="shrink widths for a CPU run (reduced(): "
                         "d_model 128, vocab 512, float32); "
                         "--no-reduce serves the published widths")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model to this many layers and change "
                         "nothing else (default: 4 with --reduce, the "
                         "published depth without)")
    ap.add_argument("--param-dtype", choices=("float32", "bfloat16"),
                    default=None,
                    help="dtype the weights are made and served in "
                         "(default: the config's; bfloat16 serves "
                         "qwen3-32b in its compute dtype)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from a CheckpointManager dir")
    ap.add_argument("--sasp", type=float, default=0.0)
    ap.add_argument("--path", choices=PATHS, default="masked",
                    help="SASP execution path (ignored when --sasp 0)")
    ap.add_argument("--scope", choices=("ffn", "all"), default="ffn")
    ap.add_argument("--int8-weights", action="store_true")
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="serve under a (data, model) mesh: caches and "
                         "decode state carry NamedShardings, packed "
                         "visit lists are TP-sharded per output-block "
                         "shard (e.g. --mesh 1,2)")
    ap.add_argument("--scheduler", action="store_true",
                    help="serve through the sharded request scheduler "
                         "(DESIGN.md §11): admission-controlled queue + "
                         "one engine shard per DP rank + continuous "
                         "batching")
    ap.add_argument("--slots-per-rank", type=int, default=None,
                    help="KV-cache slots owned by each DP-rank engine "
                         "shard (default: --slots)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission control: reject submissions once "
                         "this many requests are waiting beyond free "
                         "slot capacity (default: unbounded)")
    ap.add_argument("--admission", choices=("fcfs", "sjf", "edf"),
                    default="fcfs",
                    help="queue policy: fcfs (arrival order), sjf "
                         "(shortest remaining work first), or edf "
                         "(earliest effective deadline first — the QoS "
                         "policy, DESIGN.md §12)")
    ap.add_argument("--aging", type=float, default=0.0,
                    help="anti-starvation credit per second waited "
                         "(seconds of deadline for edf, tokens for "
                         "sjf); 0 = pure EDF/SJF")
    ap.add_argument("--preempt", action="store_true",
                    help="interactive-class requests may evict the "
                         "worst-deadline batch-class decode at step "
                         "granularity (resume is bit-identical)")
    ap.add_argument("--preempt-mode", choices=("kv", "reprefill"),
                    default="kv",
                    help="preempted-slot resume: 'kv' snapshots the "
                         "slot's cache rows, 'reprefill' re-prefills "
                         "prompt + generated tokens")
    ap.add_argument("--interactive-every", type=int, default=0,
                    help="mark every Nth synthetic request "
                         "interactive-class (0 = all batch)")
    ap.add_argument("--shed", choices=("count", "deadline"),
                    default="count",
                    help="overload shedding once --max-queue overflows: "
                         "'count' rejects the newcomer, 'deadline' "
                         "evicts the waiting request least likely to "
                         "meet its deadline (batch before interactive)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="paged KV cache (DESIGN.md §13): device page "
                         "pool size per rank engine; slots share pages "
                         "through block tables and become "
                         "oversubscribable (default: contiguous "
                         "per-slot rings)")
    ap.add_argument("--kv-page-len", type=int, default=None,
                    help="page length in tokens — must be a multiple "
                         "of the SASP tile and divide --cache-len "
                         "(default: tile-aligned automatic)")
    ap.add_argument("--kv-watermark", type=float, default=1.0,
                    help="high-watermark fraction of --kv-pages that "
                         "may stay resident; allocations beyond it "
                         "spill cold (preempted) pages to host RAM")
    ap.add_argument("--kv-host-pool", type=int, default=0,
                    help="host-RAM spill pool size in pages (0 = no "
                         "spill; cold pages drop to re-prefill resume "
                         "under pressure instead)")
    ap.add_argument("--kv-share", action="store_true",
                    help="prefix sharing over the paged pool "
                         "(DESIGN.md §16): admission maps a prompt's "
                         "full pages onto identical already-resident "
                         "pages (refcounted, copy-on-write) and "
                         "prefills only the suffix; requires "
                         "--kv-pages, incompatible with --int8-kv")
    ap.add_argument("--kv-share-min-pages", type=int, default=1,
                    help="minimum whole pages a prompt must match "
                         "before sharing is taken (shorter matches "
                         "prefill from scratch)")
    ap.add_argument("--draft-sparsity", type=float, default=None,
                    help="self-speculative decoding (DESIGN.md §17): "
                         "repack the SAME weights at this higher tile "
                         "sparsity as a cheap drafter; greedy streams "
                         "stay bit-identical (every emitted token is a "
                         "target argmax). Requires --kv-pages, "
                         "incompatible with --int8-kv")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="speculation depth: drafter tokens proposed "
                         "per verify step (draft-k/verify-1)")
    ap.add_argument("--draft-int8", action="store_true",
                    help="quantize the drafter pack's weights to INT8 "
                         "on top of --draft-sparsity (drafter fidelity "
                         "only moves acceptance rate, never outputs)")
    ap.add_argument("--draft-interactive", action="store_true",
                    help="let interactive-SLO requests speculate too "
                         "(default: batch-class only — speculation "
                         "trades per-step latency for throughput)")
    ap.add_argument("--kv-dedup-every", type=int, default=0,
                    help="cross-request dedup sweep cadence in decode "
                         "steps (0 = off): re-link identical "
                         "already-resident pages that missed "
                         "admission-time sharing; requires --kv-share")
    ap.add_argument("--buckets", default=None,
                    help="prefill shape bucketing: an int count builds "
                         "a geometric table up to --cache-len; "
                         "comma-separated lengths give it explicitly. "
                         "Bounds jitted-admission compiles at "
                         "O(buckets) under diverse prompt lengths")
    ap.add_argument("--stream", action="store_true",
                    help="serve through the per-token streaming "
                         "iterator and print tokens as they retire")
    ap.add_argument("--drain", action="store_true",
                    help="drain-batch baseline: admit only when every "
                         "slot is free (A/B control for continuous "
                         "batching)")
    ap.add_argument("--ranks", type=int, default=None,
                    help="engine shards without a mesh (testing); with "
                         "--mesh the DP axis must agree (clear error "
                         "otherwise)")
    ap.add_argument("--hosts", type=int, default=None,
                    help="serve through the fault-tolerant cluster "
                         "frontend (DESIGN.md §14) over N in-process "
                         "hosts, each its own sharded scheduler: "
                         "heartbeat health checks, bounded retries with "
                         "backoff, watchdog, graceful drain")
    ap.add_argument("--retries", type=int, default=2,
                    help="re-submissions after a host failure before a "
                         "request fails for real (frontend only)")
    ap.add_argument("--backoff", type=float, default=0.05,
                    help="retry backoff base seconds: attempt k waits "
                         "base*2^(k-1), capped, with seeded jitter")
    ap.add_argument("--timeout", type=float, default=None,
                    help="per-request wall-clock watchdog seconds "
                         "(default: none) — an overdue request is "
                         "cancelled out of its host and failed")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    help="graceful-shutdown bound: drain stops "
                         "admission and serves in-flight work at most "
                         "this many seconds before cutting stragglers")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault injection into the "
                         "frontend's hosts, e.g. "
                         "'kill:0@12,raise:1@3,drop-hb:0@5x3,"
                         "slow:1@0.02,seed:7' (serve/chaos.py grammar; "
                         "requires --hosts)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="arm the span tracer and write a Chrome "
                         "trace-event JSON of the whole run — load it "
                         "at ui.perfetto.dev or chrome://tracing "
                         "(DESIGN.md §18)")
    ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                    help="write Prometheus text exposition of every "
                         "registered counter/gauge/histogram at exit")
    ap.add_argument("--metrics-interval", type=float, default=0.0,
                    help="print an aggregated metrics summary every N "
                         "seconds while serving (0 = off)")
    return ap


def main(argv: Optional[Sequence[str]] = None, *,
         model: Optional[Tuple[Any, Any]] = None) -> ServeResult:
    """Serve one synthetic batch as the flags say. Exits non-zero when
    any request ends failed — a rank the scheduler contained, or a host
    the frontend gave up on, is still a failed run. ``model``: (params,
    cfg) made by the caller (from ``load_model``) to serve in place of
    loading them here."""
    args = build_parser().parse_args(argv)
    if args.layers is not None and args.layers < 1:
        raise SystemExit(f"--layers must be >= 1, got {args.layers}")

    # BEFORE any backend-initializing jax call: may set XLA_FLAGS
    mesh = parse_mesh(args.mesh)
    check_ranks(args.ranks, mesh)
    if args.hosts is not None and args.hosts < 1:
        raise SystemExit(f"--hosts must be >= 1, got {args.hosts}")
    if args.hosts and mesh is not None:
        raise SystemExit(
            "--hosts serves in-process hosts without a mesh; drop "
            "--mesh (per-host meshes are a multi-process deployment "
            "concern — see tests/dist_worker.py frontend_host)")
    if args.chaos and not args.hosts:
        raise SystemExit("--chaos drives the cluster frontend's fault "
                         "hooks; add --hosts N")
    if args.chaos:
        from repro.serve.chaos import parse_chaos_spec
        try:
            chaos_cfg = parse_chaos_spec(args.chaos)
        except ValueError as e:
            raise SystemExit(f"--chaos: {e}")
    else:
        chaos_cfg = None
    buckets = parse_buckets(args.buckets, args.cache_len)
    # one validator for all three serving paths (frontend / scheduler
    # / solo) — they must reject the same flag combos identically
    validate_kv_flags(
        kv_pages=args.kv_pages, kv_watermark=args.kv_watermark,
        kv_share=args.kv_share,
        kv_share_min_pages=args.kv_share_min_pages,
        int8_kv=args.int8_kv, draft_sparsity=args.draft_sparsity,
        draft_k=args.draft_k, draft_int8=args.draft_int8,
        kv_dedup_every=args.kv_dedup_every, cache_len=args.cache_len)

    enable_compile_cache()
    params, cfg = model if model is not None else load_model(args)
    params, cfg = build_serving_params(
        params, cfg, path=args.path, sparsity=args.sasp,
        int8_weights=args.int8_weights, scope=args.scope, mesh=mesh)
    if mesh is not None:
        print(f"mesh: {dict(mesh.shape)} over {len(mesh.devices.flat)} "
              "devices")

    rng = np.random.default_rng(0)
    every = args.interactive_every
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=(rng.integers(8, 48),))
                    .astype(np.int32),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature,
                    eos_id=args.eos_id,
                    slo=("interactive" if every and i % every == 0
                         else "batch"))
            for i in range(args.requests)]

    def drive(run_fn, stream_fn) -> Sequence[Request]:
        """--stream: print tokens as they retire; else run to done."""
        if not args.stream:
            return run_fn(reqs)
        n = 0
        for rid, tok in stream_fn(reqs):
            if n < 12:
                print(f"  stream: req {rid} += {tok}")
            n += 1
        print(f"  … streamed {n} tokens incrementally")
        return [r for r in reqs if r.done]

    if args.hosts:
        from repro.serve.chaos import ChaosMonkey
        from repro.serve.frontend import ClusterFrontend, \
            FrontendConfig, make_local_hosts
        from repro.serve.scheduler import SchedulerConfig
        hosts = make_local_hosts(
            params, cfg, hosts=args.hosts, ranks=args.ranks or 1,
            chaos=ChaosMonkey(chaos_cfg) if chaos_cfg else None,
            trace=bool(args.trace_out),
            sched=SchedulerConfig(
                slots_per_rank=args.slots_per_rank or args.slots,
                cache_len=args.cache_len, max_queue=args.max_queue,
                policy=args.admission, drain=args.drain,
                aging=args.aging, preempt=args.preempt,
                preempt_mode=args.preempt_mode, buckets=buckets,
                shed=args.shed, kv_pages=args.kv_pages,
                kv_page_len=args.kv_page_len,
                kv_watermark=args.kv_watermark,
                kv_host_pages=args.kv_host_pool,
                kv_share=args.kv_share,
                kv_share_min_pages=args.kv_share_min_pages,
                draft_sparsity=args.draft_sparsity,
                draft_k=args.draft_k, draft_int8=args.draft_int8,
                draft_interactive=args.draft_interactive,
                kv_dedup_every=args.kv_dedup_every))
        fe = ClusterFrontend(hosts, FrontendConfig(
            retries=args.retries, backoff_base=args.backoff,
            request_timeout=args.timeout,
            drain_timeout=args.drain_timeout))
        if args.stream:
            n_stream = [0]

            def _tok(req, tok):
                if n_stream[0] < 12:
                    print(f"  stream: req {req.rid} += {tok}")
                n_stream[0] += 1
            fe.on_token = _tok
        trace_writer, prom_fn = fe.write_trace, fe.prometheus

        def cluster_summary():
            out: dict = {}
            for h in hosts:
                cs = h.telemetry.registry.summary()["counters"]
                for k, v in cs.items():
                    out[k] = out.get(k, 0) + v
            return out

        stop_rep = start_metrics_reporter(cluster_summary,
                                          args.metrics_interval)
        t0 = time.time()
        done = fe.run(reqs)
        drained, clean = fe.drain()     # bounded graceful shutdown
        done += drained
        dt = time.time() - t0
        stop_rep.set()
        fe.close()
        if args.stream:
            print(f"  … streamed {n_stream[0]} tokens incrementally")
        st = fe.stats()
        print(f"frontend: {st['hosts']} host(s) "
              f"({st['healthy']} healthy, {st['suspect']} suspect, "
              f"{st['dead']} dead), {st['done']} done, "
              f"{st['failed']} failed, {st['rejected']} rejected, "
              f"{st['retries']} retries, "
              f"{st['deduped_tokens']} deduped tokens, "
              f"drain {'clean' if clean else 'cut stragglers'}")
        for h_st in st["per_host"]:
            print(f"  host {h_st['host']}: steps={h_st['steps']} "
                  f"live_ranks={h_st.get('live_ranks', 0)}/"
                  f"{h_st.get('ranks', 0)} "
                  f"accepted={h_st.get('accepted', 0)} "
                  f"requeued={h_st.get('requeued', 0)}")
    elif args.scheduler:
        from repro.serve.scheduler import SchedulerConfig, \
            ShardedScheduler
        sched = ShardedScheduler(
            params, cfg, mesh=mesh, ranks=args.ranks,
            telemetry=Telemetry(trace=bool(args.trace_out)),
            sched=SchedulerConfig(
                slots_per_rank=args.slots_per_rank or args.slots,
                cache_len=args.cache_len, max_queue=args.max_queue,
                policy=args.admission, drain=args.drain,
                aging=args.aging, preempt=args.preempt,
                preempt_mode=args.preempt_mode, buckets=buckets,
                shed=args.shed, kv_pages=args.kv_pages,
                kv_page_len=args.kv_page_len,
                kv_watermark=args.kv_watermark,
                kv_host_pages=args.kv_host_pool,
                kv_share=args.kv_share,
                kv_share_min_pages=args.kv_share_min_pages,
                draft_sparsity=args.draft_sparsity,
                draft_k=args.draft_k, draft_int8=args.draft_int8,
                draft_interactive=args.draft_interactive,
                kv_dedup_every=args.kv_dedup_every))
        trace_writer = sched.telemetry.write_trace
        prom_fn = sched.telemetry.prometheus
        stop_rep = start_metrics_reporter(
            lambda: sched.telemetry.registry.summary()["counters"],
            args.metrics_interval)
        t0 = time.time()
        done = drive(sched.run, sched.stream)
        dt = time.time() - t0
        stop_rep.set()
        st = sched.stats()
        print(f"scheduler: {st['ranks']} rank(s), "
              f"{st['accepted']}/{st['submitted']} admitted "
              f"({st['rejected']} rejected, {st['failed']} failed, "
              f"{st['preemptions']} preempted), "
              f"policy={args.admission}"
              f"{', drain baseline' if args.drain else ''}")
        for r_st in st["per_rank"]:
            print(f"  rank stats: {r_st}")
        if every:
            for klass in ("interactive", "batch"):
                lats = sorted(r.latency for r in done
                              if r.slo == klass and r.latency)
                if lats:
                    p50, p95 = pcts_ms(lats)
                    print(f"  {klass:12s}: n={len(lats)} "
                          f"p50={p50:.0f}ms p95={p95:.0f}ms")
        for klass, d in st.get("ttft", {}).items():
            print(f"  ttft {klass:12s}: n={d['count']} "
                  f"p50={d['p50_ms']:.1f}ms p95={d['p95_ms']:.1f}ms")
    else:
        eng = Engine(params, cfg, batch_slots=args.slots,
                     cache_len=args.cache_len, mesh=mesh,
                     buckets=buckets, kv_pages=args.kv_pages,
                     kv_page_len=args.kv_page_len,
                     kv_watermark=args.kv_watermark,
                     kv_host_pages=args.kv_host_pool,
                     kv_share=args.kv_share,
                     kv_share_min_pages=args.kv_share_min_pages,
                     draft_sparsity=args.draft_sparsity,
                     draft_k=args.draft_k, draft_int8=args.draft_int8,
                     draft_interactive=args.draft_interactive,
                     kv_dedup_every=args.kv_dedup_every,
                     telemetry=Telemetry(trace=bool(args.trace_out)))
        trace_writer = eng.telemetry.write_trace
        prom_fn = eng.telemetry.prometheus
        stop_rep = start_metrics_reporter(
            lambda: eng.telemetry.registry.summary()["counters"],
            args.metrics_interval)
        t0 = time.time()
        done = drive(eng.run, eng.stream)
        dt = time.time() - t0
        stop_rep.set()
        if args.draft_sparsity is not None:
            st = eng.stats
            drafted = st["spec_draft_tokens"]
            acc = st["spec_accepted_tokens"]
            print(f"speculative: {st['spec_rounds']} rounds, "
                  f"{acc}/{max(drafted, 1)} drafts accepted "
                  f"({acc / max(drafted, 1):.0%}), "
                  f"{st['spec_fallbacks']} fallbacks")
        mem = eng.memory_stats()
        if mem is not None:
            print(f"paged KV: {mem.device_pages} device pages × "
                  f"{eng.pool.page_len} tokens, {mem.spills} spills, "
                  f"{mem.faults} faults, {mem.drops} drops")
            if args.kv_share:
                print(f"prefix sharing: {mem.prefix_hits} hits, "
                      f"{mem.prefix_pages_reused} pages reused, "
                      f"{eng.stats['prefill_tokens_skipped']} prefill "
                      f"tokens skipped, {mem.cow_copies} COW copies")
    if args.trace_out:
        n_ev = trace_writer(args.trace_out)
        print(f"trace: {n_ev} events -> {args.trace_out} "
              "(load at ui.perfetto.dev)")
    if args.metrics_dump:
        with open(args.metrics_dump, "w", encoding="utf-8") as fh:
            fh.write(prom_fn())
        print(f"metrics -> {args.metrics_dump}")
    toks = sum(len(r.out_tokens) for r in done)
    print(f"{len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks/max(dt,1e-9):.1f} tok/s, "
          f"{dt/max(toks,1)*1e3:.0f} ms/token)")
    for r in sorted(done, key=lambda r: r.rid)[:3]:
        print(f"  req {r.rid}: prompt[{len(r.prompt)}] -> "
              f"{r.out_tokens[:10]}…")
    failed = [r for r in reqs if r.status == "failed"]
    if failed:
        raise SystemExit(
            f"{len(failed)} of {len(reqs)} requests failed; first: "
            f"req {failed[0].rid}: {failed[0].error}")
    return ServeResult(params, cfg, mesh, reqs)


if __name__ == "__main__":
    main()
