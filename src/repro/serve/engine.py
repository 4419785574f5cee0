"""Batched serving engine: prefill + greedy/temperature decode over a
fixed ring-cache budget, with slot-based continuous batching.

The engine keeps B slots. Each slot holds one sequence (its own cache
rows — caches are batched pytrees, so slot i is index i of every cache
leaf). Finished sequences free their slot; queued requests prefill into
free slots. Decode steps run over the full batch every iteration (idle
slots are masked). SASP-deployed weights (masked / BSR / kernel /
packed paths) serve through the same code — the paper's tile-skip
savings apply to every decode GEMM.

Serving fast path (DESIGN.md §9):

* **Batched multi-slot prefill** — when several slots free up at once,
  their prompts prefill in ONE left-padded forward pass (per-batch
  positions mask the pad columns out of attention and out of the KV
  cache). Attention-only stacks only; hybrid/SSM stacks fall back to
  per-request prefill (a padded prefix would corrupt the recurrent
  state).
* **Prefill shape bucketing** (DESIGN.md §12) — ``buckets=(…)`` pads
  every admission group to a fixed group size (all B slots; rows for
  slots not being admitted are masked out of the cache scatter) and
  pads the padded length S up to the smallest bucket ≥ S, so the
  jitted admission compiles O(len(buckets)) programs instead of
  O(distinct prompt lengths × group sizes) under diverse traffic.
  Greedy streams are bit-identical to the unbucketed path: extra pad
  columns carry negative positions (masked from attention, written to
  disjoint ring slots with pos = -1) and masked rows rewrite each
  untouched slot's existing cache rows verbatim.
* **On-device sampling** — greedy argmax and temperature sampling
  (``jax.random.categorical``) run inside the jitted decode step, so
  only the sampled token ids (B int32) and done flags cross to the
  host. The full (B, vocab) logits never leave the device.
* **Device-side length/EOS masking** — per-slot remaining-token budgets
  and EOS ids live in device arrays; the decode step returns done flags
  and zeros the sampled token of idle slots.
* **Mesh-native serving** (DESIGN.md §10) — ``Engine(..., mesh=...)``
  places params (incl. TP-sharded packed containers) and KV caches with
  NamedShardings and runs every prefill/decode under the active-mesh
  context, so the shard_map packed drivers and SDPA/TP paths engage.
  Greedy streams are bit-identical to the single-device packed path.

Slot lifecycle (DESIGN.md §11): each slot moves FREE -> PREFILL ->
DECODE -> FREE. PREFILL is transient inside :meth:`Engine._admit` (the
prompt's cache rows are written and the first token sampled in the same
host call); from the next :meth:`Engine.step` on the slot participates
in the batched decode, where the ``active`` mask hides FREE slots —
slots admitted at different times decode side by side. Under
``admission="continuous"`` (default) a slot freed by EOS/budget is
refilled from the queue at the very next step; ``admission="drain"`` is
the classic batch-inference baseline that only admits when EVERY slot
is free (used as the benchmark control for continuous batching).

Preemption (DESIGN.md §12): :meth:`Engine.preempt_slot` moves a
DECODE-state request back to the queue at step granularity. Two resume
modes: ``keep_kv=True`` snapshots the slot's cache rows (one on-device
gather) and resume restores them with one scatter — exact by
construction; ``keep_kv=False`` drops the KV and resume RE-PREFILLS
``prompt + out_tokens[:-1]`` through the normal admission path (no new
token is sampled — the preempted request's last token was already
emitted), trading a prefill pass for cache memory. Either way the
greedy stream across a preempt/resume cycle is bit-identical to an
uninterrupted decode. Requests carry a ``status`` field
(new/queued/running/done/failed/rejected) so schedulers and callers
observe the lifecycle.

Paged KV (DESIGN.md §13): ``Engine(..., kv_pages=N)`` replaces the
per-slot contiguous ring with a shared device page pool plus per-slot
block tables (``serve/memory.py``): decode gathers each slot's pages
into the exact ring layout (streams bit-identical to the contiguous
cache), admission allocates just the prompt's pages and decode grows
by one page at a boundary crossing, EOS frees. Slots become
oversubscribable: admission defers (instead of pinning a full ring)
when the pool is exhausted, preemption unmaps pages instead of copying
a snapshot, and a high-watermark policy spills cold (preempted) pages
to a host-RAM pool, faulting them back on resume.

Streaming: ``Engine.on_token`` (a ``(request, token) -> None`` sink) is
called for every token the moment it is sampled — prefill first tokens
and decode tokens alike; ``Engine.stream(requests)`` wraps it as a
``(rid, token)`` iterator. The sharded scheduler fans the same sink
across its ranks (``serve/scheduler.py``).

One Engine is one *engine shard*: in the sharded scheduler
(``serve/scheduler.py``) each DP rank owns an Engine whose caches —
hence slots — live on that rank's submesh, so ranks serve independent
traffic.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import MIXER_ATTN, ModelConfig
from repro.models import lm
from repro.serve.telemetry import Telemetry

ADMISSION_MODES = ("continuous", "drain")
SLO_CLASSES = ("interactive", "batch")
# request lifecycle states surfaced on Request.status
STATUSES = ("new", "queued", "running", "done", "failed", "rejected")


@dataclass(eq=False)                    # identity semantics: a Request
class Request:                          # is a mutable in-flight object
    rid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0        # 0 = greedy
    eos_id: Optional[int] = None    # stop token (device-side check)
    # QoS (DESIGN.md §12): SLO class + latency target. ``deadline`` is
    # RELATIVE seconds from submission (None = the scheduler's default
    # for the class); the scheduler stamps the absolute ``t_deadline``.
    slo: str = "batch"              # "interactive" | "batch"
    deadline: Optional[float] = None
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    status: str = "new"             # see STATUSES
    error: Optional[str] = None     # set when status == "failed"
    # serving metadata (filled by Engine / ShardedScheduler)
    rank: Optional[int] = None      # engine shard that served the request
    t_submit: Optional[float] = None   # time.monotonic() at submission
    t_first: Optional[float] = None    # first token sampled (prefill)
    t_done: Optional[float] = None     # retired
    t_deadline: Optional[float] = None  # absolute monotonic deadline
    preemptions: int = 0            # times preempted back to the queue
    requeues: int = 0               # times evacuated off a dead rank
    attempts: int = 0               # frontend retry count (serve/frontend)
    # engine-internal resume state (set by preempt_slot)
    _resume_pos: Optional[int] = field(default=None, repr=False)
    _kv: Optional[object] = field(default=None, repr=False)

    @property
    def latency(self) -> Optional[float]:
        """Submit-to-retire seconds (None until both stamps exist)."""
        if self.t_submit is None or self.t_done is None:
            return None
        return self.t_done - self.t_submit

    def cost_estimate(self) -> int:
        """Admission-policy key: total tokens this request still needs
        (prompt prefill + remaining decode budget)."""
        return len(self.prompt) + self.max_new_tokens - len(self.out_tokens)

    def mark_resumable(self):
        """Arm the re-prefill resume path off the emitted-token snapshot
        (``out_tokens`` IS the resumable state — every token the request
        has streamed so far): the next admission re-prefills
        ``prompt + out_tokens[:-1]`` and decode continues the stream
        exactly where it stopped, with no token resampled. Any KV
        snapshot is dropped (it may live on a dead rank's devices).
        No-op for requests with nothing emitted yet — a fresh prefill is
        already exact. Used when a request is moved across engines,
        ranks, or hosts (scheduler requeue-on-failure, frontend retry)."""
        self._kv = None
        self._resume_pos = (len(self.prompt) + len(self.out_tokens) - 1
                            if self.out_tokens else None)


# Engine counter keys, declared (declare-if-absent) into the telemetry
# registry scope for this engine's rank. The analyzer's
# TELEMETRY-DECLARED pass checks every stats[...] write in serve/
# against repro.serve.telemetry.DECLARED_STATS.
_STAT_KEYS = ("decode_steps", "admitted",
              "prefill_tokens", "prefill_tokens_skipped",
              "reprefill_tokens", "prefill_computed_tokens",
              "generated_tokens",
              "continuous_refills", "preemptions",
              "resumes", "failed", "requeued",
              "cancelled", "deaths",
              "spec_rounds", "spec_draft_tokens",
              "spec_accepted_tokens", "spec_fallbacks")


def profiler_span(name: str, **args) -> TraceAnnotation:
    """The tracer's profiler sink (DESIGN.md §18): every engine and
    page-pool span also enters the JAX profiler's trace, on the clock
    of the device's events, as ``engine.<name>``."""
    return TraceAnnotation("engine." + name, **args)


def named_jit(fn: Callable, *bound) -> Callable:
    """``jax.jit`` of ``fn`` with its leading arguments ``bound``,
    compiled under ``fn``'s own name: a bare ``partial`` compiles as
    ``jit__unknown``, which no trace reader can tell apart."""
    p = partial(fn, *bound)
    p.__name__ = fn.__name__
    return jax.jit(p)


def _exec_path_label(params, cfg: ModelConfig) -> str:
    """The execution-path label this engine's decode tokens are
    credited to (telemetry per-path tok/s gauges, ROADMAP item 4):
    dense / masked / bsr / kernel / packed / int8. Resolved once at
    construction — a pure host-side walk of the param tree for the
    packed-container markers (``deploy_packed`` sets path="kernel" and
    replaces the BSR overlay with ``sasp_packed``/``sasp_fused``)."""
    s = cfg.sasp
    if not getattr(s, "enabled", False):
        return "dense"
    if getattr(s, "quantize", False):
        return "int8"

    def has_packed(p) -> bool:
        if isinstance(p, dict):
            return ("sasp_packed" in p or "sasp_fused" in p
                    or any(has_packed(v) for v in p.values()))
        if isinstance(p, (list, tuple)):
            return any(has_packed(v) for v in p)
        return False

    if s.path == "kernel" and has_packed(params):
        return "packed"
    return s.path


def _sample_tokens(logits: jnp.ndarray, key, temps: jnp.ndarray
                   ) -> jnp.ndarray:
    """logits: (B, V) -> (B,) int32. Greedy where temp <= 0, else
    categorical at logits/temp. Runs on device."""
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    t = jnp.maximum(temps, 1e-6)[:, None]
    keys = jax.random.split(key, lg.shape[0])
    samp = jax.vmap(jax.random.categorical)(keys, lg / t).astype(jnp.int32)
    return jnp.where(temps > 0, samp, greedy)


def _gather_slot_rows(caches, slot):
    return jax.tree.map(lambda leaf: leaf[:, slot], caches)


def _scatter_slot_rows(caches, saved, slot):
    return jax.tree.map(lambda leaf, s: leaf.at[:, slot].set(s), caches,
                        saved)


class Engine:
    def __init__(self, params, cfg: ModelConfig, *, batch_slots: int = 4,
                 cache_len: int = 512, rng_seed: int = 0, mesh=None,
                 profile: str = "tp", admission: str = "continuous",
                 rank: int = 0,
                 buckets: Optional[Sequence[int]] = None,
                 kv_pages: Optional[int] = None,
                 kv_page_len: Optional[int] = None,
                 kv_watermark: float = 1.0,
                 kv_host_pages: int = 0,
                 kv_share: bool = False,
                 kv_share_min_pages: int = 1,
                 draft_sparsity: Optional[float] = None,
                 draft_k: int = 4,
                 draft_int8: bool = False,
                 draft_interactive: bool = False,
                 kv_dedup_every: int = 0,
                 telemetry: Optional[Telemetry] = None):
        assert admission in ADMISSION_MODES, admission
        self.admission = admission
        self.rank = rank
        self.dead = False               # set by the scheduler on a raise
        # telemetry (DESIGN.md §18): counters live in the registry's
        # per-rank scope behind the same mapping surface the ad-hoc
        # stats dict had; declare-if-absent keeps values across a
        # revive_rank rebuild against a shared Telemetry. A private
        # default (tracing off) keeps solo engines zero-config.
        self.telemetry = telemetry if telemetry is not None \
            else Telemetry()
        self._trace = self.telemetry.tracer
        self._trace.annotate = profiler_span
        self.stats = self.telemetry.engine_stats(rank) \
            .declare(_STAT_KEYS)
        self.mesh = mesh
        self.profile = profile
        if mesh is not None:
            from repro.distribution import sharding as shd
            psh = shd.param_shardings(cfg, jax.eval_shape(lambda: params),
                                      mesh, profile)
            params = jax.device_put(params, psh)
        self.params = params
        self.cfg = cfg
        self.B = batch_slots
        self.cache_len = cache_len
        # prefill length buckets (sorted, ≤ cache_len); None = exact
        # shapes (the pre-bucketing behavior, bit-identical programs)
        self.buckets: Optional[Tuple[int, ...]] = None
        if buckets:
            bs = tuple(sorted({int(b) for b in buckets}))
            if bs[0] < 1 or bs[-1] > cache_len:
                raise ValueError(
                    f"prefill buckets must lie in [1, cache_len="
                    f"{cache_len}], got {bs} — a bucket beyond the "
                    f"cache can never admit")
            self.buckets = bs
        self._attn_only = all(m == MIXER_ATTN
                              for m in cfg.layer_mixer_kinds())
        # paged KV (DESIGN.md §13): shared page pool + block tables
        # instead of per-slot contiguous rings
        self.pool = None
        if kv_share and not kv_pages:
            raise ValueError(
                "kv_share requires the paged KV pool (kv_pages) — "
                "contiguous rings have no pages to share")
        self.kv_share_min_pages = max(1, int(kv_share_min_pages))
        # rid -> prefix tokens matched at admission (prefill skips them)
        self._shared_tokens: dict = {}
        if kv_pages:
            from repro.serve.memory import PagedKVPool
            self.pool = PagedKVPool(
                params, cfg, cache_len=cache_len,
                device_pages=kv_pages, page_len=kv_page_len,
                watermark=kv_watermark, host_pages=kv_host_pages,
                mesh=mesh, profile=profile, share=kv_share,
                telemetry=self.telemetry)
            self.caches = None
        else:
            self.caches = lm.init_caches(params, cfg, batch_slots,
                                         cache_len)
            if mesh is not None:
                from repro.distribution import sharding as shd
                csh = shd.cache_shardings(
                    cfg, mesh, batch_slots,
                    jax.eval_shape(lambda: self.caches))
                self.caches = jax.device_put(self.caches, csh)
        self.pos = np.zeros((batch_slots,), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.queue: List[Request] = []
        self._finished_at_admission: List[Request] = []
        self.on_token: Optional[Callable[[Request, int], None]] = None
        self._key = jax.random.PRNGKey(rng_seed)
        if self.pool is not None:
            self._decode = named_jit(self._paged_decode_step, cfg,
                                     self.pool.NB, self.pool.page_len)
            self._prefill = named_jit(self._paged_prefill_write, cfg,
                                      cache_len)
            self._prefill_past = named_jit(
                self._paged_prefill_past_write, cfg)
        else:
            self._decode = named_jit(self._decode_step, cfg)
            self._prefill = named_jit(self._prefill_and_write, cfg,
                                      cache_len)
        self._sample = jax.jit(_sample_tokens)
        # preemption resume: one-gather snapshot / one-scatter restore of
        # a slot's cache rows (slot index is traced — no per-slot
        # recompilation). Paged engines unmap pages instead (no copy).
        self._snap = jax.jit(_gather_slot_rows)
        self._restore = jax.jit(_scatter_slot_rows)
        # self-speculative decoding (DESIGN.md §17): the SAME weights
        # re-pruned at a higher sparsity (optionally int8) draft k
        # tokens per round into scratch pages; one full-fidelity verify
        # pass accepts a prefix of them. Greedy exactness never rests
        # on the drafter — every emitted token is a target argmax.
        self.draft_sparsity = draft_sparsity
        self.draft_k = int(draft_k)
        self.draft_interactive = bool(draft_interactive)
        self._draft = None
        if draft_sparsity is not None:
            if self.pool is None:
                raise ValueError(
                    "speculative decoding (draft_sparsity) requires "
                    "the paged KV pool (kv_pages) — draft tokens live "
                    "on scratch pages")
            if getattr(cfg, "kv_quant", False):
                raise ValueError(
                    "speculative decoding is incompatible with "
                    "kv_quant: the verify pass attends fresh fp "
                    "suffix K/V while sequential decode attends "
                    "dequantized int8 entries, breaking the "
                    "bit-identity contract")
            if self.draft_k < 1:
                raise ValueError(f"draft_k={draft_k} must be >= 1")
            if self.draft_k + 1 > cache_len:
                raise ValueError(
                    f"draft_k={draft_k} needs k+1 <= cache_len="
                    f"{cache_len}: a round's write range must fit the "
                    f"ring without self-overlap")
            from repro.core.deploy import draft_pack
            dparams, dcfg = draft_pack(
                self.params, cfg, sparsity=float(draft_sparsity),
                quantize=bool(draft_int8))
            if mesh is not None:
                from repro.distribution import sharding as shd
                dsh = shd.param_shardings(
                    dcfg, jax.eval_shape(lambda: dparams), mesh,
                    profile)
                dparams = jax.device_put(dparams, dsh)
            self._draft = (dparams, dcfg)
            self._draft_decode = named_jit(
                self._paged_decode_step, dcfg, self.pool.NB,
                self.pool.page_len)
            self._verify = named_jit(self._paged_spec_verify, cfg)
        # opportunistic cross-request dedup (ROADMAP item 1 leftover):
        # re-link identical already-resident pages every N steps
        self.kv_dedup_every = max(0, int(kv_dedup_every))
        if self.kv_dedup_every and (self.pool is None
                                    or not self.pool.share):
            raise ValueError(
                "kv_dedup_every requires the sharing page pool "
                "(kv_pages + kv_share) — without the radix index "
                "there is no content evidence to merge on")
        # per-path tok/s attribution (ROADMAP item 4's autotuner input)
        self.path_label = _exec_path_label(self.params, cfg)
        if self.pool is not None:
            # export-time memory gauges — keyed so a revive_rank
            # rebuild replaces its predecessor's collector instead of
            # exporting a dead pool forever
            self.telemetry.registry.register_collector(
                self._memory_metrics, key=("kv_pool", rank))

    def _memory_metrics(self):
        """Prometheus lines for the page pool's MemoryStats (pure host
        counters — no device sync)."""
        out = {}
        for k, v in self.pool.stats().as_dict().items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f'serve_kv_{k}{{rank="{self.rank}"}}'] = v
        return out

    @staticmethod
    def _prefill_and_write(cfg, cache_len, params, toks, poss, caches,
                           slots, valid):
        """Jitted admission: prompt prefill + scatter of the new cache
        rows into the batch caches at ``slots``, one device program.
        (Admission used to run the forward eagerly — per-op dispatch
        made a single refill cost ~100 decode steps, wiping out the
        continuous-batching win under load.) Only the last-token logits
        (G, V) come back to the host.

        ``valid``: optional (G,) bool mask for the bucketed fixed-shape
        admission — rows where it is False are group padding whose
        scatter must leave the target slot untouched, so their "new"
        rows are replaced by the slot's EXISTING rows before the write
        (``slots`` covers each batch slot exactly once in that mode, so
        the scatter indices stay unique and deterministic)."""
        logits, caches1 = lm.prefill(params, cfg, tokens=toks,
                                     cache_len=cache_len,
                                     positions=poss)

        def put(batch_leaf, new_leaf):
            new_leaf = new_leaf.astype(batch_leaf.dtype)
            if valid is not None:
                keep = batch_leaf[:, slots]
                vm = valid.reshape((1, -1) + (1,) * (new_leaf.ndim - 2))
                new_leaf = jnp.where(vm, new_leaf, keep)
            return batch_leaf.at[:, slots].set(new_leaf)

        return logits[:, 0], jax.tree.map(put, caches, caches1)

    @staticmethod
    def _decode_step(cfg, params, toks, pos, caches, key, temps, active,
                     eos, remaining):
        """One fused decode + sample + retire-check step; only (B,) token
        ids and (B,) done flags leave the device."""
        logits, caches = lm.decode_step(params, cfg, toks, pos, caches)
        key, sub = jax.random.split(key)
        nxt = _sample_tokens(logits[:, 0], sub, temps)
        nxt = jnp.where(active, nxt, 0)
        done = active & ((nxt == eos) | (remaining <= 1))
        return nxt, done, caches, key

    # -- paged-KV twins (DESIGN.md §13) --------------------------------
    @staticmethod
    def _paged_prefill_write(cfg, cache_len, params, toks, poss, data,
                             dests):
        """Jitted paged admission: prompt prefill + scatter of the new
        cache PAGES into the pool at ``dests`` (G, NB) — the trash page
        absorbs unallocated logical pages and admission-group padding
        rows, so no validity mask is needed."""
        from repro.serve import memory as kvmem
        logits, caches1 = lm.prefill(params, cfg, tokens=toks,
                                     cache_len=cache_len,
                                     positions=poss, uniform_cache=True)
        return logits[:, 0], kvmem.scatter_prefill_pages(data, caches1,
                                                         dests)

    @staticmethod
    def _paged_prefill_past_write(cfg, params, toks, poss, data, past_bt,
                                  dests):
        """Jitted suffix-only admission (prefix sharing, DESIGN.md §16):
        gather each request's MATCHED prefix pages into a ring (the
        suffix region reads the zero page — masked emptiness), prefill
        just the suffix against it, scatter the fresh suffix pages.
        ``dests`` maps the shared prefix pages to the trash page, so
        the scatter can never touch a page with refcount > 1."""
        from repro.serve import memory as kvmem
        past = kvmem.gather_block_tables(data, past_bt)
        logits, caches1 = lm.prefill_with_past(params, cfg, toks, poss,
                                               past)
        return logits[:, 0], kvmem.scatter_prefill_pages(data, caches1,
                                                         dests)

    @staticmethod
    def _paged_decode_step(cfg, NB, L, params, toks, pos, data, bt, key,
                           temps, active, eos, remaining):
        """One decode step over the page pool: gather each slot's pages
        into the exact contiguous ring layout, run the unchanged decode
        math, scatter back the one page per slot that was written."""
        from repro.serve import memory as kvmem
        caches = kvmem.gather_block_tables(data, bt)
        logits, caches = lm.decode_step(params, cfg, toks, pos, caches)
        key, sub = jax.random.split(key)
        nxt = _sample_tokens(logits[:, 0], sub, temps)
        nxt = jnp.where(active, nxt, 0)
        done = active & ((nxt == eos) | (remaining <= 1))
        data = kvmem.scatter_written_pages(data, caches, bt, pos, NB, L)
        return nxt, done, data, key

    @staticmethod
    def _paged_spec_verify(cfg, params, toks, poss, data, past_bt,
                           dests):
        """Jitted speculative verify (DESIGN.md §17): ONE full-fidelity
        suffix pass over [x0, d1..dk] (absolute positions P..P+k, pad
        rows all -1) against each slot's REAL pages, returning the
        target's greedy token after EVERY position — t_pred[j] is what
        sequential decode would emit after consuming position P+j. The
        fresh target K/V merges into the round's SCRATCH pages
        (``dests``), pos-masked so pre-range and old-lap entries seeded
        from the real pages survive: whatever is later promoted is
        exact target KV (the drafter's writes are fully overwritten —
        its entries never outlive the round)."""
        from repro.serve import memory as kvmem
        past = kvmem.gather_block_tables(data, past_bt)
        logits, caches1 = lm.prefill_with_past(params, cfg, toks, poss,
                                               past, all_logits=True)
        # same greedy read as _sample_tokens' temp<=0 branch: argmax
        # over f32 logits — bit-identical token selection
        pred = jnp.argmax(logits.astype(jnp.float32),
                          axis=-1).astype(jnp.int32)
        data = kvmem.masked_scatter_pages(data, caches1, dests)
        return pred, data

    # ------------------------------------------------------------------
    def _mesh_ctx(self):
        """Active-mesh scope for every traced/executed model call: the
        shard_map packed drivers and TP/SP paths key off
        ``distribution.context.active_mesh()`` at trace time."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.distribution import context as dctx
        stack = contextlib.ExitStack()
        stack.enter_context(self.mesh)
        stack.enter_context(dctx.use_mesh(self.mesh, self.profile))
        return stack

    def submit(self, req: Request):
        """Enqueue a request (FCFS append; a scheduler imposes its own
        queue order by re-sorting before each step)."""
        if req.t_submit is None:
            req.t_submit = time.monotonic()
        req.rank = self.rank
        req.status = "queued"
        self.queue.append(req)
        self._trace.instant("submit", tid=self.rank, rid=req.rid,
                            queue=len(self.queue))

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    # -- scheduler-facing views of the slot state machine --------------
    def slot_states(self) -> List[str]:
        """Per-slot state: 'free' or 'decode' (PREFILL is transient
        inside the same ``step`` that admits — see module docstring)."""
        return ["free" if r is None else "decode" for r in self.slot_req]

    def n_free(self) -> int:
        return len(self._free_slots())

    def admission_capacity(self) -> int:
        """Requests this engine could plausibly admit RIGHT NOW: free
        slots, capped by page-pool headroom when KV is paged — the
        scheduler's admission control consults this instead of raw slot
        count (a free slot with no pages behind it absorbs nothing)."""
        free = self.n_free()
        if self.pool is None:
            return free
        return min(free, self.pool.admissible_requests())

    def memory_stats(self):
        """Paged-KV pool accounting (None when KV is contiguous)."""
        return None if self.pool is None else self.pool.stats()

    def route_headroom_tokens(self) -> Optional[int]:
        """Page-pool residency headroom in TOKENS — how much new cache
        this engine can allocate before the high-watermark policy starts
        spilling cold pages to host RAM. The scheduler's spill-aware
        routing steers traffic away from ranks whose headroom cannot
        cover a request's prefill (they are mid-spill or about to be).
        None for contiguous engines: no paging, no spill pressure.

        *Effective* headroom under prefix sharing: physical residency
        already counts a shared page ONCE however many block tables
        reference it, and rc-0 cached pages are reclaimable without any
        spill (eviction just forgets regenerable prefix KV), so they
        count as headroom too."""
        if self.pool is None:
            return None
        st = self.pool.stats()
        free = max(0, st.watermark - st.device_used) + st.cached_pages
        return free * self.pool.page_len

    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None
                                       for r in self.slot_req)

    def outstanding_tokens(self, slo: Optional[str] = None) -> int:
        """Load metric for scheduler routing: queued work (prompt still
        to prefill + decode budget) plus the REMAINING decode budget of
        every occupied slot (their prompts are already prefilled).
        ``slo`` restricts the sum to one SLO class (latency-aware
        routing keys interactive traffic on interactive contention)."""
        return (sum(r.cost_estimate() for r in self.queue
                    if slo is None or r.slo == slo)
                + sum(r.max_new_tokens - len(r.out_tokens)
                      for r in self.slot_req
                      if r is not None and (slo is None or r.slo == slo)))

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _emit(self, req: Request, tok: int):
        """Append + stream a freshly sampled token."""
        req.out_tokens.append(tok)
        self._trace.instant("token", tid=self.rank, rid=req.rid)
        if self.on_token is not None:
            self.on_token(req, tok)

    def _read(self, *arrays) -> List[np.ndarray]:
        """Device arrays to the host, under the one span name every
        blocking device-to-host read carries."""
        with self._trace.span("read", tid=self.rank):
            return [np.asarray(a) for a in arrays]

    def _sample_host(self, logits, reqs: List[Request]) -> List[int]:
        temps = jnp.asarray([r.temperature for r in reqs], jnp.float32)
        (toks,) = self._read(self._sample(logits, self._next_key(), temps))
        return [int(t) for t in toks]

    # -- preemption (DESIGN.md §12) ------------------------------------
    def preempt_slot(self, slot: int, *, keep_kv: bool = True) -> Request:
        """Move the request decoding in ``slot`` back to QUEUED at step
        granularity and free the slot. ``keep_kv=True`` snapshots the
        slot's cache rows for a one-scatter exact resume;
        ``keep_kv=False`` drops them — resume re-prefills
        ``prompt + out_tokens[:-1]`` (the last emitted token becomes the
        next decode input, exactly as if decode had never stopped). The
        caller re-queues the returned request.

        Paged KV (DESIGN.md §13): no data moves — ``keep_kv=True``
        merely UNMAPS the slot (its pages stay allocated, turn cold, and
        may spill to host RAM under memory pressure; resume faults them
        back); ``keep_kv=False`` frees the pages outright."""
        req = self.slot_req[slot]
        assert req is not None, f"preempting free slot {slot}"
        if self.pool is not None:
            if keep_kv:
                self.pool.preempt(req.rid)
            else:
                self.pool.free(req.rid)
        elif keep_kv:
            with self._mesh_ctx():
                req._kv = self._snap(self.caches, slot)
        req._resume_pos = int(self.pos[slot])
        req.preemptions += 1
        req.status = "queued"
        self.slot_req[slot] = None
        self.stats["preemptions"] += 1
        self._trace.instant("preempt", tid=self.rank, rid=req.rid,
                            kept_kv=bool(keep_kv))
        return req

    def _finish_resume(self, slot: int, req: Request):
        req._resume_pos = None
        req._kv = None
        req.status = "running"
        self.slot_req[slot] = req
        self.stats["resumes"] += 1
        self._trace.instant("resume", tid=self.rank, rid=req.rid)

    def _restore_slot(self, slot: int, req: Request):
        """KV-snapshot resume: scatter the saved cache rows back — no
        forward pass, bit-exact by construction."""
        assert self.slot_req[slot] is None, \
            f"resume into occupied slot {slot}"
        self.caches = self._restore(self.caches, req._kv, slot)
        self.pos[slot] = req._resume_pos
        self._finish_resume(slot, req)

    def _attach_paged_resume(self, slot: int, req: Request):
        """Paged resume: the request's pages were just pinned resident
        (host-spilled ones faulted back) — only the block table changes;
        no cache copy at all."""
        assert self.slot_req[slot] is None, \
            f"resume into occupied slot {slot}"
        self.pos[slot] = req._resume_pos
        self._finish_resume(slot, req)

    def _page_keys(self, seq: np.ndarray) -> Tuple[bytes, ...]:
        """Exact-content radix keys: one per FULL page of ``seq`` (the
        partial trailing page is always private — the ISSUE's
        'partial-page boundary re-prefilled into a fresh page').
        Empty when sharing is off or the sequence overflows the ring
        (wrapped pages hold mixed-position content — not indexable)."""
        if self.pool is None or not self.pool.share:
            return ()
        if len(seq) > self.cache_len:
            return ()
        L = self.pool.page_len
        a = np.ascontiguousarray(np.asarray(seq, np.int32))
        return tuple(a[j * L:(j + 1) * L].tobytes()
                     for j in range(len(seq) // L))

    def _paged_reserve(self, req: Request) -> Tuple[bool, str]:
        """Acquire the pages an admission needs. Returns (ok, mode):
        mode 'resume' re-attached a preempted request's live pages
        (skip prefill entirely), 'prefill' allocated pages for a fresh
        prompt or a re-prefill resume (dropped/never-kept pages). Not
        ok = pool exhausted; the caller defers the request.

        Sharing: the prompt's full-page keys walk the radix index and
        matched pages are mapped instead of allocated — capped so at
        least ONE token is always prefilled (the first sampled token
        comes from the suffix forward's last-position logits)."""
        if req._resume_pos is not None and self.pool.has_pages(req.rid):
            return self.pool.resume(req.rid), "resume"
        seq = self._prefill_tokens(req)
        n = self.pool.pages_for(len(seq))
        keys = self._page_keys(seq)
        if keys:
            keys = keys[:(len(seq) - 1) // self.pool.page_len]
        ok, m = self.pool.admit_prefix(
            req.rid, n, keys, min_pages=self.kv_share_min_pages)
        if ok and m:
            self._shared_tokens[req.rid] = m * self.pool.page_len
        return ok, "prefill"

    def _prefill_tokens(self, req: Request) -> np.ndarray:
        """The token sequence admission must prefill: the prompt, or for
        a re-prefill resume the prompt + all generated tokens but the
        last (which is the next decode input)."""
        if req._resume_pos is None:
            return np.asarray(req.prompt, np.int32)
        return np.concatenate([
            np.asarray(req.prompt, np.int32),
            np.asarray(req.out_tokens[:-1], np.int32)])

    # -- prefill bucketing (DESIGN.md §12) -----------------------------
    def _bucket_len(self, S: int) -> int:
        """Smallest bucket ≥ S; exact S when S exceeds every bucket
        (rare tail — one extra program, never a wrong answer)."""
        for b in self.buckets:
            if b >= S:
                return b
        return S

    def _run_prefill(self, toks, poss, all_slots, reqs, valid):
        """Dispatch one jitted admission pass: contiguous engines
        scatter cache ROWS into the batch caches at ``all_slots``
        (``valid`` masks bucketed padding rows); paged engines scatter
        cache PAGES into the pool at each request's allocated pages
        (padding rows write to the trash page — no mask needed).
        Returns the last-token logits (G, V)."""
        rows, S = (int(n) for n in toks.shape)
        self.stats["prefill_computed_tokens"] += rows * S
        with self._trace.span("prefill", tid=self.rank,
                              rids=[r.rid for r in reqs], rows=rows, S=S):
            if self.pool is not None:
                dests = self.pool.dest_table([r.rid for r in reqs], rows)
                logits_last, self.pool.data = self._prefill(
                    self.params, toks, poss, self.pool.data,
                    jnp.asarray(dests))
            else:
                logits_last, self.caches = self._prefill(
                    self.params, toks, poss, self.caches,
                    jnp.asarray(np.asarray(all_slots, np.int32)), valid)
        return logits_last

    def _prefill_into_slot(self, slot: int, req: Request,
                           seq: np.ndarray):
        """Single-sequence prefill; its cache rows are written into the
        batch caches at ``slot``. Fallback path: hybrid/SSM stacks and
        prompts longer than the cache."""
        toks = jnp.asarray(seq[None, :], jnp.int32)
        logits_last = self._run_prefill(toks, None, [slot], [req], None)
        self._register_prompt([req], [seq])
        assert self.slot_req[slot] is None, \
            f"prefill into occupied slot {slot}"
        self.pos[slot] = len(seq)
        if req._resume_pos is not None:
            self._finish_resume(slot, req)
            return
        (nxt,) = self._sample_host(logits_last, [req])
        self._emit(req, nxt)
        req.t_first = time.monotonic()
        self._observe_ttft(req)
        if self._retired_at_admission(req):
            return
        req.status = "running"
        self.slot_req[slot] = req

    def _prefill_group(self, slots: List[int], reqs: List[Request],
                       seqs: List[np.ndarray]):
        """Batched multi-slot prefill: one LEFT-padded forward pass for
        all admitted prompts. Row i of the positions array is
        [-(S-L_i) … -1, 0 … L_i-1]; negative positions are masked out of
        attention and land in the cache with pos = -1, so shorter
        prompts are bit-exact vs solo prefill. With ``buckets`` the
        group is padded to a FIXED shape — all B rows, S rounded up to a
        bucket — and a validity mask keeps the pad rows from touching
        any slot (O(len(buckets)) compiled programs total)."""
        G = len(reqs)
        lens = [len(s) for s in seqs]
        S = max(lens)
        valid = None
        all_slots = list(slots)
        if self.buckets:
            S = self._bucket_len(S)
            all_slots += [i for i in range(self.B) if i not in slots]
            valid = jnp.asarray(np.arange(len(all_slots)) < G)
        Gp = len(all_slots)
        toks = np.zeros((Gp, S), np.int32)
        poss = np.tile(np.arange(S, dtype=np.int32) - S, (Gp, 1))
        for g, seq in enumerate(seqs):
            pad = S - lens[g]
            toks[g, pad:] = seq
            poss[g] = np.arange(S) - pad
        logits_last = self._run_prefill(jnp.asarray(toks),
                                        jnp.asarray(poss), all_slots,
                                        reqs, valid)
        self._register_prompt(reqs, seqs)
        temps = np.zeros((Gp,), np.float32)
        for g, r in enumerate(reqs):
            temps[g] = r.temperature
        (sampled,) = self._read(self._sample(
            logits_last, self._next_key(), jnp.asarray(temps)))
        nxts = [int(t) for t in sampled[:G]]
        now = time.monotonic()
        for slot, req, nxt, L in zip(slots, reqs, nxts, lens):
            assert self.slot_req[slot] is None, \
                f"prefill into occupied slot {slot}"
            self.pos[slot] = L
            if req._resume_pos is not None:
                # re-prefill resume: the sampled token is discarded (the
                # request's last token was emitted before preemption)
                self._finish_resume(slot, req)
                continue
            self._emit(req, nxt)
            req.t_first = now
            self._observe_ttft(req)
            if self._retired_at_admission(req):
                continue
            req.status = "running"
            self.slot_req[slot] = req

    def _observe_ttft(self, req: Request):
        """Aggregate time-to-first-token into the per-SLO-class
        histogram the moment ``t_first`` is stamped (the stamp used to
        be write-only — nothing ever read it back)."""
        if req.t_submit is not None and req.t_first is not None:
            self.telemetry.observe_ttft(req.slo,
                                        req.t_first - req.t_submit)

    def _register_prompt(self, reqs: List[Request],
                         seqs: List[np.ndarray]):
        """Publish freshly prefilled full prompt pages into the radix
        index. Runs right after the prefill pass and BEFORE any
        retire-at-admission free, so even a prompt that EOSes
        immediately seeds the cache (its pages turn cached, not free).
        No-op with sharing off."""
        if self.pool is None or not self.pool.share:
            return
        for r, s in zip(reqs, seqs):
            self.pool.register_prefix(r.rid, self._page_keys(s))

    def _prefill_group_shared(self, slots: List[int],
                              reqs: List[Request],
                              seqs: List[np.ndarray]):
        """Suffix-only batched prefill for admissions whose prompt
        matched shared prefix pages: row g holds ``seq[skip_g:]``
        left-padded, with ABSOLUTE positions (pads carry -1 — masked
        as keys, routed to the sacrificial slot by
        ``build_cache_from_suffix``). The jitted pass gathers each
        row's matched pages as its past ring and scatters only the
        fresh suffix pages back (shared pages are never written)."""
        L = self.pool.page_len
        skips = [self._shared_tokens[r.rid] for r in reqs]
        sufs = [np.asarray(s[m:], np.int32)
                for s, m in zip(seqs, skips)]
        lens = [len(s) for s in sufs]
        G = len(reqs)
        S = max(lens)
        nrows = G
        if self.buckets:
            S = self._bucket_len(S)
            nrows = self.B
        toks = np.zeros((nrows, S), np.int32)
        poss = np.full((nrows, S), -1, np.int32)
        for g, suf in enumerate(sufs):
            pad = S - lens[g]
            toks[g, pad:] = suf
            poss[g, pad:] = np.arange(skips[g], skips[g] + lens[g])
        rids = [r.rid for r in reqs]
        skip_pages = [m // L for m in skips]
        past_bt = self.pool.prefix_table(rids, skip_pages, nrows)
        dests = self.pool.dest_table(rids, nrows,
                                     skip_pages=skip_pages)
        self.stats["prefill_computed_tokens"] += nrows * S
        with self._trace.span("prefill", tid=self.rank, rids=rids,
                              rows=int(nrows), S=int(S), shared=True):
            logits_last, self.pool.data = self._prefill_past(
                self.params, jnp.asarray(toks), jnp.asarray(poss),
                self.pool.data, jnp.asarray(past_bt), jnp.asarray(dests))
        self._register_prompt(reqs, seqs)
        temps = np.zeros((nrows,), np.float32)
        for g, r in enumerate(reqs):
            temps[g] = r.temperature
        (sampled,) = self._read(self._sample(
            logits_last, self._next_key(), jnp.asarray(temps)))
        nxts = [int(t) for t in sampled[:G]]
        now = time.monotonic()
        for slot, req, nxt, seq in zip(slots, reqs, nxts, seqs):
            assert self.slot_req[slot] is None, \
                f"prefill into occupied slot {slot}"
            self.pos[slot] = len(seq)       # FULL prompt length
            if req._resume_pos is not None:
                self._finish_resume(slot, req)
                continue
            self._emit(req, nxt)
            req.t_first = now
            self._observe_ttft(req)
            if self._retired_at_admission(req):
                continue
            req.status = "running"
            self.slot_req[slot] = req

    def _retired_at_admission(self, req: Request) -> bool:
        """EOS / budget check on the prefill-sampled token: a request can
        finish without ever occupying a decode slot."""
        if ((req.eos_id is not None
             and req.out_tokens[-1] == req.eos_id)
                or len(req.out_tokens) >= req.max_new_tokens):
            req.done = True
            req.status = "done"
            req.t_done = time.monotonic()
            if self.pool is not None:
                self.pool.free(req.rid)
            self._finished_at_admission.append(req)
            return True
        return False

    def _admit(self):
        free = self._free_slots()
        if self.admission == "drain" and len(free) < self.B:
            return                  # drain-batch baseline: wait for all
        take = min(len(free), len(self.queue))
        if take:
            with self._trace.span("admission", tid=self.rank,
                                  rids=[r.rid for r in self.queue[:take]]):
                self._admit_into(free, take)

    def _admit_into(self, free: List[int], take: int):
        """Admit the first ``take`` queued requests into ``free`` slots."""
        popped = [self.queue.pop(0) for _ in range(take)]
        slots = free[:take]
        try:
            # KV-snapshot / page resumes restore directly (no forward
            # pass); paged admissions acquire their pages first and
            # DEFER (back to the queue, in order) once the pool is
            # exhausted — slots are oversubscribable, pages are not
            pending = []
            for k, (slot, req) in enumerate(zip(slots, popped)):
                if self.pool is not None:
                    ok, mode = self._paged_reserve(req)
                    if not ok:
                        self.queue[:0] = popped[k:]
                        popped = popped[:k]
                        break
                    if mode == "resume":
                        self._attach_paged_resume(slot, req)
                        continue
                if req._resume_pos is not None and req._kv is not None:
                    self._restore_slot(slot, req)
                else:
                    pending.append((slot, req))
            if len(free) < self.B:  # refill while other slots decode
                self.stats["continuous_refills"] += len(popped)
            self.stats["admitted"] += len(popped)
            if self._trace.enabled:
                for req in popped:
                    self._trace.instant("admit", tid=self.rank,
                                        rid=req.rid)
            if not pending:
                return
            # split sharing admissions (suffix-only prefill through the
            # past-attending jit) from normal ones (unchanged path —
            # trivially bit-identical to sharing off)
            shared, normal = [], []
            for slot, req in pending:
                seq = self._prefill_tokens(req)
                skip = self._shared_tokens.get(req.rid, 0)
                if req._resume_pos is None:
                    self.stats["prefill_tokens"] += len(seq) - skip
                    self.stats["prefill_tokens_skipped"] += skip
                else:
                    # re-prefill resume: the prompt was already counted
                    # (and its shared pages already credited) at first
                    # admission — charging it again double-counts both
                    # stats vs the solo run. The recovery work is its
                    # own counter.
                    self.stats["reprefill_tokens"] += len(seq) - skip
                (shared if skip else normal).append((slot, req, seq))
            if shared:
                self._prefill_group_shared(
                    [s for s, _, _ in shared], [r for _, r, _ in shared],
                    [q for _, _, q in shared])
            if normal:
                slots = [s for s, _, _ in normal]
                reqs = [r for _, r, _ in normal]
                seqs = [q for _, _, q in normal]
                if (self._attn_only
                        and max(len(s) for s in seqs) <= self.cache_len
                        and (len(reqs) > 1 or self.buckets)):
                    self._prefill_group(slots, reqs, seqs)
                else:
                    for slot, req, seq in zip(slots, reqs, seqs):
                        self._prefill_into_slot(slot, req, seq)
            for _, req in pending:
                self._shared_tokens.pop(req.rid, None)
        except BaseException:
            # a raising prefill/restore must not lose the popped
            # requests: everything not yet slotted (or retired at
            # admission) goes back to the queue front, so the
            # scheduler's failure handler can re-route it
            placed = {id(r) for r in self.slot_req if r is not None}
            placed |= {id(r) for r in self._finished_at_admission}
            back = [r for r in popped if id(r) not in placed]
            for r in popped:
                self._shared_tokens.pop(r.rid, None)
            if self.pool is not None:
                # unwind page state: un-prefilled admissions release
                # their pages; unplaced page-holding resumes turn cold
                # again (spillable) instead of leaking resident pages
                for r in back:
                    if not self.pool.has_pages(r.rid):
                        continue
                    if r._resume_pos is not None:
                        self.pool.mark_preempted(r.rid)
                    else:
                        self.pool.free(r.rid)
            self.queue[:0] = back
            raise

    # ------------------------------------------------------------------
    def step(self) -> List[Request]:
        """Admit queued requests, run one decode step, retire finished.
        Returns completed requests."""
        with self._mesh_ctx(), self._trace.span("step", tid=self.rank):
            return self._step_inner()

    def _step_inner(self) -> List[Request]:
        self._admit()

        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        # speculative rounds (DESIGN.md §17) claim eligible slots
        # FIRST: their decode writes land on scratch pages, so they
        # skip the write-rule guard below entirely
        specs = self._collect_specs(active) if active else []
        if self.pool is not None and active:
            # decode growth + write rule: the page holding this step's
            # write position must be resident AND writable (rc == 1,
            # unregistered) BEFORE the step — a shared page is
            # copy-on-written here, never scattered to (DESIGN.md §16).
            # A slot that cannot grow/copy (pool exhausted, nothing
            # cold to spill) is preempted with its pages kept — they
            # turn cold, so some other slot's growth (or this one's
            # later resume) can evict them. watermark >= one ring
            # guarantees a lone slot always fits.
            C, L = self.cache_len, self.pool.page_len
            for i in list(active):
                req = self.slot_req[i]
                if not self.pool.ensure_writable(
                        req.rid, (int(self.pos[i]) % C) // L):
                    self.queue.insert(0, self.preempt_slot(i))
                    active.remove(i)
        if not active and not specs:
            finished = self._finished_at_admission
            self._finished_at_admission = []
            if self.pool is not None:
                self.stats["memory"] = self.pool.stats().as_dict()
            return finished
        # requests retired AT admission stay buffered until the decode
        # below succeeds — if it raises, the scheduler's failure handler
        # can still recover them as completed (they are done, not lost)
        finished: List[Request] = []

        if active:
            with self._trace.span("inputs", tid=self.rank,
                                  slots=len(active)):
                last = np.zeros((self.B, 1), np.int32)
                temps = np.zeros((self.B,), np.float32)
                act = np.zeros((self.B,), bool)
                eos = np.full((self.B,), -1, np.int32)
                remaining = np.zeros((self.B,), np.int32)
                for i in active:
                    req = self.slot_req[i]
                    last[i, 0] = req.out_tokens[-1]
                    temps[i] = req.temperature
                    act[i] = True
                    eos[i] = -1 if req.eos_id is None else req.eos_id
                    remaining[i] = req.max_new_tokens - len(req.out_tokens)
                last, pos, temps, act, eos, remaining = (
                    jnp.asarray(a) for a in (last, self.pos, temps, act,
                                             eos, remaining))
                if self.pool is not None:
                    # speculating slots are masked AND their rows
                    # read/write the trash tables — the normal decode
                    # never touches their pages this step
                    bt = jnp.asarray(self.pool.block_table(
                        [r.rid if (r is not None and i in active) else None
                         for i, r in enumerate(self.slot_req)]))
            with self._trace.span("decode", tid=self.rank):
                if self.pool is not None:
                    nxt, done, self.pool.data, self._key = self._decode(
                        self.params, last, pos, self.pool.data, bt,
                        self._key, temps, act, eos, remaining)
                else:
                    nxt, done, self.caches, self._key = self._decode(
                        self.params, last, pos, self.caches, self._key,
                        temps, act, eos, remaining)
            # (B,) int32 tokens and done flags: the ONLY per-token host
            # traffic
            nxt, done = self._read(nxt, done)
        else:
            # every live slot is speculating: still split the step key
            # once so the RNG key-state stays in lockstep with the
            # non-speculative engine (temperature>0 requests admitted
            # later draw identical randomness either way)
            self._next_key()

        with self._trace.span("emit", tid=self.rank, tokens=len(active)):
            self.stats["decode_steps"] += 1
            self.stats["generated_tokens"] += len(active)
            self.telemetry.note_tokens(self.path_label, len(active))
            for i in active:
                req = self.slot_req[i]
                self.pos[i] += 1
                self._emit(req, int(nxt[i]))
                if bool(done[i]):
                    req.done = True
                    req.status = "done"
                    req.t_done = time.monotonic()
                    if self.pool is not None:   # EOS frees the pages
                        self.pool.free(req.rid)
                    finished.append(req)
                    self.slot_req[i] = None
        if specs:
            finished += self._run_spec_round(specs)
        if (self.kv_dedup_every
                and self.stats["decode_steps"] % self.kv_dedup_every
                == 0):
            self.pool.dedup_sweep()
        finished = self._finished_at_admission + finished
        self._finished_at_admission = []
        if self.pool is not None:
            self.stats["memory"] = self.pool.stats().as_dict()
        return finished

    # -- speculative decoding (DESIGN.md §17) --------------------------
    def _collect_specs(self, active: List[int]
                       ) -> List[Tuple[int, Request, dict]]:
        """Claim the slots that speculate this step (removed from
        ``active``): greedy (temperature 0) requests — batch-class by
        default, interactive only when opted in — with at least two
        tokens of budget left, whose draft round can get its scratch
        pages. Under pool pressure a slot silently decodes the normal
        way this step (never preempted just to speculate)."""
        if self._draft is None:
            return []
        C, L = self.cache_len, self.pool.page_len
        k = self.draft_k
        specs = []
        for i in list(active):
            req = self.slot_req[i]
            if req.temperature > 0:
                continue
            if req.slo == "interactive" and not self.draft_interactive:
                continue
            if req.max_new_tokens - len(req.out_tokens) < 2:
                continue                  # one token left: just decode
            P = int(self.pos[i])
            js = sorted({((P + t) % C) // L for t in range(k + 1)})
            got = self.pool.begin_scratch(req.rid, js)
            if got is None:
                self.stats["spec_fallbacks"] += 1
                continue
            specs.append((i, req, got))
            active.remove(i)
        return specs

    def _run_spec_round(self, specs: List[Tuple[int, Request, dict]]
                        ) -> List[Request]:
        """Draft-k/verify-1 over the claimed slots, batched.

        Draft: k drafter decode steps through a block table whose
        write-range logical pages are swapped to the round's scratch
        pages — the drafter reads the real prefix, its KV lands only on
        scratch. Verify: ONE target pass over [x0, d1..dk] against the
        REAL pages, whose fresh KV overwrites the drafter's entries in
        the same scratch pages (promoted KV is always target KV). With
        a = the longest prefix of drafts matching the target's greedy
        predictions, positions P..P+a were verified exactly as
        sequential decode would have computed them: emit t_pred[0..a]
        (all target argmaxes — a+1 tokens), promote the scratch pages
        fully inside the accepted range, masked-merge the boundary
        page, discard the rest. EOS/budget truncates the emitted run
        and frees everything. On a promotion failure (pool exhausted
        mid-merge) the slot falls back to an exact re-prefill resume —
        rollback is always an unmap, never a copy."""
        with self._trace.span("spec_round", tid=self.rank,
                              slots=len(specs)) as span:
            finished, emitted = self._spec_round(specs)
            span.set_metadata(emitted=emitted)
        if emitted:
            self.telemetry.note_tokens("draft", emitted)
        return finished

    def _spec_round(self, specs: List[Tuple[int, Request, dict]]
                    ) -> Tuple[List[Request], int]:
        """The round itself: (finished requests, tokens emitted)."""
        from repro.serve.memory import ZERO_PAGE, TRASH_PAGE
        k = self.draft_k
        C, L, NB = self.cache_len, self.pool.page_len, self.pool.NB
        B = self.B
        dparams, _ = self._draft
        finished: List[Request] = []
        emitted = 0
        try:
            slot_rids: List[Optional[int]] = [None] * B
            for i, req, _ in specs:
                slot_rids[i] = req.rid
            dbt = self.pool.block_table(slot_rids)
            for i, req, got in specs:
                for j, s in got.items():
                    dbt[i, j] = s
            dbt_j = jnp.asarray(dbt)
            cur = np.zeros((B, 1), np.int32)
            act = np.zeros((B,), bool)
            for i, req, _ in specs:
                cur[i, 0] = req.out_tokens[-1]
                act[i] = True
            pos_d = self.pos.astype(np.int32).copy()
            temps0 = jnp.zeros((B,), jnp.float32)
            eos_none = jnp.full((B,), -1, jnp.int32)
            rem_big = jnp.full((B,), 1 << 30, jnp.int32)
            act_j = jnp.asarray(act)
            dkey = jax.random.PRNGKey(0)  # temp 0: argmax ignores it
            drafts = np.zeros((k, B), np.int32)
            for t in range(k):
                nxt, _, self.pool.data, _ = self._draft_decode(
                    dparams, jnp.asarray(cur),
                    jnp.asarray(pos_d), self.pool.data, dbt_j,
                    dkey, temps0, act_j, eos_none, rem_big)
                (drafts[t],) = self._read(nxt)
                cur = drafts[t].reshape(B, 1)
                pos_d += 1
            toks = np.zeros((B, k + 1), np.int32)
            poss = np.full((B, k + 1), -1, np.int32)
            verify_bt = np.full((B, NB), ZERO_PAGE, np.int32)
            dests = np.full((B, NB), TRASH_PAGE, np.int32)
            for i, req, got in specs:
                P = int(self.pos[i])
                toks[i, 0] = req.out_tokens[-1]
                toks[i, 1:] = drafts[:, i]
                poss[i] = np.arange(P, P + k + 1)
                for j, p in enumerate(
                        self.pool.alloc.dev_pages(req.rid)):
                    if p is not None:
                        verify_bt[i, j] = p
                for j, s in got.items():
                    dests[i, j] = s
            pred, self.pool.data = self._verify(
                self.params, jnp.asarray(toks), jnp.asarray(poss),
                self.pool.data, jnp.asarray(verify_bt),
                jnp.asarray(dests))
            (pred,) = self._read(pred)          # (B, k+1) target argmax
            for i, req, got in specs:
                P = int(self.pos[i])
                a = 0
                while a < k and drafts[a, i] == pred[i, a]:
                    a += 1
                self.stats["spec_rounds"] += 1
                self.stats["spec_draft_tokens"] += k
                self.stats["spec_accepted_tokens"] += a
                self.telemetry.note_spec_round(a, k)
                done = False
                for t in range(a + 1):
                    tok = int(pred[i, t])
                    self._emit(req, tok)
                    self.stats["generated_tokens"] += 1
                    emitted += 1
                    if ((req.eos_id is not None and tok == req.eos_id)
                            or len(req.out_tokens)
                            >= req.max_new_tokens):
                        done = True
                        break
                if done:
                    self.pool.discard_scratch(req.rid)
                    req.done = True
                    req.status = "done"
                    req.t_done = time.monotonic()
                    self.pool.free(req.rid)
                    finished.append(req)
                    self.slot_req[i] = None
                    continue
                # not done => all a+1 tokens emitted; keep KV for
                # positions P..P+a. Promotion invariant: a real page
                # never holds entries beyond the slot's last written
                # position — fully-accepted pages swap in (pure
                # bookkeeping), the boundary page masked-merges only
                # the accepted range, rejected pages just unmap.
                hi = P + a
                ok = True
                for j in sorted(got):
                    wj = [p for p in range(P, P + k + 1)
                          if (p % C) // L == j]
                    kj = [p for p in wj if p <= hi]
                    if not kj:
                        continue      # fully rejected: discard below
                    if len(kj) == len(wj):
                        self.pool.promote_scratch(req.rid, j)
                    else:
                        if not self.pool.ensure_writable(req.rid, j):
                            ok = False
                            break
                        dst = self.pool.alloc.dev_pages(req.rid)[j]
                        self.pool.merge_scratch_slots(got[j], dst,
                                                      P, hi)
                self.pool.discard_scratch(req.rid)
                if not ok:
                    # pool exhausted mid-promotion: the emitted tokens
                    # stand; resume re-prefills prompt + out[:-1]
                    # (always exact), releasing every page
                    self.stats["spec_fallbacks"] += 1
                    self.queue.insert(
                        0, self.preempt_slot(i, keep_kv=False))
                    continue
                self.pos[i] = P + a + 1
        finally:
            # containment: a raise mid-round must not leak scratch
            for _, req, _ in specs:
                self.pool.discard_scratch(req.rid)
        return finished, emitted

    # -- failure containment (DESIGN.md §12/§14) -----------------------
    def _release_slot(self, slot: int) -> Request:
        """Detach the request occupying ``slot`` (pages/snapshot freed,
        slot back to FREE) WITHOUT deciding its fate — the caller marks
        it failed, requeues it, or cancels it."""
        req = self.slot_req[slot]
        assert req is not None, f"releasing free slot {slot}"
        if self.pool is not None and self.pool.has_pages(req.rid):
            self.pool.free(req.rid)
        self.slot_req[slot] = None
        return req

    def evacuate_inflight(self) -> List[Request]:
        """Pull every in-flight (slot-occupying) request off this engine
        with its emitted-token snapshot armed for an exact re-prefill
        resume elsewhere (:meth:`Request.mark_resumable`). Called by the
        scheduler's requeue-on-failure path when this shard's step
        raised: the evacuated requests re-route to live ranks and their
        greedy streams continue bit-identically."""
        evacuated = []
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            self._release_slot(i)
            req.mark_resumable()
            evacuated.append(req)
        return evacuated

    def fail_inflight(self, err) -> List[Request]:
        """Mark every in-flight (slot-occupying) request failed and free
        its slot. Called by the scheduler when this shard's step raised
        and requeueing is off (or nowhere to requeue to): only the
        requests that were mid-flight on the broken rank fail; queued
        requests are re-routable by the caller."""
        failed = []
        now = time.monotonic()
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            self._release_slot(i)
            req.status = "failed"
            req.error = f"{type(err).__name__}: {err}"
            req.t_done = now
            self.stats["failed"] += 1
            failed.append(req)
        return failed

    def cancel(self, rid: int) -> Optional[Request]:
        """Remove a request from this engine wherever it sits — waiting
        in the queue or mid-decode in a slot — releasing its pages and
        any KV snapshot. Returns the request (status untouched; the
        caller decides what the cancellation means — watchdog timeout,
        drain expiry, user abort), or None if ``rid`` is not here."""
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                self.queue.pop(i)
                if self.pool is not None and self.pool.has_pages(rid):
                    self.pool.free(rid)
                req._kv = None
                self.stats["cancelled"] += 1
                return req
        for i, req in enumerate(self.slot_req):
            if req is not None and req.rid == rid:
                self._release_slot(i)
                req._kv = None
                self.stats["cancelled"] += 1
                return req
        return None

    def run(self, requests: List[Request],
            on_token: Optional[Callable[[Request, int], None]] = None
            ) -> List[Request]:
        prev = self.on_token
        if on_token is not None:
            self.on_token = on_token
        try:
            for r in requests:
                self.submit(r)
            done: List[Request] = []
            while len(done) < len(requests):
                done.extend(self.step())
            return done
        finally:
            self.on_token = prev

    def stream(self, requests: List[Request]
               ) -> Iterator[Tuple[int, int]]:
        """Per-token iterator: yields ``(rid, token)`` in sampling order
        as decode steps retire — same serving semantics as :meth:`run`,
        incremental visibility."""
        buf: List[Tuple[int, int]] = []
        prev = self.on_token
        self.on_token = lambda req, tok: buf.append((req.rid, tok))
        try:
            for r in requests:
                self.submit(r)
            ndone = 0
            while ndone < len(requests):
                ndone += len(self.step())
                while buf:
                    yield buf.pop(0)
        finally:
            self.on_token = prev
