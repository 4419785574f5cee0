"""Serving telemetry subsystem (DESIGN.md §18): mergeable histogram
snapshots (associative + commutative, quantiles invariant to merge
order), the bounded span-tracer ring, Chrome trace-event schema
round-trips, the backward-compatible CounterView surface, Prometheus
export, per-path tok/s gauges + spec acceptance EMA — and the standing
acceptance bar: greedy streams are bit-identical with tracing armed,
across the plain, paged, shared-prefix, speculative, and
preempt/spill/fault paths."""
import dataclasses
import json

import numpy as np
import jax
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # the fixed twin below still runs
    HAVE_HYPOTHESIS = False

from repro.configs import get_config, reduced
from repro.models import lm
from repro.serve.chaos import ChaosConfig, ChaosMonkey
from repro.serve.engine import Engine, Request
from repro.serve.frontend import ClusterFrontend, FrontendConfig, \
    make_local_hosts
from repro.serve.scheduler import SchedulerConfig, ShardedScheduler
from repro.serve.telemetry import CounterView, DECLARED_STATS, \
    Histogram, HistSnapshot, MetricsRegistry, RollingRate, SpanTracer, \
    Telemetry, TTFT_BOUNDS_S, merged_ttft_stats, nearest_rank, pcts_ms

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# nearest-rank quantiles (the deduped bench/CLI helpers)
# ---------------------------------------------------------------------------


def _legacy_pcts_ms(lats):
    """The formula that used to live (twice) in bench_engine.py and
    launch/serve.py — dedup must not move any reported number."""
    xs = sorted(lats)
    pct = lambda q: xs[min(len(xs) - 1, int(len(xs) * q))] * 1e3
    return pct(0.5), pct(0.95)


def test_pcts_ms_matches_legacy_formula():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 7, 19, 20, 100):
        lats = sorted(rng.exponential(0.1, size=n).tolist())
        assert pcts_ms(lats) == _legacy_pcts_ms(lats)
    assert nearest_rank([5.0], 0.95) == 5.0
    with pytest.raises(ValueError):
        nearest_rank([], 0.5)


# ---------------------------------------------------------------------------
# histogram snapshots: merge is associative + commutative
# ---------------------------------------------------------------------------


def _snap(vals, bounds=TTFT_BOUNDS_S):
    h = Histogram(bounds)
    for v in vals:
        h.observe(v)
    return h.snapshot()


def _same(x: HistSnapshot, y: HistSnapshot) -> None:
    # everything discrete is exactly equal; total is a float sum, so
    # merge order can move its last bit
    assert (x.bounds, x.counts, x.count, x.vmin, x.vmax) == \
        (y.bounds, y.counts, y.count, y.vmin, y.vmax)
    assert x.total == pytest.approx(y.total)


def _assert_merge_laws(a_vals, b_vals, c_vals):
    a, b, c = _snap(a_vals), _snap(b_vals), _snap(c_vals)
    _same(a.merge(b), b.merge(a))                        # commutative
    _same(a.merge(b).merge(c), a.merge(b.merge(c)))      # associative
    # any merge order equals one histogram observing the union
    union = _snap(list(a_vals) + list(b_vals) + list(c_vals))
    _same(c.merge(a).merge(b), union)
    for q in (0.5, 0.95, 0.99):
        assert a.merge(b).merge(c).quantile(q) == union.quantile(q)


def test_hist_merge_laws_fixed_twin():
    rng = np.random.default_rng(1)
    for _ in range(25):
        groups = [rng.exponential(0.2,
                                  size=int(rng.integers(0, 40))).tolist()
                  for _ in range(3)]
        _assert_merge_laws(*groups)


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(*(st.lists(st.floats(min_value=0.0, max_value=100.0,
                                allow_nan=False), max_size=30)
             for _ in range(3)))
    def test_hist_merge_laws_property(a_vals, b_vals, c_vals):
        _assert_merge_laws(a_vals, b_vals, c_vals)


def test_hist_quantile_semantics():
    bounds = (1.0, 2.0, 4.0)
    assert HistSnapshot.empty(bounds).quantile(0.5) is None
    # quantile resolves to the upper bound of the holding bucket
    assert _snap([0.5], bounds).quantile(0.5) == 1.0
    assert _snap([1.5, 1.6, 1.7], bounds).quantile(0.5) == 2.0
    # overflow bucket answers vmax, the only exact value it has
    assert _snap([9.0, 11.0], bounds).quantile(0.95) == 11.0
    with pytest.raises(ValueError, match="different bucket bounds"):
        _snap([1.0], bounds).merge(_snap([1.0], (1.0, 2.0)))
    with pytest.raises(ValueError, match="strictly increasing"):
        Histogram((2.0, 1.0))
    d = _snap([0.5, 3.0], bounds).as_dict()
    assert d["count"] == 2 and d["min"] == 0.5 and d["max"] == 3.0


def test_merged_ttft_stats_order_independent():
    t1, t2 = Telemetry(), Telemetry()
    for v in (0.002, 0.003, 0.004):
        t1.observe_ttft("interactive", v)
    for v in (0.2, 0.4):
        t2.observe_ttft("interactive", v)
    t2.observe_ttft("batch", 1.3)
    ab = merged_ttft_stats([t1, t2])
    assert ab == merged_ttft_stats([t2, t1])
    assert ab["interactive"]["count"] == 5
    assert ab["batch"]["count"] == 1
    assert ab["interactive"]["p50_ms"] <= ab["interactive"]["p95_ms"]
    # the facade view is the single-instance merge
    assert t1.ttft_stats()["interactive"]["count"] == 3


# ---------------------------------------------------------------------------
# span tracer: bounded ring, free when disabled, Chrome schema
# ---------------------------------------------------------------------------


def test_tracer_ring_never_exceeds_capacity():
    tr = SpanTracer(capacity=16, enabled=True)
    for i in range(50):
        tr.instant(f"ev{i}", tid=0)
    assert len(tr) == 16
    assert tr.dropped == 50 - 16
    names = [e["name"] for e in tr.events()]
    assert names == [f"ev{i}" for i in range(34, 50)]   # oldest fell off


def test_tracer_disabled_is_inert():
    tr = SpanTracer(capacity=8, enabled=False)
    tr.instant("x")
    with tr.span("y", rows=3) as sp:     # no sink injected: a no-op
        sp.set_metadata(emitted=1)
    assert tr.span("z") is tr.span("w")  # no object made per span
    assert len(tr) == 0 and tr.events() == []


class _Sink:
    """A fake profiler sink: records (enter|exit, name, args)."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **args):
        return _SinkSpan(self.log, name, dict(args))


class _SinkSpan:
    def __init__(self, log, name, args):
        self.log, self.name, self.args = log, name, args

    def __enter__(self):
        self.log.append(("enter", self.name, self.args))
        return self

    def set_metadata(self, **args):
        self.args.update(args)

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.args))


@pytest.mark.parametrize("ring", [True, False])
def test_spans_reach_the_ring_and_the_sink_nested(ring):
    tr = SpanTracer(capacity=64, enabled=ring)
    sink = tr.annotate = _Sink()
    with tr.span("step", tid=2):
        with tr.span("prefill", tid=2, rows=12, S=256):
            pass
        tr.instant("token", tid=2)       # instants stay ring-only
        with tr.span("spec_round", tid=2, slots=1) as sp:
            sp.set_metadata(emitted=3)
    assert [(k, n) for k, n, _ in sink.log] == [
        ("enter", "step"), ("enter", "prefill"), ("exit", "prefill"),
        ("enter", "spec_round"), ("exit", "spec_round"), ("exit", "step")]
    assert sink.log[1][2] == {"rows": 12, "S": 256}
    assert sink.log[4][2] == {"slots": 1, "emitted": 3}
    ev = {e["name"]: e for e in tr.events()}
    if not ring:
        assert ev == {}
        return
    assert [e["name"] for e in tr.events()] == [
        "prefill", "token", "spec_round", "step"]  # a span lands on exit
    step = ev["step"]
    for child in ("prefill", "spec_round"):
        c = ev[child]
        assert step["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= step["ts"] + step["dur"]
        assert c["tid"] == 2 and c["ph"] == "X"
    assert ev["prefill"]["ts"] + ev["prefill"]["dur"] <= ev["token"]["ts"]
    assert ev["spec_round"]["args"] == {"slots": 1, "emitted": 3}


def _check_chrome(trace):
    """Schema check for the Chrome trace-event JSON object format —
    the invariants Perfetto / chrome://tracing need to load a file."""
    assert set(trace) == {"traceEvents", "displayTimeUnit"}
    assert trace["displayTimeUnit"] == "ms"
    for ev in trace["traceEvents"]:
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["cat"], str)
        assert isinstance(ev["ts"], (int, float))       # microseconds
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        assert isinstance(ev["args"], dict)
        if ev["ph"] == "X":                             # complete span
            assert ev["dur"] >= 0.0
        else:
            assert ev["ph"] == "i" and ev["s"] == "g"   # global instant
    return [e["name"] for e in trace["traceEvents"]]


def test_chrome_trace_schema_roundtrip(tmp_path):
    tr = SpanTracer(capacity=64, enabled=True)
    tr.instant("submit", tid=1, rid=7)
    with tr.span("prefill", tid=1, tokens=12):
        pass
    tr.instant("admit", tid=0, cat="sched")
    path = tmp_path / "trace.json"
    assert tr.write(str(path)) == 3
    with open(path) as fh:
        trace = json.load(fh)
    names = _check_chrome(trace)
    assert names == ["submit", "prefill", "admit"]
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert spans[0]["args"] == {"tokens": 12}


# ---------------------------------------------------------------------------
# counters + registry export
# ---------------------------------------------------------------------------


def test_counter_view_backward_compatible_surface():
    reg = MetricsRegistry()
    view = reg.counter_scope(rank=0).declare(["admitted", "failed"])
    view["admitted"] += 2
    view.update(failed=1)
    view["memory"] = {"pages": 4}        # non-int side object
    assert dict(view, extra=9)["extra"] == 9
    assert view["memory"] == {"pages": 4}
    assert ("memory", 4) not in view.int_items()
    # declare-if-absent: a revived rank re-declaring must not zero
    again = reg.counter_scope(rank=0).declare(["admitted", "failed"])
    assert again is view and again["admitted"] == 2
    # distinct label sets are distinct scopes
    assert reg.counter_scope(rank=1)["admitted"] == 0 \
        if "admitted" in reg.counter_scope(rank=1) else True


def test_registry_prometheus_export():
    reg = MetricsRegistry()
    view = reg.counter_scope(rank=0).declare(["admitted"])
    view["admitted"] += 3
    reg.gauge("serve_queue_depth", 5)
    reg.gauge("serve_none_gauge", lambda: None)          # skipped
    reg.histogram("serve_ttft_seconds", (0.1, 1.0),
                  slo="interactive").observe(0.05)
    reg.histogram("serve_ttft_seconds", (0.1, 1.0),
                  slo="interactive").observe(0.5)
    reg.register_collector(lambda: {"serve_custom_total": 7}, key="c")
    reg.register_collector(lambda: {"serve_custom_total": 8}, key="c")
    text = reg.prometheus()
    assert 'serve_admitted_total{rank="0"} 3' in text
    assert "# TYPE serve_admitted_total counter" in text
    assert "serve_queue_depth 5" in text
    assert "serve_none_gauge" not in text
    assert 'le="0.1"' in text and 'le="+Inf"' in text
    assert 'serve_ttft_seconds_count{slo="interactive"} 2' in text
    # keyed collector registration is idempotent — the replacement wins
    assert "serve_custom_total 8" in text
    assert "serve_custom_total 7" not in text


def test_rolling_rate_trims_on_add():
    """A rate nobody reads (a server nobody scrapes) still holds at most
    one window of events."""
    r = RollingRate(window_s=1.0)
    for i in range(2000):
        r.add(1, t=i * 0.01)
    assert len(r._events) <= 101
    assert r.per_s(now=19.99) == pytest.approx(101.0)


def test_telemetry_imports_without_jax():
    """The analyzer's telemetry pass imports this module: no JAX."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = ("import sys; sys.path.insert(0, %r); "
            "import repro.serve.telemetry; "
            "assert 'jax' not in sys.modules, 'jax imported'" % src)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_path_gauges_and_accept_ema():
    tel = Telemetry()
    assert tel.tok_s("packed") == 0.0
    tel.note_tokens("packed", 40)
    assert tel.tok_s("packed") > 0.0
    text = tel.prometheus()
    assert 'serve_path_tok_s{path="packed"}' in text
    assert "serve_spec_accept_ema" not in text   # None until first round
    tel.note_spec_round(3, 4)
    assert tel.accept_ema.value == pytest.approx(0.75)
    tel.note_spec_round(0, 0)                    # no division by zero
    assert "serve_spec_accept_ema 0.75" in tel.prometheus()
    assert "admitted" in DECLARED_STATS          # contract sanity


# ---------------------------------------------------------------------------
# bit-identity: tracing armed must not move a single token
# ---------------------------------------------------------------------------


def _setup():
    cfg = reduced(get_config("qwen3-32b"), layers=2, d_model=64, vocab=64)
    params = lm.init_params(KEY, cfg)
    params = jax.tree.map(lambda a: a * 3.0, params)  # see test_scheduler
    return cfg, params


def _mk_requests(n, rng, max_new=6):
    return [Request(rid=i,
                    prompt=rng.integers(0, 64, size=(int(
                        rng.integers(4, 30)),)).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


_PLAIN = {}
_PAGED = dict(kv_pages=16, kv_page_len=8)
_SHARE = dict(kv_pages=16, kv_page_len=8, kv_share=True)
_SPEC = dict(kv_pages=16, kv_page_len=8, draft_sparsity=0.75, draft_k=4)
# the engine's spans in a JAX profiler trace (the profiler sink)
_PHASES = {"step", "admission", "prefill", "inputs", "decode", "read",
           "emit"}


def _profiled_span_names(tmp_path, fn):
    """Run ``fn`` under the JAX profiler; the names of the host events
    it recorded."""
    import glob
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    names = {e.name for plane in pd.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    return out, names


@pytest.mark.parametrize("kw, sink", [
    (_PLAIN, "ring"), (_PAGED, "ring"), (_SHARE, "ring"), (_SPEC, "ring"),
    (_PLAIN, "profiler"), (_SHARE, "profiler"), (_SPEC, "profiler"),
], ids=["plain", "paged", "share", "spec", "plain-profiler",
        "share-profiler", "spec-profiler"])
def test_engine_streams_bit_identical_with_tracing(kw, sink, tmp_path):
    cfg, params = _setup()

    def drive(trace):
        rng = np.random.default_rng(0)
        eng = Engine(params, cfg, batch_slots=2, cache_len=64,
                     telemetry=Telemetry(trace=trace), **kw)
        done = eng.run(_mk_requests(5, rng))
        return {r.rid: r.out_tokens for r in done}, eng

    ref, _ = drive(False)
    if sink == "profiler":
        (got, eng), names = _profiled_span_names(
            tmp_path, lambda: drive(False))
        assert got == ref
        assert {"engine." + n for n in _PHASES} <= names, names
        if "draft_sparsity" in kw:
            assert "engine.spec_round" in names, names
        assert len(eng.telemetry.tracer) == 0       # the ring stayed off
        return
    got, eng = drive(True)
    assert got == ref
    names = set(_check_chrome(eng.telemetry.tracer.chrome()))
    assert {"submit", "admit", "prefill", "token"} <= names, names
    assert _PHASES <= names, names
    if "draft_sparsity" in kw:
        assert "spec_round" in names, names


def test_engine_step_phases_reach_the_profiler_sink():
    """The spans of ``Engine.step``, in order, in a fake sink: an
    admitting step, then a decode step that admits nothing."""
    cfg, params = _setup()
    eng = Engine(params, cfg, batch_slots=2, cache_len=64, buckets=(32,))
    sink = eng.telemetry.tracer.annotate = _Sink()
    rng = np.random.default_rng(1)
    reqs = _mk_requests(2, rng, max_new=3)
    for r in reqs:
        eng.submit(r)
    eng.step()
    first = [(k, n) for k, n, _ in sink.log]
    assert first == [
        ("enter", "step"), ("enter", "admission"), ("enter", "prefill"),
        ("exit", "prefill"), ("enter", "read"), ("exit", "read"),
        ("exit", "admission"), ("enter", "inputs"), ("exit", "inputs"),
        ("enter", "decode"), ("exit", "decode"), ("enter", "read"),
        ("exit", "read"), ("enter", "emit"), ("exit", "emit"),
        ("exit", "step")]
    args = {n: a for k, n, a in sink.log if k == "exit"}
    assert args["admission"] == {"rids": [0, 1]}
    assert args["prefill"] == {"rids": [0, 1], "rows": 2, "S": 32}
    assert args["inputs"] == {"slots": 2}
    assert args["emit"] == {"tokens": 2}
    del sink.log[:]
    eng.step()
    assert [n for k, n, _ in sink.log if k == "enter"] == [
        "step", "inputs", "decode", "read", "emit"]
    # greedy streams as with no sink at all
    solo = Engine(params, cfg, batch_slots=2, cache_len=64, buckets=(32,))
    ref = {r.rid: r.out_tokens for r in solo.run(
        _mk_requests(2, np.random.default_rng(1), max_new=3))}
    while eng.has_work():
        eng.step()
    assert {r.rid: r.out_tokens for r in reqs} == ref


@pytest.mark.parametrize("kw", [dict(buckets=(16, 32)), _SHARE],
                         ids=["bucketed", "share"])
def test_prefill_computed_tokens_counts_rows_times_length(kw):
    cfg, params = _setup()
    eng = Engine(params, cfg, batch_slots=2, cache_len=64, **kw)
    shapes = []
    for name in ("_prefill", "_prefill_past"):
        fn = getattr(eng, name, None)
        if fn is None:
            continue

        def rec(params, toks, *a, _fn=fn):
            shapes.append(tuple(toks.shape))
            return _fn(params, toks, *a)
        setattr(eng, name, rec)
    rng = np.random.default_rng(3)
    reqs = _mk_requests(4, rng)
    if "kv_share" in kw:                 # a shared prefix: suffix prefill
        for r in reqs[2:]:
            r.prompt = np.concatenate([reqs[0].prompt[:16], r.prompt])
        reqs[0].prompt = reqs[0].prompt[:20] if len(
            reqs[0].prompt) >= 20 else np.concatenate(
                [reqs[0].prompt, np.ones((20,), np.int32)])[:20]
    eng.run(reqs)
    assert shapes
    assert eng.stats["prefill_computed_tokens"] == sum(
        g * s for g, s in shapes)
    assert eng.stats["prefill_computed_tokens"] > eng.stats["prefill_tokens"]
    if "kv_share" in kw:
        assert eng.stats["prefill_tokens_skipped"] > 0


def _lowered_module_names(calls):
    """Module names of jitted functions lowered with the arguments of
    their first recorded call: {name: (jitted, args)}."""
    out = {}
    for name, (fn, args) in calls.items():
        text = fn.lower(*args).as_text()
        out[name] = text.split("module @", 1)[1].split(" ", 1)[0]
    return out


def _record_calls(obj, names):
    """Wrap the jitted attributes ``names`` of ``obj`` to record their
    first call: {name: (jitted, args)}."""
    calls = {}
    for name in names:
        fn = getattr(obj, name)

        def rec(*a, _fn=fn, _name=name):
            calls.setdefault(_name, (_fn, a))
            return _fn(*a)
        setattr(obj, name, rec)
    return calls


ENGINE_JITS = ("_decode", "_prefill", "_prefill_past", "_sample", "_snap",
               "_restore", "_draft_decode", "_verify")
POOL_JITS = ("_read", "_write", "_scrub", "_copy", "_merge")


def test_engine_and_pool_jits_compile_under_their_names():
    cfg, params = _setup()
    rng = np.random.default_rng(5)
    # contiguous: admission, decode, a kept-KV preemption and its resume
    eng = Engine(params, cfg, batch_slots=2, cache_len=64)
    calls = _record_calls(eng, [n for n in ENGINE_JITS if hasattr(eng, n)])
    for r in _mk_requests(2, rng):
        eng.submit(r)
    eng.step()
    eng.queue.insert(0, eng.preempt_slot(0, keep_kv=True))
    while eng.has_work():
        eng.step()
    names = _lowered_module_names(calls)
    assert set(names) == {"_decode", "_prefill", "_sample", "_snap",
                          "_restore"}
    # paged, sharing, speculating: every engine program of that layout
    peng = Engine(params, cfg, batch_slots=2, cache_len=64, **_SHARE,
                  draft_sparsity=0.75, draft_k=4)
    pcalls = _record_calls(peng, [n for n in ENGINE_JITS
                                  if hasattr(peng, n)])
    reqs = _mk_requests(3, rng)
    for r in reqs[1:]:
        r.prompt = np.concatenate([reqs[0].prompt[:16], r.prompt])
    reqs[0].prompt = np.concatenate([reqs[0].prompt,
                                     np.ones((16,), np.int32)])
    peng.run(reqs)
    names.update({"paged" + k: v for k, v in
                  _lowered_module_names(pcalls).items()})
    assert {"paged_decode", "paged_prefill", "paged_prefill_past",
            "paged_draft_decode", "paged_verify"} <= set(names), names
    # the page pool's programs, lowered on its own arrays
    pool = peng.pool
    ids = jax.numpy.asarray([2, 3], jax.numpy.int32)
    one = jax.numpy.asarray(2, jax.numpy.int32)
    pool_args = {
        "_read": (pool._read, (pool.data, ids)),
        "_write": (pool._write, (pool.data, ids,
                                 pool._read(pool.data, ids))),
        "_scrub": (pool._scrub, (pool.data, ids)),
        "_copy": (pool._copy, (pool.data, one, one)),
        "_merge": (pool._merge, (pool.data, one, one, one, one)),
    }
    names.update({"pool" + k: v for k, v in
                  _lowered_module_names(pool_args).items()})
    assert set(POOL_JITS) <= {k[len("pool"):] for k in names
                              if k.startswith("pool")}
    for k, v in names.items():
        assert v.startswith("jit_") and "unknown" not in v \
            and "lambda" not in v, (k, v)
    assert names["_decode"] == "jit__decode_step"
    assert names["paged_decode"] == "jit__paged_decode_step"
    assert names["_prefill"] == "jit__prefill_and_write"
    assert names["_sample"] == "jit__sample_tokens"


def test_preempt_spill_resume_traced_and_bit_identical(tmp_path):
    """The forced preempt→spill→fault cycle from test_memory.py, with
    the tracer armed: streams still equal the (untraced) solo engine,
    and the written trace file carries the full lifecycle."""
    cfg, params = _setup()
    rng = np.random.default_rng(4)
    batch = Request(rid=0, prompt=rng.integers(0, 64, size=(18,))
                    .astype(np.int32), max_new_tokens=14, slo="batch")
    inter = Request(rid=1, prompt=rng.integers(0, 64, size=(40,))
                    .astype(np.int32), max_new_tokens=3,
                    slo="interactive", deadline=0.01)
    ref = {}
    for r in (batch, inter):
        solo = Request(rid=r.rid, prompt=r.prompt,
                       max_new_tokens=r.max_new_tokens)
        ref[r.rid] = Engine(params, cfg, batch_slots=1,
                            cache_len=64).run([solo])[0].out_tokens
    sched = ShardedScheduler(
        params, cfg, ranks=1,
        sched=SchedulerConfig(slots_per_rank=1, cache_len=64,
                              policy="edf", preempt=True,
                              preempt_mode="kv", kv_pages=8,
                              kv_page_len=8, kv_host_pages=8),
        telemetry=Telemetry(trace=True))
    assert sched.submit(batch)
    for _ in range(4):
        sched.step()
    assert sched.submit(inter)
    done = []
    while sched.has_work():
        done.extend(sched.step())
    assert {r.rid: r.out_tokens for r in done} == ref
    assert sched.stats()["preemptions"] >= 1
    path = tmp_path / "sched_trace.json"
    sched.telemetry.write_trace(str(path))
    with open(path) as fh:
        names = set(_check_chrome(json.load(fh)))
    assert {"submit", "admit", "prefill", "token", "preempt",
            "spill", "resume"} <= names, names
    # TTFT histogram observed both SLO classes through the same run
    ttft = sched.stats()["ttft"]
    assert ttft["interactive"]["count"] >= 1
    assert ttft["batch"]["count"] >= 1


@pytest.mark.chaos
def test_chaos_kill_trace_loads_and_carries_recovery(tmp_path):
    """The acceptance trace: a ``kill:0@3`` chaos run (then a revive)
    exports one Perfetto-loadable file whose events span both hosts'
    rank activity (host pids) and the frontend's own retry/death/revive
    instants (pid -1) — and the streams still finish bit-identically."""
    cfg, params = _setup()
    rng = np.random.default_rng(0)
    reqs = _mk_requests(6, rng, max_new=4)
    solo = {}
    for r in reqs:
        s = Request(rid=r.rid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens)
        solo[r.rid] = Engine(params, cfg, batch_slots=1,
                             cache_len=64).run([s])[0].out_tokens
    chaos = ChaosMonkey(ChaosConfig(kill_at_step={0: 3}))
    hosts = make_local_hosts(
        params, cfg, hosts=2,
        sched=SchedulerConfig(slots_per_rank=2, cache_len=64),
        chaos=chaos, trace=True)
    fe = ClusterFrontend(hosts, FrontendConfig(retries=2,
                                               backoff_base=0.001,
                                               rng_seed=1))
    completed = fe.run(reqs)
    assert {r.rid: r.out_tokens for r in completed} == solo
    assert fe.n_retries >= 1
    fe.revive_host(0)

    path = tmp_path / "chaos_trace.json"
    n = fe.write_trace(str(path))
    with open(path) as fh:
        trace = json.load(fh)
    assert len(trace["traceEvents"]) == n
    names = set(_check_chrome(trace))
    # (a host-level kill leaves its ranks intact, so host_revive — not
    # the scheduler's revive_rank — is the recovery marker here)
    assert {"submit", "admit", "prefill", "token", "host_kill",
            "host_dead", "retry", "host_revive"} <= names, names
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert {-1, 0, 1} <= pids, pids      # frontend + both hosts
    # events are globally time-ordered (the exporter sorts the concat)
    ts = [e["ts"] for e in trace["traceEvents"]]
    assert ts == sorted(ts)
    # cluster-level Prometheus: per-host counter series, no duplicates
    text = fe.prometheus()
    assert 'host="0"' in text and 'host="1"' in text
    assert "serve_frontend_retries_total" in text
    # merged TTFT view aggregates across hosts
    ttft = fe.stats()["ttft"]
    assert sum(d["count"] for d in ttft.values()) >= len(reqs)


def test_exec_path_labels_feed_gauges():
    from repro.configs import SASPConfig
    from repro.serve.engine import _exec_path_label
    cfg, params = _setup()
    assert _exec_path_label(params, cfg) == "dense"
    sasp = SASPConfig(enabled=True, block_k=8, block_n=8, sparsity=0.25)
    assert _exec_path_label(
        params, dataclasses.replace(cfg, sasp=sasp)) == sasp.path
    assert _exec_path_label(
        params, dataclasses.replace(
            cfg, sasp=dataclasses.replace(sasp, quantize=True))) == "int8"
    # decode tokens are credited to the engine's resolved label
    eng = Engine(params, cfg, batch_slots=1, cache_len=64)
    assert eng.path_label == "dense"
    rng = np.random.default_rng(2)
    eng.run(_mk_requests(1, rng, max_new=4))
    assert eng.telemetry.tok_s("dense") > 0.0
