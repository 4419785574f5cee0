"""The engine-span reduction and its three readers (``decode_host_ms``,
``admit_host_ms``, ``prefill_useful_share``): on a hand-built trace
whose numbers are known, on the recorded trace of a program without
engine spans (nothing is reported), and on a short trace recorded on a
TPU v5e with them (``data/q3s50-engine.xplane.pb``: decode steps and
one admission of the q3s50-decode cell)."""
import os
import shutil
from types import SimpleNamespace as NS

import pytest

import devtrace
import engine_spans
import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OLD = os.path.join(DATA, "q3s50-decode.xplane.pb")
RECORDED = os.path.join(DATA, "q3s50-engine.xplane.pb")
READERS = ("decode_host_ms", "admit_host_ms", "prefill_useful_share")


def ev(name, start_us, end_us, **stats):
    return NS(name=name, start_ns=start_us * 1e3,
              duration_ns=(end_us - start_us) * 1e3,
              stats=list(stats.items()))


def e(name, a, b, **stats):
    return ev("engine." + name, a, b, **stats)


def fake():
    host = [ev("window", 0, 300), ev("step", 4, 86),
            e("step", 5, 85), e("admission", 6, 40, rids="[0]"),
            e("prefill", 8, 12, rows=2, S=8), e("read", 20, 35),
            e("inputs", 41, 50, slots=1), e("decode", 50, 55),
            e("read", 55, 75), e("emit", 75, 84, tokens=1),
            e("step", 90, 150), e("inputs", 91, 100), e("decode", 100, 104),
            e("read", 104, 140), e("emit", 140, 149),
            e("step", 200, 260), e("inputs", 201, 215),
            e("decode", 215, 218), e("read", 218, 250), e("emit", 250, 259),
            e("step", 310, 320)]                  # after the window
    ops = [ev("fusion.1", 10, 35), ev("fusion.2", 52, 74),
           ev("fusion.3", 101, 139), ev("fusion.4", 216, 249)]
    mods = [ev("jit__prefill_and_write(1)", 10, 35),
            ev("jit__decode_step(2)", 52, 74),
            ev("jit__decode_step(2)", 101, 139),
            ev("jit__unknown(3)", 216, 249)]
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=ops), NS(name="XLA Modules", events=mods)])
    return NS(planes=[NS(name="/host:CPU", lines=[
        NS(name="python3", events=host)]), dev])


def test_a_hand_built_trace():
    r = engine_spans.reduce_profile(fake())
    us = pytest.approx
    assert r["window_s"] == us(300e-6)
    # idle: [0,10] [35,52] [74,101] [139,216] [249,300] us
    assert r["idle_s"] == us(182e-6)
    assert r["covered_s"] == us(82e-6)
    b = r["by_name"]
    assert b["step"]["count"] == 3               # the fourth lies after
    assert b["step"]["total_s"] == us(200e-6)
    # self: 80 less its children's 77, and 2 x (60 less 58)
    assert b["step"]["self_s"] == us(7e-6)
    assert b["admission"]["self_s"] == us(15e-6)
    idle = {k: v["idle_s"] for k, v in b.items()}
    assert idle == {"step": us(7e-6), "admission": us(7e-6),
                    "prefill": us(2e-6), "inputs": us(32e-6),
                    "decode": us(4e-6), "read": us(3e-6),
                    "emit": us(27e-6)}
    top = dict(r["idle_by_span"])
    assert top[engine_spans.NONE] == us(100e-6)
    assert r["idle_by_span"][0][0] == engine_spans.NONE
    assert r["idle_by_span"][1] == ["inputs", us(32e-6)]
    assert sum(top.values()) == us(182e-6)
    assert r["unnamed_modules"] == 1
    # the spans carry their args
    (adm,) = [s for s in engine_spans.walk(r["roots"])
              if s.name == "admission"]
    assert adm.args == {"rids": "[0]"}
    assert adm.below("read") == us(15e3)         # ns


def test_the_readers_on_a_hand_built_record():
    rec = {"trace": {"window_s": 300e-6}, "counters": {"prefill_tokens": 12},
           engine_spans.KEY: engine_spans.reduce_profile(fake())}
    got = {n: harness.read_metric(n, rec) for n in READERS}
    # decode steps with no admission: (60 - 36) and (60 - 32) us
    assert got["decode_host_ms"] == pytest.approx(0.026)
    # the admission: 34 us less its 15 us read
    assert got["admit_host_ms"] == pytest.approx(0.019)
    # 12 prompt tokens of 2 rows x 8
    assert got["prefill_useful_share"] == pytest.approx(75.0)


def test_a_trace_without_engine_spans_reports_nothing(tmp_path):
    """The recorded trace of a program that has no engine spans: the
    readers find its directory and report nothing, and raise nothing."""
    d = tmp_path / "bench-trace-x" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    shutil.copy(OLD, d / "host.xplane.pb")
    rec = {"trace": devtrace.reduce_file(OLD),
           "counters": {"prefill_tokens": 100}}
    assert engine_spans.for_record(rec, str(tmp_path)) is None
    assert all(harness.read_metric(n, rec) is None for n in READERS)


def _recorded_dir(tmp_path, name="bench-trace-y"):
    d = tmp_path / name / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    shutil.copy(RECORDED, d / "host.xplane.pb")
    return tmp_path


def test_a_recorded_v5e_trace_with_engine_spans(tmp_path, capsys):
    tmp = _recorded_dir(tmp_path)
    dev = devtrace.reduce_file(RECORDED)
    # another run's trace of another window is passed over
    other = {"trace": dict(dev, window_s=dev["window_s"] + 1e-3),
             "counters": {"prefill_tokens": 1}}
    assert engine_spans.for_record(other, str(tmp)) is None
    rec = {"trace": dev, "counters": {"prefill_tokens": 1}}
    r = engine_spans.for_record(rec, str(tmp))
    assert r is not None and rec[engine_spans.KEY] is r
    err = capsys.readouterr().err
    assert "under an engine span" in err and "idle_by_span" in err
    names = {s.name for s in engine_spans.walk(r["roots"])}
    assert {"step", "admission", "prefill", "inputs", "decode", "read",
            "emit"} <= names, names
    assert r["unnamed_modules"] == 0
    assert r["covered_s"] >= 0.9 * r["idle_s"]
    (p,) = [s for s in engine_spans.walk(r["roots"]) if s.name == "prefill"]
    assert p.args["rows"] == 12 and p.args["S"] in (128, 256, 512, 1024)
    for n in READERS:
        assert harness.read_metric(n, rec) > 0
    # the engine's programs carry their own names
    assert dev["programs"]["_decode_step"][0] >= 3
    assert dev["programs"]["_prefill_and_write"][0] == 1


def test_the_ffn_kernel_is_found_by_shape_and_by_name():
    """``ffn_kernel_roofline`` finds the fused FFN kernel by its operand
    shapes; in the recorded trace exactly the ops it finds carry the
    kernel's name."""
    import importlib.util
    path = os.path.join(os.path.dirname(DATA), "..", "metrics",
                        "ffn_kernel_roofline.py")
    spec = importlib.util.spec_from_file_location("ffn_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ops = devtrace.reduce_file(RECORDED)["ops"]
    by_shape = {k for k in ops if mod.is_ffn_kernel(k, 5120)}
    by_name = {k for k in ops if '"kernel":"sasp_ffn_fused"' in k}
    assert by_shape and by_shape == by_name
