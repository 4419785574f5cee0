"""The trace reduction, on a hand-built trace whose numbers are known
and on a small trace recorded on a TPU v5e (a few decode steps of the
q3s50-decode cell, ``data/q3s50-decode.xplane.pb``)."""
import os
from types import SimpleNamespace as NS

import pytest

import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "q3s50-decode.xplane.pb")


def ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3)


def fake():
    ops = [ev("%while.1 = (f32[8]{0}) while(f32[8]{0} %p)", 10, 45),
           ev("fusion.1", 10, 15), ev("%k.1 = bf16[8,64]{1,0} "
                                       "custom-call(bf16[8,64]{1,0} %x)",
                                       25, 30),
           ev("fusion.2", 70, 10), ev("fusion.3", 200, 50)]
    # the engine's programs are named by their dispatch on the host
    mods = [ev("jit__unknown(12)", 10, 45), ev("jit__unknown(12)", 70, 10),
            ev("jit__sample_tokens(-4)", 82, 1),
            ev("jit__unknown(3)", 200, 50)]
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=ops), NS(name="XLA Modules", events=mods)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("window", 0, 300), ev("step", 5, 80), ev("step", 90, 100),
        ev("wait for arrival", 120, 60),
        ev("PjitFunction(_decode_step)", 6, 3),
        ev("PjitFunction(_decode_step)", 7, 1),     # an echo, nested
        ev("PjitFunction(_decode_step)", 66, 3),
        ev("PjitFunction(_sample_tokens)", 81, 1),
        ev("PjitFunction(_prefill_and_write)", 195, 3)])])
    return NS(planes=[host, dev])


def test_a_hand_built_trace():
    r = devtrace.reduce_profile(fake())
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(300e-6)
    # ops cover [10, 55] + [70, 80] + [200, 250] us
    assert r["busy_s"] == pytest.approx(105e-6)
    assert r["programs"]["_decode_step"] == [2, pytest.approx(55e-6)]
    assert r["programs"]["_prefill_and_write"] == [1, pytest.approx(50e-6)]
    assert r["programs"]["_sample_tokens"][0] == 1
    # self time: the while's 45 us less the 15 + 30 nested in it
    assert r["ops"]["%while.1 = (f32[8]{0}) while(f32[8]{0} %p)"][1] == \
        pytest.approx(0.0)
    k = "%k.1 = bf16[8,64]{1,0} custom-call(bf16[8,64]{1,0} %x)"
    assert r["ops"][k] == [1, pytest.approx(30e-6)]
    gaps = r["idle_gaps"]
    assert gaps[0][0].startswith("wait for arrival")
    assert gaps[0][1] == pytest.approx(120e-6)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    assert sum(g[1] for g in gaps) == pytest.approx(300e-6 - 105e-6)
    assert r["device_ops"][0][0] == "fusion.3"
    assert devtrace.op_label(k) == "%k.1 custom-call bf16[8,64]"


def test_a_trace_without_a_device_is_refused():
    host = NS(name="/host:CPU", lines=[])
    with pytest.raises(ValueError, match="TPU"):
        devtrace.reduce_profile(NS(planes=[host]))


def test_a_recorded_v5e_trace():
    r = devtrace.reduce_file(RECORDED)
    assert r["chips"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["programs"]["_decode_step"][0] == 9
    assert r["programs"]["_prefill_and_write"][0] == 1
    kernels = [k for k in r["ops"] if "tpu_custom_call" in k]
    assert kernels and all("[200,5120,128]" in k for k in kernels)
    assert len(r["device_ops"]) <= devtrace.TOP
    assert len(r["idle_gaps"]) <= devtrace.TOP
    assert all(isinstance(n, str) and s >= 0 for n, s in r["idle_gaps"])
