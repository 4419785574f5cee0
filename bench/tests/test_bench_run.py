"""``bench/run.py`` refuses to run without a TPU or without the program,
and a tiny cell driven on the CPU (the chip check skipped from here)
runs its window and proves correct against the reference."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_tiny

ROOT = os.path.dirname(bench_tiny.BENCH)


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "q3s50-decode",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        assert not line.strip().startswith("{"), line


def test_no_tpu_means_no_result():
    p = _run(ROOT)
    _no_result(p)
    assert "no TPU" in p.stderr


def test_the_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench_tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    _no_result(p)


@pytest.mark.parametrize("name", ["qwen3-32b-sasp50", "qwen2.5-32b-dense"])
def test_a_tiny_cell_runs_and_proves_correct(name):
    res = bench_tiny.run(bench_tiny.cell(name))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tok_s", "ttft_p90_ms", "itl_p50_ms",
                                   "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert list(res["checks"])[-1] == "gap_mean"
    json.dumps(res)


def test_the_window_opens_after_the_lead_in():
    import time

    import harness
    c = bench_tiny.cell("qwen2.5-32b-dense")
    eng, cfg, _ = harness.build_engine(c.config, c.mix, 5)
    harness.warm_up(eng, c.mix)
    win = harness.Window(eng, c.mix, 5, None, cfg.vocab_size)
    opened = []
    start = time.monotonic()
    win.run(1.0, lambda: opened.append(time.monotonic()))
    assert len(opened) == 1 and opened[0] - start >= c.mix.lead_s
    assert win.t0 >= opened[0]
    stamps = [t for ts in win.tok_t.values() for t in ts]
    assert min(stamps) < win.t0             # the lead-in served tokens
    win.end_to_end()
    assert win.counts["tokens"] == sum(win.t0 <= t <= win.t1
                                       for t in stamps)
    assert win.counts["itl"] == sum(
        win.t0 <= b <= win.t1 for ts in win.tok_t.values()
        for b in ts[1:])
