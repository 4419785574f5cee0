"""launch/serve.py argument validation: malformed --mesh and
--ranks/--buckets misuse must fail with a clear usage error at parse
time, not as a cryptic make_mesh / submesh shape failure downstream."""
import dataclasses
import os
import subprocess
import sys

import jax
import pytest

from repro.launch.serve import check_ranks, parse_buckets, parse_mesh
from repro.launch.mesh import auto_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("spec", ["2", "a,b", "1,2,3", ",2", "2,"])
def test_parse_mesh_rejects_malformed_spec(spec, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "")       # parse_mesh may touch it
    with pytest.raises(SystemExit, match="--mesh expects 'DP,TP'"):
        parse_mesh(spec)


@pytest.mark.parametrize("spec", ["0,2", "2,0", "0,0"])
def test_parse_mesh_rejects_zero_axes(spec, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "")
    with pytest.raises(SystemExit, match="must both be >= 1"):
        parse_mesh(spec)


def test_parse_mesh_none_and_valid(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "")
    assert parse_mesh(None) is None
    assert parse_mesh("") is None
    mesh = parse_mesh("1,1")                  # fits any device count
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_check_ranks_exceeding_dp_size_is_a_clear_error():
    mesh = auto_mesh((1, 1), ("data", "model"))
    with pytest.raises(SystemExit, match="exceeds the mesh's DP size"):
        check_ranks(2, mesh)


def test_check_ranks_accepts_match_and_meshless():
    mesh = auto_mesh((1, 1), ("data", "model"))
    check_ranks(None, mesh)                   # omitted: mesh decides
    check_ranks(1, mesh)                      # equals DP size: fine
    check_ranks(7, None)                      # meshless: any count


def test_parse_buckets_forms():
    assert parse_buckets(None, 512) is None
    assert parse_buckets("", 512) is None
    assert parse_buckets("4", 512) == (64, 128, 256, 512)
    assert parse_buckets("32,64,128", 512) == (32, 64, 128)
    for bad in ("x", "0", "8,0", "-1"):
        with pytest.raises(SystemExit, match="--buckets"):
            parse_buckets(bad, 512)
    # a bucket beyond the cache could never admit: loud error, not a
    # silent fall-back to exact shapes
    with pytest.raises(SystemExit, match="cache-len"):
        parse_buckets("128,256", 64)


def _main_exits(argv, match, monkeypatch):
    import sys

    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", ["serve"] + argv)
    with pytest.raises(SystemExit, match=match):
        serve.main()


def test_frontend_flags_validate_before_model_build(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "")
    _main_exits(["--hosts", "0"], "--hosts must be >= 1", monkeypatch)
    _main_exits(["--hosts", "2", "--mesh", "1,1"],
                "in-process hosts without a mesh", monkeypatch)
    _main_exits(["--chaos", "kill:0@3"], "add --hosts N", monkeypatch)
    _main_exits(["--hosts", "2", "--chaos", "explode:0@3"],
                "--chaos", monkeypatch)


def test_engine_rejects_buckets_beyond_cache_len():
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.models import lm
    from repro.serve.engine import Engine

    cfg = reduced(get_config("qwen3-32b"), layers=1, d_model=32,
                  vocab=32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="cache_len"):
        Engine(params, cfg, batch_slots=1, cache_len=32,
               buckets=(16, 64))


def _valid_kv(**over):
    """validate_kv_flags kwargs for a healthy paged+spec config;
    override per-case to isolate the rule under test."""
    from repro.launch.serve import validate_kv_flags
    kw = dict(kv_pages=24, kv_watermark=0.9, kv_share=True,
              kv_share_min_pages=1, int8_kv=False, draft_sparsity=0.75,
              draft_k=4, draft_int8=False, kv_dedup_every=64,
              cache_len=256)
    kw.update(over)
    return validate_kv_flags(**kw)


def test_validate_kv_flags_accepts_healthy_combinations():
    _valid_kv()                                    # paged+share+spec
    _valid_kv(draft_sparsity=None, kv_dedup_every=0)
    _valid_kv(kv_pages=None, kv_share=False, draft_sparsity=None,
              kv_dedup_every=0)                    # contiguous engine
    _valid_kv(kv_share=False, kv_dedup_every=0,
              int8_kv=False, draft_int8=True)      # int8 drafter pack


@pytest.mark.parametrize("over,match", [
    (dict(kv_watermark=0.0), "--kv-watermark"),
    (dict(kv_watermark=1.5), "--kv-watermark"),
    (dict(kv_pages=0), "--kv-pages must be >= 1"),
    (dict(kv_pages=None, draft_sparsity=None, kv_dedup_every=0),
     "--kv-share requires --kv-pages"),
    (dict(int8_kv=True, draft_sparsity=None, kv_dedup_every=0),
     "--kv-share is incompatible with --int8-kv"),
    (dict(kv_share_min_pages=0), "--kv-share-min-pages"),
    (dict(kv_pages=None, kv_share=False, kv_dedup_every=0),
     "--draft-sparsity requires --kv-pages"),
    (dict(kv_share=False, int8_kv=True, kv_dedup_every=0),
     "--draft-sparsity is incompatible with --int8-kv"),
    (dict(draft_sparsity=1.0), "--draft-sparsity must lie in"),
    (dict(draft_sparsity=-0.5), "--draft-sparsity must lie in"),
    (dict(draft_k=0), "--draft-k must be >= 1"),
    (dict(draft_k=400), "shrink --draft-k"),
    (dict(draft_sparsity=None, draft_int8=True, kv_dedup_every=0),
     "add --draft-sparsity"),
    (dict(kv_dedup_every=-1), "--kv-dedup-every must be >= 0"),
    (dict(kv_share=False), "--kv-dedup-every requires"),
    (dict(kv_pages=None, kv_share=False, draft_sparsity=None),
     "--kv-dedup-every requires"),
])
def test_validate_kv_flags_rejects_bad_combinations(over, match):
    with pytest.raises(SystemExit, match=match):
        _valid_kv(**over)


def test_draft_flags_validate_identically_on_all_three_paths(
        monkeypatch):
    """The same bad --draft-* combo must exit with the same message
    whether the launcher would build a frontend, a scheduler, or a
    solo engine — the whole point of the consolidated validator."""
    monkeypatch.setenv("XLA_FLAGS", "")
    for path in ([], ["--scheduler"], ["--hosts", "2"]):
        _main_exits(path + ["--draft-sparsity", "0.75"],
                    "--draft-sparsity requires --kv-pages", monkeypatch)
        _main_exits(path + ["--kv-pages", "16", "--int8-kv",
                            "--draft-sparsity", "0.75"],
                    "incompatible with --int8-kv", monkeypatch)
        _main_exits(path + ["--draft-int8"],
                    "add --draft-sparsity", monkeypatch)
        _main_exits(path + ["--kv-pages", "16", "--kv-dedup-every",
                            "32"],
                    "--kv-dedup-every requires", monkeypatch)


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    from repro.launch import compile_cache as cc
    monkeypatch.delenv(cc.ENV, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = cc.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_lands_in_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing and a
    compile is written there."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_no_reduce_cuts_depth_only():
    """--no-reduce --layers N keeps the published widths; --reduce (the
    default) shrinks them for CPU runs."""
    from repro.configs import get_config
    from repro.launch.serve import build_parser, serving_config
    full = get_config("qwen3-32b")
    cfg = serving_config(build_parser().parse_args(
        ["--no-reduce", "--layers", "4", "--param-dtype", "bfloat16"]))
    assert cfg.num_layers == 4 and cfg.param_dtype == "bfloat16"
    assert cfg == dataclasses.replace(full, num_layers=4,
                                      param_dtype="bfloat16")
    small = serving_config(build_parser().parse_args(["--layers", "2"]))
    assert small.num_layers == 2 and small.d_model == 128
    assert serving_config(build_parser().parse_args([])).d_model == 128
