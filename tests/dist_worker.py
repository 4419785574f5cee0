"""Multi-device worker invoked by tests/test_distribution.py in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8.
Each mode prints one JSON line of results."""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import json
import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import auto_mesh


def out(**kw):
    print("RESULT " + json.dumps(kw))


def mode_sharded_train():
    from repro.configs import get_config, reduced
    from repro.distribution import context as dctx
    from repro.distribution import sharding as shd
    from repro.models import lm
    from repro.train.optimizer import AdamWConfig, adamw_init, \
        opt_state_shardings
    from repro.train.train_step import make_train_step

    cfg = reduced(get_config("qwen3-32b"), layers=2, d_model=64,
                  vocab=128)
    mesh = auto_mesh((4, 2), ("data", "model"))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamWConfig(lr=1e-3)
    opt = adamw_init(params, opt_cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 128)
    step = make_train_step(cfg, opt_cfg)

    # single-device reference
    p_ref, _, m_ref = step(params, opt, {"tokens": toks})

    with mesh, dctx.use_mesh(mesh):
        psh = shd.param_shardings(cfg, jax.eval_shape(lambda: params),
                                  mesh)
        osh = opt_state_shardings(cfg, jax.eval_shape(lambda: params),
                                  mesh, opt_cfg, psh)
        bsh = {"tokens": NamedSharding(mesh, P("data", None))}
        jstep = jax.jit(step, in_shardings=(psh, osh, bsh),
                        out_shardings=(psh, osh, None))
        p_sh, _, m_sh = jstep(params, opt, {"tokens": toks})
    diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(jax.tree.leaves(p_ref),
                               jax.tree.leaves(p_sh)))
    out(loss_ref=float(m_ref["loss"]), loss_sh=float(m_sh["loss"]),
        max_param_diff=diff)


def mode_moe_ep():
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.distribution.moe_ep import can_use_ep, moe_ffn_ep
    from repro.models import lm, moe as moe_mod

    cfg = reduced(get_config("granite-moe-1b-a400m"), layers=2,
                  d_model=64, vocab=128)
    # drop-free capacity: EP (per-shard caps) and local (global cap) then
    # dispatch identical token sets and must agree numerically
    cfg_nodrop = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    mesh = auto_mesh((4, 2), ("data", "model"))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    slot = jax.tree.map(lambda a: a[0],
                        params["segments"][0]["slot0"])["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 16, 64))

    assert can_use_ep(cfg, x.shape, mesh)
    y_l0, aux_l0 = moe_mod.moe_ffn_local(slot, cfg_nodrop, x)
    with mesh:
        y_e0, aux_e0 = jax.jit(lambda s, xx: moe_ffn_ep(
            s, cfg_nodrop, xx, mesh))(slot, x)
    denom = float(jnp.max(jnp.abs(y_l0))) + 1e-9
    rel_nodrop = float(jnp.max(jnp.abs(y_l0 - y_e0))) / denom

    # default capacity: outputs may differ on dropped tokens; mean gap
    # must stay small
    y_l1, _ = moe_mod.moe_ffn_local(slot, cfg, x)
    with mesh:
        y_e1, _ = jax.jit(lambda s, xx: moe_ffn_ep(
            s, cfg, xx, mesh))(slot, x)
    mean_rel = float(jnp.mean(jnp.abs(y_l1 - y_e1))
                     / (jnp.mean(jnp.abs(y_l1)) + 1e-9))
    out(rel_nodrop=rel_nodrop, mean_rel=mean_rel,
        aux_local=float(aux_l0), aux_ep=float(aux_e0))


def mode_grad_compress():
    from repro.train.grad_compress import compressed_psum

    mesh = auto_mesh((2, 4), ("pod", "data"))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 1024)) \
        * jnp.array([[1.0], [3.0]])      # different per pod

    def body(x_loc):
        y, res = compressed_psum(x_loc[0], "pod", None)
        return y[None], res[None]

    from repro.distribution.context import shard_map
    with mesh:
        y, res = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("pod", None),
            out_specs=(P("pod", None), P("pod", None))))(x)
    exact = jnp.mean(x, axis=0)
    err = float(jnp.max(jnp.abs(y[0] - exact)))
    amax = float(jnp.max(jnp.abs(x)))
    # one-step error bounded by shared-scale int8 resolution
    out(err=err, bound=amax / 127.0 * 1.01,
        residual_norm=float(jnp.abs(res).max()))


def mode_elastic_reshard():
    from repro.train.checkpoint import CheckpointManager

    state = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 8))}
    mesh1 = auto_mesh((4, 2), ("data", "model"))
    sh1 = {"w": NamedSharding(mesh1, P("data", "model"))}
    st1 = jax.device_put(state, sh1)
    mgr = CheckpointManager(sys.argv[2])
    mgr.save(1, st1)

    # "elastic": restore on a DIFFERENT mesh shape
    mesh2 = auto_mesh((2, 4), ("data", "model"))
    sh2 = {"w": NamedSharding(mesh2, P("data", "model"))}
    restored, _ = mgr.restore(jax.eval_shape(lambda: state),
                              shardings=sh2)
    ok_value = bool(jnp.allclose(restored["w"], state["w"]))
    ok_shard = restored["w"].sharding.is_equivalent_to(sh2["w"], 2)
    out(ok_value=ok_value, ok_shard=bool(ok_shard))


def mode_decode_sharded():
    from repro.configs import get_config, reduced
    from repro.distribution import context as dctx
    from repro.distribution import sharding as shd
    from repro.models import lm

    cfg = reduced(get_config("gemma3-4b"), layers=4, d_model=64,
                  vocab=128)
    mesh = auto_mesh((4, 2), ("data", "model"))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, S0 = 8, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S0 + 4), 0, 128)

    # unsharded reference
    logits_ref, caches = lm.prefill(params, cfg, toks[:, :S0],
                                    cache_len=S0 + 4)
    pos = jnp.full((B,), S0, jnp.int32)
    ref_step, _ = lm.decode_step(params, cfg, toks[:, S0:S0 + 1], pos,
                                 caches)

    with mesh, dctx.use_mesh(mesh):
        csh = shd.cache_shardings(cfg, mesh, B,
                                  jax.eval_shape(lambda: caches))
        caches_s = jax.device_put(caches, csh)
        step = jax.jit(lambda p, t, po, c: lm.decode_step(p, cfg, t, po,
                                                          c))
        got, _ = step(params, toks[:, S0:S0 + 1], pos, caches_s)
    out(max_diff=float(jnp.max(jnp.abs(got - ref_step))))


def mode_collective_parser_ground_truth():
    from repro.analysis.hlo import collective_bytes

    mesh = auto_mesh((8,), ("model",))
    L, M, N = 5, 64, 128

    def step(ws, x):
        def body(x, w):
            h = x @ w[0]
            y = h @ w[1].T        # contracts the sharded dim -> psum
            return y * 1e-3 + x, None
        x, _ = jax.lax.scan(body, x, ws)
        return x

    ws = jax.ShapeDtypeStruct((L, 2, M, N), jnp.float32)
    x = jax.ShapeDtypeStruct((M, M), jnp.float32)
    wsh = NamedSharding(mesh, P(None, None, None, "model"))
    with mesh:
        compiled = jax.jit(step, in_shardings=(
            wsh, NamedSharding(mesh, P()))).lower(ws, x).compile()
    got = collective_bytes(compiled.as_text())
    out(all_reduce=got.get("all-reduce", 0), expected=L * M * M * 4)


def mode_rs_ag_int8_ffn():
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.distribution import context as dctx
    from repro.models.ffn import ffn_apply, ffn_init

    cfg = dataclasses.replace(
        reduced(get_config("qwen3-32b"), layers=2, d_model=64, vocab=128),
        d_ff=128)
    cfg8 = dataclasses.replace(cfg, tp_comm="rs_ag_int8")
    p = ffn_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8, 64))
    y0 = ffn_apply(p, cfg, x)
    mesh = auto_mesh((4, 2), ("data", "model"))
    with mesh, dctx.use_mesh(mesh):
        y1 = jax.jit(lambda pp, xx: ffn_apply(pp, cfg8, xx))(p, x)
    rel = float(jnp.max(jnp.abs(y0 - y1))
                / (jnp.max(jnp.abs(y0)) + 1e-9))
    out(rel=rel)


def mode_packed_serve_mesh():
    from repro.configs import get_config, reduced
    from repro.launch.serve import build_serving_params
    from repro.models import lm
    from repro.serve.engine import Engine, Request

    cfg0 = reduced(get_config("qwen3-32b"), layers=2, d_model=64,
                   vocab=128)
    params0 = lm.init_params(jax.random.PRNGKey(0), cfg0)

    def reqs():
        rng = np.random.default_rng(0)
        return [Request(rid=i,
                        prompt=rng.integers(0, 128, size=(8 + 7 * i,))
                        .astype(np.int32), max_new_tokens=6)
                for i in range(3)]

    def streams(params, cfg, mesh=None):
        eng = Engine(params, cfg, batch_slots=2, cache_len=64, mesh=mesh)
        return {r.rid: r.out_tokens for r in eng.run(reqs())}

    # scope="all" exercises BOTH sharded drivers: the fused gated-FFN
    # kernel (d_ff visit shards + reduction) and the per-matrix packed
    # attention projections (col-sharded wq/wk/wv, row-sharded wo).
    # sparsity=0.25 (NOT 0.5): at 0.5 this reduced config prunes the
    # whole d_ff grid, the fused FFN output is identically zero, and the
    # bit-identity check has no discriminative power over the reduction.
    deploy = dict(path="packed", sparsity=0.25, block_k=8, block_n=8,
                  scope="all", verbose=False)
    p1, c1 = build_serving_params(params0, cfg0, **deploy)
    s_ref = streams(p1, c1)

    # the fused path must actually contribute signal (guards the check
    # above against config drift re-zeroing the FFN)
    from repro.core.deploy import packed_ffn_apply
    f0 = jax.tree.map(lambda a: a[0],
                      p1["segments"][0]["slot0"]["ffn"]["sasp_fused"])
    probe = packed_ffn_apply(jnp.ones((2, cfg0.d_model), jnp.float32), f0)
    fused_signal = float(jnp.abs(probe).max())

    mesh = auto_mesh((2, 2), ("data", "model"))
    p2, c2 = build_serving_params(params0, cfg0, mesh=mesh, **deploy)
    s_mesh = streams(p2, c2, mesh=mesh)
    out(equal=int(s_ref == s_mesh), n=len(s_ref),
        fused_signal=fused_signal,
        streams_ref={str(k): v for k, v in s_ref.items()},
        streams_mesh={str(k): v for k, v in s_mesh.items()})


def mode_sched_mesh():
    """Sharded-scheduler continuous batching on mesh packed paths
    (DESIGN.md §11 bit-identity contract): a slot freed by EOS is
    refilled from the queue, and every greedy stream equals the solo
    single-batch engine run on the same deployment. 1×2 mesh = one DP
    rank with TP-sharded visit lists; 2×2 mesh = two DP-rank engine
    shards on dp_submeshes, each with its own cache-slot slice."""
    from repro.configs import get_config, reduced
    from repro.launch.serve import build_serving_params
    from repro.models import lm
    from repro.serve.engine import Engine, Request
    from repro.serve.scheduler import SchedulerConfig, ShardedScheduler

    cfg0 = reduced(get_config("qwen3-32b"), layers=2, d_model=64,
                   vocab=128)
    params0 = lm.init_params(jax.random.PRNGKey(0), cfg0)
    # amplified weights: unit-scale random init greedy-decodes into a
    # constant stream, which would make the mid-decode EOS unreachable
    params0 = jax.tree.map(lambda a: a * 3.0, params0)
    deploy = dict(path="packed", sparsity=0.25, block_k=8, block_n=8,
                  scope="all", verbose=False)
    rng = np.random.default_rng(0)
    # 6 requests > 2 ranks × 2 slots, so BOTH mesh shapes build a queue
    # and exercise the mid-decode refill
    prompts = [rng.integers(0, 128, size=(6 + 4 * i,)).astype(np.int32)
               for i in range(6)]
    budgets = [8, 8, 4, 5, 6, 3]

    def solo(params, cfg, mesh, i, eos_id=None):
        eng = Engine(params, cfg, batch_slots=1, cache_len=64,
                     mesh=mesh)
        return eng.run([Request(rid=i, prompt=prompts[i],
                                max_new_tokens=budgets[i],
                                eos_id=eos_id)])[0].out_tokens

    results = {}
    stash = {}
    for name, shape in (("1x2", (1, 2)), ("2x2", (2, 2))):
        mesh = auto_mesh(shape, ("data", "model"))
        p, c = build_serving_params(params0, cfg0, mesh=mesh, **deploy)
        # EOS for request 1: first token in its stream with no earlier
        # occurrence, so the slot frees MID-DECODE and is refilled
        stream1 = solo(p, c, mesh, 1)
        eos_at = next(i for i in range(1, len(stream1) - 1)
                      if stream1[i] not in stream1[:i])
        eos_id = int(stream1[eos_at])
        ref = {i: solo(p, c, mesh, i, eos_id=eos_id if i == 1 else None)
               for i in range(len(prompts))}
        sched = ShardedScheduler(
            p, c, mesh=mesh,
            sched=SchedulerConfig(slots_per_rank=2, cache_len=64))
        done = sched.run(
            [Request(rid=i, prompt=prompts[i], max_new_tokens=budgets[i],
                     eos_id=eos_id if i == 1 else None)
             for i in range(len(prompts))])
        got = {r.rid: r.out_tokens for r in done}
        st = sched.stats()
        results[name] = dict(
            equal=int(got == ref),
            eos_early=int(len(ref[1]) == eos_at + 1),
            refills=sum(r["continuous_refills"] for r in st["per_rank"]),
            ranks=st["ranks"],
            ranks_served=len({r.rank for r in done}),
            streams_ref={str(k): v for k, v in ref.items()},
            streams_got={str(k): v for k, v in got.items()})
        stash[name] = (mesh, p, c, ref, eos_id)

    # streaming + prefill bucketing + EDF on the mesh path
    # (DESIGN.md §12): the per-token iterator over the 1×2 TP-sharded
    # deployment must yield every request's greedy stream bit-identical
    # to the solo mesh engine, with bucketed admission bounding the jit
    # cache (every admission shape (B, bucket))
    mesh, p, c, ref, eos_id = stash["1x2"]
    buckets = (16, 32, 64)
    sched = ShardedScheduler(
        p, c, mesh=mesh,
        sched=SchedulerConfig(slots_per_rank=2, cache_len=64,
                              policy="edf", buckets=buckets))
    shapes = set()
    eng = sched.shards[0]
    orig_prefill = eng._prefill

    def counting(params, toks, poss, caches, slots, valid):
        shapes.add(tuple(toks.shape))
        return orig_prefill(params, toks, poss, caches, slots, valid)

    eng._prefill = counting
    per = {}
    for rid, tok in sched.stream(
            [Request(rid=i, prompt=prompts[i],
                     max_new_tokens=budgets[i],
                     eos_id=eos_id if i == 1 else None,
                     slo="interactive" if i % 2 else "batch")
             for i in range(len(prompts))]):
        per.setdefault(rid, []).append(tok)
    out(stream_equal=int(per == ref),
        stream_events=sum(len(v) for v in per.values()),
        admit_shapes=sorted(shapes),
        admit_shapes_ok=int(len(shapes) <= len(buckets) and all(
            g == 2 and s in buckets for g, s in shapes)),
        **{f"{k}_{n}": v for n, res in results.items()
           for k, v in res.items()})


def mode_paged_mesh():
    """Paged KV on the 1×2-mesh packed path (DESIGN.md §13 acceptance):
    greedy streams with the page pool + block tables must equal the
    contiguous-cache mesh engine bit-for-bit, including across a forced
    preempt (page unmap) → spill (device→host) → fault → resume cycle
    driven by the QoS scheduler on the TP-sharded packed deployment."""
    from repro.configs import get_config, reduced
    from repro.launch.serve import build_serving_params
    from repro.models import lm
    from repro.serve.engine import Engine, Request
    from repro.serve.scheduler import SchedulerConfig, ShardedScheduler

    cfg0 = reduced(get_config("qwen3-32b"), layers=2, d_model=64,
                   vocab=128)
    params0 = lm.init_params(jax.random.PRNGKey(0), cfg0)
    params0 = jax.tree.map(lambda a: a * 3.0, params0)
    deploy = dict(path="packed", sparsity=0.25, block_k=8, block_n=8,
                  scope="all", verbose=False)
    mesh = auto_mesh((1, 2), ("data", "model"))
    p, c = build_serving_params(params0, cfg0, mesh=mesh, **deploy)

    def streams(**kv):
        eng = Engine(p, c, batch_slots=2, cache_len=64, mesh=mesh, **kv)
        rngs = np.random.default_rng(0)
        done = eng.run([Request(
            rid=i, prompt=rngs.integers(0, 128, size=(8 + 7 * i,))
            .astype(np.int32), max_new_tokens=6) for i in range(4)])
        return {r.rid: r.out_tokens for r in done}, eng

    s_ref, _ = streams()
    # tile-aligned pages (block 8): oversubscribed pool + host spill
    s_paged, eng = streams(kv_pages=12, kv_page_len=8, kv_host_pages=8)
    mem = eng.memory_stats()
    equal = int(s_paged == s_ref)
    drained = int(mem.device_used == 0 and mem.host_used == 0)

    # forced preempt → spill → fault → resume on the mesh deployment
    def solo(req):
        e = Engine(p, c, batch_slots=1, cache_len=64, mesh=mesh)
        return e.run([Request(rid=req.rid, prompt=req.prompt,
                              max_new_tokens=req.max_new_tokens)]
                     )[0].out_tokens

    rngq = np.random.default_rng(4)
    batch = Request(rid=0, prompt=rngq.integers(0, 128, size=(18,))
                    .astype(np.int32), max_new_tokens=12, slo="batch")
    inter = Request(rid=1, prompt=rngq.integers(0, 128, size=(40,))
                    .astype(np.int32), max_new_tokens=3,
                    slo="interactive", deadline=0.01)
    ref_q = {r.rid: solo(r) for r in (batch, inter)}
    sched = ShardedScheduler(
        p, c, mesh=mesh,
        sched=SchedulerConfig(slots_per_rank=1, cache_len=64,
                              policy="edf", preempt=True,
                              preempt_mode="kv", kv_pages=8,
                              kv_page_len=8, kv_host_pages=8))
    assert sched.submit(batch)
    for _ in range(4):
        sched.step()
    assert sched.submit(inter)
    done = []
    while sched.has_work():
        done.extend(sched.step())
    st = sched.stats()
    memq = st["per_rank"][0]["memory"]
    out(equal=equal, drained=drained, spills_run1=mem.spills,
        cycle_equal=int({r.rid: r.out_tokens for r in done} == ref_q),
        preemptions=st["preemptions"], spills=memq["spills"],
        faults=memq["faults"], device_used=memq["device_used"],
        streams_ref={str(k): v for k, v in s_ref.items()},
        streams_paged={str(k): v for k, v in s_paged.items()})


def mode_frontend_host():
    """Cluster-frontend subprocess host (DESIGN.md §14): one
    single-process ShardedScheduler driven over a newline-JSON protocol
    — commands on stdin (``ping``/``submit``/``step``/``cancel``/
    ``exit``), ``EV {json}`` events on stdout (``ready``/``pong``/
    ``submitted``/``tok``/``done``/``failed``/``stepped``/
    ``cancelled``). ``serve.frontend.SubprocessHost`` is the parent
    side; tests ``kill -9`` this process mid-load to prove the
    frontend's retry/resume guarantees against a real OS-level death.
    ``sys.argv[2]`` (optional) is a JSON dict of model/scheduler knobs.
    Token events carry the GLOBAL output index (resume prefixes
    included), so the parent can dedup replays exactly."""
    from repro.configs import get_config, reduced
    from repro.models import lm
    from repro.serve.engine import Request
    from repro.serve.scheduler import SchedulerConfig, ShardedScheduler

    spec = json.loads(sys.argv[2]) if len(sys.argv) > 2 else {}
    cfg = reduced(get_config("qwen3-32b"),
                  layers=spec.get("layers", 2),
                  d_model=spec.get("d_model", 64),
                  vocab=spec.get("vocab", 64))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    # same 3x amplification as the serving tests: unit-scale random
    # init greedy-decodes into a constant stream
    params = jax.tree.map(lambda a: a * 3.0, params)
    sched = ShardedScheduler(
        params, cfg, ranks=spec.get("ranks", 1),
        sched=SchedulerConfig(slots_per_rank=spec.get("slots", 2),
                              cache_len=spec.get("cache_len", 64),
                              rng_seed=spec.get("seed", 0)))

    def ev(**kw):
        print("EV " + json.dumps(kw), flush=True)

    sched.set_on_token(lambda req, tok: ev(
        ev="tok", rid=req.rid, i=len(req.out_tokens) - 1, tok=int(tok)))
    ev(ev="ready")
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        msg = json.loads(line)
        cmd = msg["cmd"]
        if cmd == "ping":
            ev(ev="pong")
        elif cmd == "submit":
            req = Request(
                rid=msg["rid"],
                prompt=np.asarray(msg["prompt"], np.int32),
                max_new_tokens=msg["max_new"],
                temperature=msg.get("temperature", 0.0),
                eos_id=msg.get("eos"), slo=msg.get("slo", "batch"),
                out_tokens=list(msg.get("resume") or []))
            if req.out_tokens:
                req.mark_resumable()   # exact re-prefill continuation
            ok = sched.submit(req)
            ev(ev="submitted", rid=req.rid, ok=bool(ok),
               status=req.status)
        elif cmd == "step":
            for r in sched.step():
                ev(ev="done", rid=r.rid)
            for r in sched.failed:
                ev(ev="failed", rid=r.rid,
                   error=r.error or "rank failure")
            sched.failed[:] = []
            ev(ev="stepped")
        elif cmd == "cancel":
            sched.cancel(msg["rid"])
            ev(ev="cancelled", rid=msg["rid"])
        elif cmd == "exit":
            break


if __name__ == "__main__":
    globals()[f"mode_{sys.argv[1]}"]()
