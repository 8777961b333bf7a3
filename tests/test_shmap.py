"""Sharded TCEC dispatch (kernels/shmap.py): plan construction, mesh-aware
routing + kernel-call counters, the shard_map knob, per-shard tuning keys,
and multi-device fused-vs-fallback parity (2-/4-/8-way CPU meshes in a
subprocess with a forced device count, like test_distribution.py)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import repro
from repro import numerics
from repro.kernels import dispatch, shmap, tuning
from repro.parallel import ctx


class FakeMesh:
    """Shape-only mesh stand-in for plan computation (no devices)."""
    def __init__(self, **shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def _one_device_mesh():
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


# ---------------------------------------------------------------- plans

def test_matmul_plan_prefers_n_then_k_then_m():
    mesh = FakeMesh(data=1, model=4)
    # all divisible -> N (column parallel)
    plan = shmap.matmul_plan((256, 256), (256, 256), mesh)
    assert plan.sharded_dim == "N" and not plan.psum_axes
    assert plan.b_spec == P(None, "model") and plan.out_spec == P(None, "model")
    assert plan.local == (1, 256, 64, 256)
    # N indivisible -> K (row parallel: local fold then f32 psum)
    plan = shmap.matmul_plan((256, 256), (256, 129), mesh)
    assert plan.sharded_dim == "K" and plan.psum_axes == ("model",)
    assert plan.a_spec == P(None, "model") and plan.b_spec == P("model", None)
    assert plan.out_spec == P(None, None)
    # N and K indivisible -> M
    plan = shmap.matmul_plan((256, 131), (131, 129), mesh)
    assert plan.sharded_dim == "M"
    assert plan.a_spec == P("model", None) and plan.out_spec == P("model", None)
    # nothing divisible -> unsupported
    assert shmap.matmul_plan((130, 131), (131, 129), mesh) is None


def test_matmul_plan_batch_and_dp_axes():
    mesh = FakeMesh(pod=2, data=2, model=2)
    plan = shmap.matmul_plan((8, 256, 256), (8, 256, 256), mesh)
    assert plan.a_spec == P(("pod", "data"), None, None)
    assert plan.b_spec == P(("pod", "data"), None, "model")
    assert plan.local == (2, 256, 128, 256)
    # 2-D under dp axes: M takes them
    plan = shmap.matmul_plan((256, 256), (256, 256), mesh)
    assert plan.a_spec == P(("pod", "data"), None)
    # indivisible batch AND M -> unsupported
    assert shmap.matmul_plan((3, 129, 256), (3, 256, 256), mesh) is None


def test_plans_reject_unknown_axis_names():
    mesh = FakeMesh(expert=2)
    assert shmap.matmul_plan((256, 256), (256, 256), mesh) is None
    assert shmap.attention_plan((1, 256, 4, 64), (1, 256, 2, 64),
                                mesh) is None
    assert shmap.paged_plan((2, 8, 64), (9, 8, 2, 64), mesh) is None
    # size-1 unknown axes never block
    assert shmap.matmul_plan((256, 256), (256, 256),
                             FakeMesh(expert=1, model=2)) is not None


def test_attention_plan_heads_then_qseq():
    mesh = FakeMesh(data=2, model=2)
    # Hkv divisible -> head sharding (whole GQA groups per device)
    plan = shmap.attention_plan((2, 256, 8, 64), (2, 256, 4, 64), mesh)
    assert plan.mode == "heads"
    assert plan.q_spec == P("data", None, "model", None)
    assert plan.k_spec == P("data", None, "model", None)
    assert plan.local == (1, 2, 256, 256)
    # Hkv indivisible, S divisible -> q-sequence sharding, K/V replicated
    plan = shmap.attention_plan((2, 256, 3, 64), (2, 256, 1, 64), mesh)
    assert plan.mode == "qseq"
    assert plan.q_spec == P("data", "model", None, None)
    assert plan.k_spec == P("data", None, None, None)
    assert plan.qp_spec == P("data", "model")    # global offsets ride along
    assert plan.local == (1, 1, 128, 256)
    # neither divisible -> unsupported
    assert shmap.attention_plan((2, 251, 3, 64), (2, 251, 1, 64),
                                mesh) is None
    # batch indivisible by the dp axes -> unsupported
    assert shmap.attention_plan((3, 256, 8, 64), (3, 256, 4, 64),
                                mesh) is None


def test_paged_plan_heads_on_model_tables_local():
    mesh = FakeMesh(data=2, model=2)
    plan = shmap.paged_plan((2, 8, 64), (9, 4, 8, 64), mesh)
    assert plan.pool_spec == P(None, "model", None, None)
    assert plan.bt_spec == P("data", None)       # device-local block tables
    assert plan.len_spec == P("data")
    assert plan.local == (1, 2)
    assert shmap.paged_plan((2, 8, 64), (9, 3, 8, 64), mesh) is None


# ----------------------------------------------- routing + counters (1 dev)

def test_matmul_routes_through_shard_map_under_mesh():
    a, b = _rand((128, 128), 0), _rand((128, 128), 1)
    with numerics.use(force=True, interpret=True, min_dim=0,
                      block=(128, 128, 128)):
        ref = repro.matmul(a, b, policy="tcec_bf16x6")
        n0 = shmap.counters()["matmul"]
        with ctx.use_mesh(_one_device_mesh()):
            out = repro.matmul(a, b, policy="tcec_bf16x6")
        assert shmap.counters()["matmul"] == n0 + 1
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_shard_map_knob_declines_to_xla_under_mesh():
    from repro.kernels import ops
    a, b = _rand((128, 128), 2), _rand((128, 128), 3)
    calls = []
    real = ops.tcec_matmul
    try:
        ops.tcec_matmul = lambda *x, **kw: (calls.append(1),
                                            real(*x, **kw))[1]
        with numerics.use(force=True, interpret=True, min_dim=0,
                          shard_map=False):
            with ctx.use_mesh(_one_device_mesh()):
                out = repro.matmul(a, b, policy="tcec_bf16x6")
        assert calls == []                       # kernel never ran
        with numerics.use(enabled=False):
            xla = repro.matmul(a, b, policy="tcec_bf16x6")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(xla))
    finally:
        ops.tcec_matmul = real


def test_unsupported_spec_declines_to_xla():
    """The decline path: a mesh whose model axis divides nothing must fall
    back to the XLA expansion (GSPMD shards that natively)."""
    a = _rand((2, 128, 128), 4)
    b = _rand((2, 128, 128), 5)
    dims = (((2,), (1,)), ((0,), (0,)))
    pol = repro.get_policy("tcec_bf16x6")
    with numerics.use(force=True, interpret=True, min_dim=0):
        assert dispatch.decide(a, b, pol, dims) is not None
        with ctx.use_mesh(FakeMesh(model=3)):
            assert dispatch.decide(a, b, pol, dims) is None
            assert dispatch.maybe_dispatch(a, b, pol, dims) is None


def test_dp_over_model_context_declines():
    """When the installed context declares "model" a *batch* axis
    (dp_over_model: pure DP, params replicated), the plan builders would
    misassign it to N/K/M and force an entry all-gather — dispatch must
    decline to the XLA fallback instead."""
    a, b = _rand((256, 256), 8), _rand((256, 256), 9)
    dims = (((1,), (0,)), ((), ()))
    pol = repro.get_policy("tcec_bf16x6")
    mesh = _one_device_mesh()
    with numerics.use(force=True, interpret=True, min_dim=0):
        with ctx.use_mesh(mesh):                      # default batch axes
            assert dispatch.decide(a, b, pol, dims) is not None
        with ctx.use_mesh(mesh, ("data", "model")):   # dp_over_model
            assert dispatch.decide(a, b, pol, dims) is None
            q = _rand((1, 128, 4, 64), 10)
            k = _rand((1, 128, 2, 64), 11)
            assert not dispatch.attention_eligible(q, k, k,
                                                   policy="tcec_bf16x6")


def test_pool_spec_head_dim_fallback():
    """Engine pool layout: KV heads on model when divisible, else
    head_dim (the parallel/sharding.py cache convention), else
    replicated — pool capacity scales with TP either way."""
    from repro.serving.engine import _pool_spec
    # K/V leaves are (nL, NP, Hkv, ps, hd)
    assert _pool_spec((2, 9, 4, 8, 64), FakeMesh(data=2, model=2)) \
        == P(None, None, "model", None, None)
    assert _pool_spec((2, 9, 2, 8, 64), FakeMesh(data=1, model=4)) \
        == P(None, None, None, None, "model")  # Hkv=2 < msize=4 -> head_dim
    assert _pool_spec((2, 9, 3, 8, 7), FakeMesh(data=1, model=4)) \
        == P(None, None, None, None, None)  # nothing divides -> replicated
    # latent leaves (nL, NP, ps, d) shard d
    assert _pool_spec((2, 9, 8, 64), FakeMesh(data=1, model=4)) \
        == P(None, None, None, "model")


def test_epilogue_fusion_declines_under_mesh():
    pol = repro.get_policy("tcec_bf16x6")
    with numerics.use(force=True, interpret=True, fuse_epilogue=True):
        assert dispatch.epilogue_eligible(pol)
        with ctx.use_mesh(_one_device_mesh()):
            assert not dispatch.epilogue_eligible(pol)


# ----------------------------------------------------- per-shard tuning keys

def test_shmap_tuning_namespace_keys():
    assert tuning.cache_key(1, 128, 128, 128, "tcec_bf16x6", "cpu",
                            namespace=shmap.NAMESPACE) \
        == "cpu/shmap/tcec_bf16x6/b1_m128_n128_k128"
    assert tuning.attn_cache_key(1, 2, 4, 128, 256, 64, 64, "tcec_bf16x6",
                                 "cpu", True, shmap.NAMESPACE) \
        .startswith("cpu/shmap/attn/")
    assert tuning.paged_cache_key(1, 2, 4, 4, 8, 64, 64, "tcec_bf16x6",
                                  "cpu", shmap.NAMESPACE) \
        .startswith("cpu/shmap/paged/")
    # shmap keys never collide with the global namespace for the same shape
    assert tuning.cache_key(1, 128, 128, 128, "tcec_bf16x6", "cpu") \
        != tuning.cache_key(1, 128, 128, 128, "tcec_bf16x6", "cpu",
                            namespace=shmap.NAMESPACE)


def test_mesh_dispatch_tunes_the_local_tile(tmp_path):
    """A mesh-routed matmul measures/records under backend/shmap/... keyed
    by the per-shard shape, not the global one."""
    cache = str(tmp_path / "tune.json")
    a, b = _rand((128, 128), 6), _rand((128, 128), 7)
    with numerics.use(force=True, interpret=True, min_dim=0, tune="force",
                      tune_cache=cache):
        with ctx.use_mesh(_one_device_mesh()):
            repro.matmul(a, b, policy="tcec_bf16x6")
    import json
    entries = json.load(open(cache))["entries"]
    assert any(k.startswith("cpu/shmap/tcec_bf16x6/") for k in entries), \
        sorted(entries)


# --------------------------------------------------------------- env knob

def test_repro_shard_map_registered_and_round_trips(monkeypatch):
    """Regrowth-guard extension: the knob is in the registry, feeds the
    NumericsConfig field, and round-trips through the env defaults."""
    var = numerics.ENV_VARS["REPRO_SHARD_MAP"]
    assert var.field == "shard_map" and var.kind == "bool"
    assert var.default is True
    assert numerics.NumericsConfig().shard_map is True
    monkeypatch.setenv("REPRO_SHARD_MAP", "0")
    assert not numerics.reload_env_defaults().shard_map
    monkeypatch.delenv("REPRO_SHARD_MAP")
    assert numerics.reload_env_defaults().shard_map


# -------------------------------------------------- sharded model entry

def test_sharded_train_step_runs_and_routes_fused_attention(tmp_path):
    """train(mesh=...) jits the sharded step and — with dispatch forced —
    exercises the fused attention route under the mesh (counter asserts
    it), the acceptance hook for the training wiring."""
    from repro.configs import get_smoke_config
    from repro.data.pipeline import DataConfig
    from repro.optim import adamw
    from repro.train.loop import TrainLoopConfig, train
    from repro.launch.mesh import make_host_mesh
    cfg = get_smoke_config("qwen3-0.6b")
    # all devices on the model axis: works at any forced device count
    # (Hkv=2 falls back to q-sequence sharding when model > 2)
    mesh = make_host_mesh(model=len(jax.devices()))
    n0 = shmap.counters()["attention"]
    with numerics.use(force=True, interpret=True):
        state, hist = train(cfg, adamw.OptConfig(lr=1e-3),
                            DataConfig(seed=0, global_batch=2, seq_len=128),
                            TrainLoopConfig(total_steps=1, ckpt_every=100),
                            str(tmp_path), mesh=mesh, log=lambda m: None)
    assert np.isfinite(hist[-1]["loss"])
    assert shmap.counters()["attention"] > n0     # fused route fired in the step


def test_engine_under_mesh_matches_unsharded_greedy():
    """Continuous-batching engine under a mesh (sharded pool layout, paged
    kernel via shard_map) stays token-identical to the unsharded engine."""
    from repro.configs import get_smoke_config
    from repro.models import get_model
    from repro.serving import Engine, SamplingParams
    cfg = get_smoke_config("qwen3-0.6b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, cfg.vocab_size, 5)),
               list(rng.integers(0, cfg.vocab_size, 9))]
    sp = SamplingParams(temperature=0.0, max_tokens=5)
    nc = numerics.active().replace(force=True, interpret=True)
    base = Engine(cfg, params, max_slots=2, numerics_config=nc).run(
        prompts, sp)
    n0 = shmap.counters()["paged"]
    with ctx.use_mesh(_one_device_mesh()):
        eng = Engine(cfg, params, max_slots=2, numerics_config=nc)
    out = eng.run(prompts, sp)     # mesh captured at construction
    assert eng.mesh is not None
    assert shmap.counters()["paged"] > n0
    assert list(base.values()) == list(out.values())


# ------------------------------------------- multi-device parity battery
#
# One subprocess with 8 forced CPU devices runs the whole battery: 2-, 4-,
# and 8-way meshes; matmul M/N/K-sharded; attention head- and
# q-sequence-sharded (incl. causal+window mask offsets); paged decode.

SUBPROC_BATTERY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    import repro
    from repro import numerics
    from repro.kernels import shmap
    from repro.launch.mesh import make_mesh
    from repro.parallel import ctx

    def rand(shape, seed):
        return jnp.asarray(
            np.random.default_rng(seed).standard_normal(shape), jnp.float32)

    mesh2 = make_mesh((1, 2), ("data", "model"))
    mesh4 = make_mesh((2, 2), ("data", "model"))
    mesh8 = make_mesh((2, 4), ("data", "model"))

    with numerics.use(force=True, interpret=True, min_dim=0,
                      block=(128, 128, 128), attn_block=(128, 128),
                      paged_block=2):
        cfg = numerics.active()

        # ---- matmul: N-, K-, M-, and batch-sharded --------------------
        a, b = rand((256, 256), 0), rand((256, 256), 1)
        ref = repro.matmul(a, b, policy="tcec_bf16x6")
        for mesh, tag in ((mesh2, "2way"), (mesh8, "8way")):
            plan = shmap.matmul_plan(a.shape, b.shape, mesh)
            assert plan.sharded_dim in ("N",), (tag, plan)
            with ctx.use_mesh(mesh):
                out = repro.matmul(a, b, policy="tcec_bf16x6")
            assert np.array_equal(np.asarray(out), np.asarray(ref)), tag

        ak, bk = rand((4, 131, 256), 2), rand((4, 256, 129), 3)
        plan = shmap.matmul_plan(ak.shape, bk.shape, mesh4)
        assert plan.sharded_dim == "K" and plan.psum_axes == ("model",)
        refk = repro.matmul(ak, bk, policy="tcec_bf16x6")
        with ctx.use_mesh(mesh4):
            outk = repro.matmul(ak, bk, policy="tcec_bf16x6")
        # K sharding: f32 psum AFTER the local fold — f32-level agreement,
        # not bit equality (documented reduction-order change)
        err = float(jnp.max(jnp.abs(outk - refk)))
        scale = float(jnp.max(jnp.abs(refk)))
        assert err <= 1e-5 * max(scale, 1.0), err
        with numerics.use(enabled=False):
            xlak = repro.matmul(ak, bk, policy="tcec_bf16x6")
        assert float(jnp.max(jnp.abs(outk - xlak))) <= 1e-5 * max(scale, 1.0)

        am, bm = rand((256, 131), 4), rand((131, 129), 5)
        plan = shmap.matmul_plan(am.shape, bm.shape, mesh2)
        assert plan.sharded_dim == "M"
        refm = repro.matmul(am, bm, policy="tcec_bf16x6")
        with ctx.use_mesh(mesh2):
            outm = repro.matmul(am, bm, policy="tcec_bf16x6")
        assert np.array_equal(np.asarray(outm), np.asarray(refm))

        # ---- attention: head- and q-sequence-sharded ------------------
        q = rand((2, 256, 8, 64), 6)
        k = rand((2, 256, 4, 64), 7)
        v = rand((2, 256, 4, 64), 8)
        refa = repro.attention(q, k, v, policy="tcec_bf16x6", window=37,
                               softcap=20.0)
        plan = shmap.attention_plan(q.shape, k.shape, mesh8)
        assert plan.mode == "heads", plan
        n0 = shmap.counters()["attention"]
        with ctx.use_mesh(mesh8):
            outa = repro.attention(q, k, v, policy="tcec_bf16x6", window=37,
                                   softcap=20.0)
        assert shmap.counters()["attention"] == n0 + 1
        assert np.array_equal(np.asarray(outa), np.asarray(refa))

        q1 = rand((2, 256, 2, 64), 9)          # Hkv=1: forces qseq on 4-way
        k1 = rand((2, 256, 1, 64), 10)
        v1 = rand((2, 256, 1, 64), 11)
        mesh_q = make_mesh((2, 4), ("data", "model"))
        plan = shmap.attention_plan(q1.shape, k1.shape, mesh_q)
        assert plan.mode == "qseq", plan
        refq = repro.attention(q1, k1, v1, policy="tcec_bf16x6", window=37)
        with ctx.use_mesh(mesh_q):
            outq = repro.attention(q1, k1, v1, policy="tcec_bf16x6",
                                   window=37)
        # causal + window masks offset by the shard's global position:
        # bit-identical per shard to the unsharded kernel
        assert np.array_equal(np.asarray(outq), np.asarray(refq))
        with numerics.use(enabled=False):
            xlaq = repro.attention(q1, k1, v1, policy="tcec_bf16x6",
                                   window=37)
        assert float(jnp.max(jnp.abs(outq - xlaq))) < 2e-6

        # ---- paged decode ---------------------------------------------
        from repro import tcec_paged_attention
        from repro.kernels import dispatch as kd
        rng = np.random.default_rng(12)
        B, Hkv, rep, hd, ps, maxp, NP = 2, 4, 2, 64, 8, 4, 9
        qd = rand((B, Hkv * rep, hd), 13)
        kp = jnp.asarray(rng.standard_normal((NP, Hkv, ps, hd)), jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal((NP, Hkv, ps, hd)), jnp.bfloat16)
        bt = jnp.asarray(rng.permutation(8).reshape(B, maxp) + 1, jnp.int32)
        lens = jnp.asarray([25, 30], jnp.int32)
        refp = kd.attention_decode(qd, kp, vp, bt, lens,
                                   policy="tcec_bf16x6", window=17)
        assert refp is not None
        n0 = shmap.counters()["paged"]
        with ctx.use_mesh(mesh8):
            outp = kd.attention_decode(qd, kp, vp, bt, lens,
                                       policy="tcec_bf16x6", window=17)
        assert outp is not None and shmap.counters()["paged"] == n0 + 1
        assert np.array_equal(np.asarray(outp), np.asarray(refp))

        # ---- 4-way sharded train step exercises the fused route -------
        from repro.configs import get_smoke_config
        from repro.data.pipeline import DataConfig
        from repro.optim import adamw
        from repro.train.loop import TrainLoopConfig, train
        import tempfile
        cfg_m = get_smoke_config("qwen3-0.6b")
        n0 = shmap.counters()["attention"]
        with numerics.use(min_dim=128, block=None, attn_block=(128, 128)):
            with tempfile.TemporaryDirectory() as d:
                state, hist = train(
                    cfg_m, adamw.OptConfig(lr=1e-3),
                    DataConfig(seed=0, global_batch=4, seq_len=128),
                    TrainLoopConfig(total_steps=1, ckpt_every=100),
                    d, mesh=mesh4, log=lambda m: None)
        assert np.isfinite(hist[-1]["loss"])
        assert shmap.counters()["attention"] > n0
        # params really sharded on the mesh
        shardings = {s for leaf in jax.tree.leaves(state["params"])
                     for s in [leaf.sharding]}
        assert any(not s.is_fully_replicated for s in shardings)

    print("OK")
""")


def test_sharded_parity_battery_subprocess():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", SUBPROC_BATTERY],
                       capture_output=True, text=True, cwd=root,
                       timeout=900)
    assert "OK" in r.stdout, (r.stdout[-2000:], r.stderr[-4000:])


ENGINE_MESH_SUBPROC = textwrap.dedent("""
    import contextlib, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, "src")
    import jax, numpy as np
    from repro import numerics
    from repro.configs import get_smoke_config
    from repro.kernels import shmap
    from repro.launch.mesh import make_mesh
    from repro.models import get_model
    from repro.obs.explain import decisions, reset
    from repro.parallel import ctx
    from repro.serving import Engine, SamplingParams

    cfg = get_smoke_config("qwen3-0.6b")            # 2 KV heads
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (5, 9)]
    sp = SamplingParams(temperature=0.0, max_tokens=5)
    forced = numerics.active().replace(force=True, interpret=True)

    def serve(nc, mesh=None):
        with (ctx.use_mesh(mesh) if mesh is not None
              else contextlib.nullcontext()):
            eng = Engine(cfg, params, max_slots=2, numerics_config=nc)
        out = eng.run(prompts, sp)
        return [list(v) for v in out.values()], eng.stats()

    def kv_write_rules():
        return {r for k, v in decisions().items() if "/kv_write/" in k
                for r in v}

    one, st = serve(forced)
    assert st["decode_steps_in_place"] == st["decode_steps"] > 0
    # KV heads on "model", alone and beside a data axis: the stacked-pool
    # write and the paged kernel run per shard, token-identical
    for shape in ((1, 2), (2, 2)):
        reset()
        n0 = shmap.counters()["kv_write"]
        got, st = serve(forced, make_mesh(shape, ("data", "model")))
        assert got == one, (shape, got, one)
        assert st["decode_steps_in_place"] == st["decode_steps"] > 0
        assert shmap.counters()["kv_write"] > n0, shape
        assert kv_write_rules() == {"fused"}, kv_write_rules()
    # a declined plan (the shard_map knob off) keeps the per-layer scan:
    # token-identical to one device on the same XLA attention path
    plain, _ = serve(numerics.active())
    reset()
    got, st = serve(forced.replace(shard_map=False),
                    make_mesh((1, 2), ("data", "model")))
    assert got == plain, (got, plain)
    assert st["decode_steps_in_place"] == 0 and st["decode_steps"] > 0
    assert kv_write_rules() == {"mesh-declined"}, kv_write_rules()
    print("OK")
""")


def test_engine_in_place_decode_under_mesh_subprocess():
    """The decode step's in-place path under ``shard_map`` on 4 virtual
    CPU devices matches one device token for token; a declined plan
    falls back to the per-layer scan."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", ENGINE_MESH_SUBPROC],
                       capture_output=True, text=True, cwd=root,
                       timeout=600)
    assert "OK" in r.stdout, (r.stdout[-2000:], r.stderr[-4000:])
