"""Dispatch + autotuning subsystem tests.

Covers: dispatch-path selection (Pallas vs XLA fallback, escape hatch),
bit-equivalence of the two paths in interpret mode (forward AND the
policy-preserving backward), the batched grid and fused epilogue vs the
ref.py oracle, the autotuner cache round-trip (in-memory LRU, on-disk JSON,
cross-process reuse), and the models.layers epilogue-fusion hook.
"""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import numerics
from repro.core.policy import POLICIES, get_policy, pdot, policy_bmm, policy_mm
from repro.kernels import (dispatch, tcec_bmm_ref, tcec_matmul,
                           tcec_matmul_ref, tuning)
from repro.numerics import NumericsConfig


def _rand(shape, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


def _bits(x):
    return np.asarray(x, dtype=np.float32).view(np.uint32)


# ----------------------------------------------------------- eligibility

def test_policy_eligibility_rules():
    assert dispatch.eligible_policy(get_policy("tcec_bf16x6"))
    assert dispatch.eligible_policy(get_policy("tcec_bf16x3"))
    assert not dispatch.eligible_policy(get_policy("fp32"))      # plain
    assert not dispatch.eligible_policy(get_policy("bf16"))      # plain
    assert not dispatch.eligible_policy(get_policy("fp16_halfhalf"))  # fp16
    assert not dispatch.eligible_policy(get_policy("fp16_markidis"))


def test_dispatch_off_by_default_on_cpu():
    """Without force, a CPU backend must keep the XLA term-expansion path."""
    a, b = _rand((128, 128), 0), _rand((128, 128), 1)
    pol = get_policy("tcec_bf16x6")
    dims = (((1,), (0,)), ((), ()))
    assert jax.default_backend() != "tpu"
    assert dispatch.maybe_dispatch(a, b, pol, dims) is None


def test_env_flags_treat_zero_as_off():
    for off in ("0", "false", "no", "off", ""):
        env = {"REPRO_FORCE_PALLAS": off, "REPRO_DISABLE_PALLAS": off}
        cfg = NumericsConfig.from_env(env)
        assert not cfg.force and cfg.enabled, off
    assert NumericsConfig.from_env({"REPRO_TUNE": "0"}).tune == "auto"
    with numerics.use(tune="auto"):
        assert tuning._should_measure() == (jax.default_backend() == "tpu")


def test_escape_hatch_env_var():
    cfg = NumericsConfig.from_env({"REPRO_DISABLE_PALLAS": "1"})
    assert not cfg.enabled
    # even under force, the hatch wins
    with numerics.use(enabled=False, force=True, min_dim=0,
                      interpret=True):
        a, b = _rand((128, 128), 0), _rand((128, 128), 1)
        out = dispatch.maybe_dispatch(a, b, get_policy("tcec_bf16x6"),
                                      (((1,), (0,)), ((), ())))
        assert out is None


def test_min_dim_gate_and_shape_rules():
    pol = get_policy("tcec_bf16x6")
    with numerics.use(force=True, interpret=True, min_dim=128):
        small = dispatch.maybe_dispatch(
            _rand((8, 32), 0), _rand((32, 16), 1), pol,
            (((1,), (0,)), ((), ())))
        assert small is None          # below min_dim -> XLA
    x, w = _rand((4, 8, 128), 0), _rand((128, 128), 1)
    h, wo = _rand((2, 8, 4, 32), 2), _rand((4, 32, 128), 3)
    with numerics.use(force=True, interpret=True, min_dim=0):
        # free and contracted dims collapse onto one (M, K) @ (K, N) GEMM
        multi_m = dispatch.maybe_dispatch(x, w, pol, (((2,), (0,)),
                                                      ((), ())))
        multi_k = dispatch.maybe_dispatch(h, wo, pol, (((2, 3), (0, 1)),
                                                       ((), ())))
        two_batch = dispatch.maybe_dispatch(
            _rand((2, 2, 8, 128), 4), _rand((2, 2, 128, 128), 5), pol,
            (((3,), (2,)), ((0, 1), (0, 1))))
        assert two_batch is None      # more than one batch dim -> XLA
    ref_m = _xla(lambda: pdot("bsd,df->bsf", x, w, pol.name))
    ref_k = _xla(lambda: pdot("bshk,hkd->bsd", h, wo, pol.name))
    for got, ref in ((multi_m, ref_m), (multi_k, ref_k)):
        assert got is not None and got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-5)


def test_explicit_cfg_argument_wins_over_ambient():
    """decide()/maybe_dispatch() take the config as an explicit static
    argument — the ambient context only supplies the default."""
    pol = get_policy("tcec_bf16x6")
    a, b = _rand((128, 128), 0), _rand((128, 128), 1)
    dims = (((1,), (0,)), ((), ()))
    on = numerics.active().replace(force=True, interpret=True, min_dim=0)
    off = on.replace(enabled=False)
    with numerics.use(enabled=False):
        assert dispatch.decide(a, b, pol, dims, cfg=on) is not None
    with numerics.use(force=True, interpret=True, min_dim=0):
        assert dispatch.decide(a, b, pol, dims, cfg=off) is None


# ------------------------------------------------------ bit-equivalence

def _xla(fn, *args):
    with numerics.use(enabled=False):
        return fn(*args)


def test_policy_mm_bit_identical_to_xla_path():
    """Acceptance: fused kernel == term expansion, bit for bit, when the
    K block covers the contraction (same RN-f32 operation sequence)."""
    a, b = _rand((256, 256), 2), _rand((256, 256), 3)
    for pol in ("tcec_bf16x3", "tcec_bf16x6"):
        with numerics.use(force=True, interpret=True, min_dim=0,
                               block=(256, 256, 256)):
            y_pal = policy_mm(a, b, pol)
        y_xla = _xla(policy_mm, a, b, pol)
        assert np.array_equal(_bits(y_pal), _bits(y_xla)), pol


def test_policy_bmm_bit_identical_to_xla_path():
    a, b = _rand((2, 128, 128), 4), _rand((2, 128, 128), 5)
    with numerics.use(force=True, interpret=True, min_dim=0,
                           block=(128, 128, 128)):
        y_pal = policy_bmm(a, b, "tcec_bf16x6")
    y_xla = _xla(policy_bmm, a, b, "tcec_bf16x6")
    assert np.array_equal(_bits(y_pal), _bits(y_xla))


def test_pdot_routes_through_kernel_and_matches():
    """pdot's canonical transpose makes attention/MLP-shaped einsums
    eligible; K-blocked dispatch stays allclose to the XLA path."""
    a, b = _rand((256, 384), 6), _rand((384, 128), 7)
    with numerics.use(force=True, interpret=True, min_dim=0):
        y_pal = pdot("mk,kn->mn", a, b, "tcec_bf16x6")
    y_xla = _xla(pdot, "mk,kn->mn", a, b, "tcec_bf16x6")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_xla),
                               rtol=1e-6, atol=1e-5)


def test_backward_is_policy_preserving_and_bit_identical():
    """The custom_vjp backward GEMMs (dA = g B^T, dB = A^T g) must also
    route through the kernel — and stay bit-identical with full-K blocks."""
    a = _rand((256, 256), 8)
    w = _rand((256, 256), 9)

    def loss(w):
        return jnp.sum(policy_mm(a, w, "tcec_bf16x6") ** 2)

    with numerics.use(force=True, interpret=True, min_dim=0,
                           block=(256, 256, 256)):
        g_pal = jax.grad(loss)(w)
    with numerics.use(enabled=False):
        g_xla = jax.grad(loss)(w)
    assert np.array_equal(_bits(g_pal), _bits(g_xla))


# ------------------------------------------- batched / epilogue kernels

def test_batched_kernel_vs_ref_oracle():
    a, b = _rand((3, 128, 256), 10), _rand((3, 256, 128), 11)
    out = tcec_matmul(a, b, policy="tcec_bf16x6", block=(128, 128, 128),
                      interpret=True)
    ref = tcec_bmm_ref(a, b, "tcec_bf16x6")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-5)


def test_batched_kernel_nonaligned_pads():
    a, b = _rand((2, 100, 200), 12), _rand((2, 200, 60), 13)
    out = tcec_matmul(a, b, policy="tcec_bf16x3", block=(128, 128, 128),
                      interpret=True)
    assert out.shape == (2, 100, 60)
    ref = tcec_bmm_ref(a, b, "tcec_bf16x3")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("activation", [None, "relu", "gelu", "silu"])
def test_fused_epilogue_bitwise_vs_unfused(activation):
    from repro.kernels.ref import epilogue_ref
    a, b = _rand((128, 256), 14), _rand((256, 128), 15)
    bias = _rand((128,), 16)
    plain = tcec_matmul(a, b, policy="tcec_bf16x6", block=(128, 128, 128),
                        interpret=True)
    fused = tcec_matmul(a, b, policy="tcec_bf16x6", block=(128, 128, 128),
                        interpret=True, bias=bias, activation=activation,
                        out_scale=0.5)
    ref = epilogue_ref(plain, bias, activation, 0.5)
    if activation == "gelu":
        # gelu's tanh polynomial picks up different FMA contraction inside
        # vs outside the kernel graph — ULP-level, not algorithmic
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=2e-6, atol=2e-6)
    else:
        assert np.array_equal(_bits(fused), _bits(ref))


@pytest.mark.parametrize("activation", ["silu", "relu", None])
def test_fused_linear_layer_hook(activation):
    """models.layers.fused_linear: fused forward matches the unfused path,
    and its recompute-backward stays close to the unfused gradients — for
    every supported epilogue activation, not just the silu default."""
    from repro.models.layers import fused_linear
    x = _rand((2, 64, 128), 17)
    w = _rand((128, 256), 18)

    def run(fuse):
        kw = dict(fuse_epilogue=fuse, force=True, interpret=True, min_dim=0)
        with numerics.use(**kw):
            y, vjp = jax.vjp(
                lambda x, w: fused_linear(x, w, None, activation,
                                          "tcec_bf16x6"),
                x, w)
            dx, dw = vjp(jnp.ones_like(y))
        return y, dx, dw

    y_f, dx_f, dw_f = run(True)
    y_u, dx_u, dw_u = run(False)
    # regression (review finding): the custom_vjp must differentiate THIS
    # activation, not a silu default — oracle is plain autodiff through the
    # same policy forward (identical z bits, so identical relu mask)
    from repro.kernels.tcec_matmul import EPILOGUE_ACTIVATIONS

    def ref_loss(x, w):
        z = pdot("bsd,df->bsf", x, w, "tcec_bf16x6")
        return jnp.sum(EPILOGUE_ACTIVATIONS[activation](z))

    with numerics.use(fuse_epilogue=False, force=True, interpret=True,
                           min_dim=0):
        dx_ref, dw_ref = jax.grad(ref_loss, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dx_f), np.asarray(dx_ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_u),
                               rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dx_f), np.asarray(dx_u),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_u),
                               rtol=1e-5, atol=1e-4)


# ------------------------------------------------------------ autotuner

def test_autotune_cache_roundtrip(tmp_path):
    calls = []

    def fake_measure(block):
        calls.append(block)
        return 1.0 + abs(block[0] - 256) / 1e3   # prefers bm=256

    path = str(tmp_path / "tune.json")
    cache = tuning.BlockCache(path=path)
    blk1, meta1 = tuning.autotune(1, 512, 512, 512, "tcec_bf16x6",
                                  measure=fake_measure, cache=cache)
    assert meta1["source"] == "measured"
    assert blk1[0] == 256
    n_measured = len(calls)
    assert n_measured > 1

    # in-memory LRU hit: no re-measurement
    blk2, meta2 = tuning.autotune(1, 512, 512, 512, "tcec_bf16x6",
                                  measure=fake_measure, cache=cache)
    assert blk2 == blk1 and meta2["source"] == "cache"
    assert len(calls) == n_measured

    # shape bucketing: 500^3 pads to the same 512^3 bucket -> same entry
    blk3, meta3 = tuning.autotune(1, 500, 500, 500, "tcec_bf16x6",
                                  measure=fake_measure, cache=cache)
    assert blk3 == blk1 and meta3["source"] == "cache"

    # fresh cache object (new process) reads the persisted JSON: still no
    # re-measurement
    cache2 = tuning.BlockCache(path=path)
    blk4, meta4 = tuning.autotune(1, 512, 512, 512, "tcec_bf16x6",
                                  measure=fake_measure, cache=cache2)
    assert blk4 == blk1 and meta4["source"] == "cache"
    assert len(calls) == n_measured

    with open(path) as f:
        data = json.load(f)
    assert data["version"] == tuning.CACHE_VERSION
    [entry] = data["entries"].values()
    assert tuple(entry["block"]) == blk1 and entry["source"] == "measured"


def test_autotune_reuse_across_processes(tmp_path):
    """Acceptance: a *different process* reuses the persisted winner."""
    path = str(tmp_path / "tune.json")
    cache = tuning.BlockCache(path=path)
    blk, _ = tuning.autotune(1, 256, 256, 256, "tcec_bf16x3",
                             measure=lambda b: float(sum(b)), cache=cache)
    code = (
        "import json, sys\n"
        "sys.path.insert(0, 'src')\n"
        "from repro.kernels import tuning\n"
        f"cache = tuning.BlockCache(path={path!r})\n"
        "blk, meta = tuning.autotune(1, 256, 256, 256, 'tcec_bf16x3',\n"
        "    measure=lambda b: (_ for _ in ()).throw(AssertionError('remeasured')),\n"
        "    cache=cache)\n"
        "print('SOURCE', meta['source'], tuple(blk))\n"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd="/root/repo", timeout=300)
    assert f"SOURCE cache {blk}" in r.stdout, (r.stdout, r.stderr)


def test_autotune_never_measures_inside_a_trace(tmp_path, monkeypatch):
    """Inside ``jit`` a timed kernel call returns tracers, so the tuner
    would time tracing: it must give the heuristic block, unpersisted,
    even under ``tune="force"``."""
    calls = []
    monkeypatch.setattr(tuning, "_measure_block",
                        lambda *a, **kw: calls.append(a) or 1.0)
    path = tmp_path / "tune.json"
    a = jnp.asarray(np.random.default_rng(0).standard_normal((256, 256)),
                    jnp.float32)
    seen = {}

    def f(x):
        seen["direct"] = tuning.autotune(
            1, 512, 512, 512, "tcec_bf16x6", cache=tuning.BlockCache(
                path=str(path)), measure=lambda blk: calls.append(blk) or 1.0)
        return numerics.matmul(x, x, policy="tcec_bf16x6")

    with numerics.use(force=True, interpret=True, min_dim=0, tune="force",
                      tune_cache=str(path)):
        out = jax.jit(f)(a)
    assert calls == []
    blk, meta = seen["direct"]
    assert meta["source"] == "heuristic"
    assert blk == tuning.heuristic_block(512, 512, 512, "tcec_bf16x6")
    entries = (json.load(open(path))["entries"] if path.exists() else {})
    assert not any(e.get("source") == "measured" for e in entries.values())
    with numerics.use(enabled=False):
        ref = numerics.matmul(a, a, policy="tcec_bf16x6")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_heuristic_fallback_not_persisted(tmp_path):
    path = str(tmp_path / "tune.json")
    cache = tuning.BlockCache(path=path)
    with numerics.use(tune="off"):
        blk, meta = tuning.autotune(1, 1024, 1024, 1024, "tcec_bf16x6",
                                    cache=cache)
    assert meta["source"] == "heuristic"
    assert blk == tuning.heuristic_block(1024, 1024, 1024, "tcec_bf16x6")
    assert not (tmp_path / "tune.json").exists()   # heuristics never persist


def test_candidate_blocks_respect_vmem_and_alignment():
    for pol in POLICIES:
        if get_policy(pol).is_plain():
            continue
        for blk in tuning.candidate_blocks(4096, 4096, 4096, pol):
            assert all(s % 128 == 0 for s in blk)
        # no candidate overshoots a small padded problem
        for blk in tuning.candidate_blocks(128, 128, 128, pol):
            assert blk == (128, 128, 128)
