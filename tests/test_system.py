"""End-to-end behaviour tests for the paper's system: the accuracy claim
measured through a full train step, and the serving path."""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.pipeline import DataConfig, device_batch
from repro.launch.step import make_train_step
from repro.models import get_model
from repro.optim import adamw


def _losses(policy, steps=6):
    cfg = get_smoke_config("qwen3-0.6b").replace(policy=policy)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw.OptConfig(lr=1e-3, warmup_steps=2, total_steps=steps)
    state = {"params": params, "opt": adamw.init_state(params, opt)}
    step = jax.jit(make_train_step(cfg, opt))
    data = DataConfig(seed=0, global_batch=4, seq_len=32)
    out = []
    for i in range(steps):
        state, m = step(state, device_batch(cfg, data, i))
        out.append(float(m["loss"]))
    return np.asarray(out)


def test_tcec_training_matches_fp32_end_to_end():
    """The paper's headline claim through optimizer dynamics: the corrected
    6-pass policy tracks fp32 loss far closer than uncorrected bf16."""
    ref = _losses("fp32")
    l6 = _losses("tcec_bf16x6")
    lb = _losses("bf16")
    d6 = float(np.max(np.abs(l6 - ref)))
    db = float(np.max(np.abs(lb - ref)))
    assert np.all(np.isfinite(ref)) and ref[-1] < ref[0]
    assert d6 < 1e-3, d6
    assert d6 < db + 1e-9, (d6, db)


def test_serving_generates_deterministically():
    from repro.launch.serve import generate
    cfg = get_smoke_config("mamba2-130m")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 4)),
        jnp.int32)
    a = generate(cfg, params, prompts, gen_len=6)
    b = generate(cfg, params, prompts, gen_len=6)
    assert a.shape == (2, 6)
    assert jnp.array_equal(a, b)


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_dir(tmp_path, env_dir):
    """The launch binaries keep JAX's compile cache where
    ``JAX_COMPILATION_CACHE_DIR`` says and set nothing else; without it, in
    a fixed, git-ignored directory of the checkout."""
    root = pathlib.Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(root / "src")
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = ("import jax; from repro.launch import compile_cache; "
            "d = compile_cache.enable(); "
            "print(d, jax.config.jax_compilation_cache_dir)")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    want = str(tmp_path) if env_dir else str(root / ".jax_cache")
    assert r.stdout.split() == [want, want]
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
