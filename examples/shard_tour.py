"""Tour of sharded TCEC dispatch on a fake multi-device CPU mesh.

Forces 4 CPU devices (before jax import), then walks the sharded stack:

  1. mesh setup + plan inspection — which dims each mesh axis shards;
  2. sharded matmul parity: N-sharded (bit-exact) and K-sharded (local
     fold first, one f32 psum after — f32-level agreement, the documented
     reduction-order guarantee of docs/parallel.md);
  3. sharded attention parity: head-sharded, bit-exact vs the unsharded
     fused kernel, with the kernel-call counter proving the route;
  4. a sharded train step on the same mesh (params land sharded).

Run:  PYTHONPATH=src python examples/shard_tour.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax                                                       # noqa: E402
import jax.numpy as jnp                                          # noqa: E402
import numpy as np                                               # noqa: E402

import repro                                                     # noqa: E402
from repro import numerics                                       # noqa: E402
from repro.launch.mesh import make_mesh                          # noqa: E402
from repro.parallel import ctx                                   # noqa: E402

# ----------------------------------------------------- 1. mesh + plans
mesh = make_mesh((2, 2), ("data", "model"))
print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")

plan = repro.shmap.matmul_plan((256, 256), (256, 256), mesh)
print(f"square GEMM plan: shard {plan.sharded_dim}, local (B,M,N,K) = "
      f"{plan.local}")
plan_k = repro.shmap.matmul_plan((4, 131, 256), (4, 256, 129), mesh)
print(f"odd-N GEMM plan:  shard {plan_k.sharded_dim}, "
      f"psum over {plan_k.psum_axes}")

# ------------------------------------------------- 2. matmul parity
rng = np.random.default_rng(0)
with numerics.use(force=True, interpret=True, min_dim=0,
                  block=(128, 128, 128)):
    a = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    ref = repro.matmul(a, b, policy="tcec_bf16x6")
    with ctx.use_mesh(mesh):
        out = repro.matmul(a, b, policy="tcec_bf16x6")
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    print("N-sharded matmul: bit-identical to the unsharded kernel")

    ak = jnp.asarray(rng.standard_normal((4, 131, 256)), jnp.float32)
    bk = jnp.asarray(rng.standard_normal((4, 256, 129)), jnp.float32)
    refk = repro.matmul(ak, bk, policy="tcec_bf16x6")
    with ctx.use_mesh(mesh):
        outk = repro.matmul(ak, bk, policy="tcec_bf16x6")
    err = float(jnp.max(jnp.abs(outk - refk)))
    assert err < 1e-4, err
    print(f"K-sharded matmul: f32 psum after the local fold, "
          f"max |diff| = {err:.2e}")

    # ------------------------------------------- 3. attention parity
    q = jnp.asarray(rng.standard_normal((2, 256, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 256, 4, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 256, 4, 64)), jnp.float32)
    with numerics.use(attn_block=(128, 128)):
        refa = repro.attention(q, k, v, policy="tcec_bf16x6", window=37)
        n0 = repro.shmap.counters()["attention"]
        with ctx.use_mesh(mesh):
            outa = repro.attention(q, k, v, policy="tcec_bf16x6", window=37)
    assert repro.shmap.counters()["attention"] == n0 + 1
    assert np.array_equal(np.asarray(outa), np.asarray(refa))
    aplan = repro.shmap.attention_plan(q.shape, k.shape, mesh)
    print(f"{aplan.mode}-sharded attention: routed via shard_map "
          f"(counter {n0} -> {n0 + 1}), bit-identical")

# --------------------------------------------- 4. sharded train step
import tempfile                                                  # noqa: E402

from repro.configs import get_smoke_config                       # noqa: E402
from repro.data.pipeline import DataConfig                       # noqa: E402
from repro.optim import adamw                                    # noqa: E402
from repro.train.loop import TrainLoopConfig, train              # noqa: E402

cfg = get_smoke_config("qwen3-0.6b")
with tempfile.TemporaryDirectory() as d:
    state, hist = train(cfg, adamw.OptConfig(lr=1e-3),
                        DataConfig(seed=0, global_batch=4, seq_len=32),
                        TrainLoopConfig(total_steps=2, ckpt_every=100),
                        d, mesh=mesh, log=lambda m: None)
shardings = {str(leaf.sharding.spec) for leaf in
             jax.tree.leaves(state["params"])
             if not leaf.sharding.is_fully_replicated}
print(f"sharded train step: loss {hist[-1]['loss']:.4f}, "
      f"{len(shardings)} distinct param specs on the mesh")
print("shard tour complete")
