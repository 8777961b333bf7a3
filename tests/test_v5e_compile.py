"""The three TCEC kernels compile for a TPU v5e, at qwen3-0.6b widths.

Interpret mode cannot see what Mosaic refuses (block tiling, in-kernel
reshapes, scoped VMEM), so these tests compile each kernel for a
described ``v5e:2x2`` topology — no chip attached — and check that the
compiled program contains the Pallas custom call.  qwen3-0.6b: d_model
1024, d_ff 3072, 16 query heads over 8 KV heads, head_dim 128; the
serving engine's page size is 16.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and the test workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.policy import POLICIES, get_policy
from repro.kernels import dispatch, tuning
from repro.kernels.tcec_attention import tcec_attention
from repro.kernels.tcec_matmul import tcec_matmul_pallas
from repro.kernels.tcec_paged_attention import tcec_paged_attention

POLICY = "tcec_bf16x6"
FUSED_POLICIES = [p for p in POLICIES if dispatch.eligible_policy(get_policy(p))]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding):
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def test_gemm_compiles_at_heuristic_block(one_chip):
    """The MLP up-projection, ``(1024, 1024) @ (1024, 3072)``, at the block
    dispatch picks when nothing is tuned."""
    s = _spec(one_chip)
    block = tuning.heuristic_block(1024, 3072, 1024, POLICY)
    _compile(lambda a, b: tcec_matmul_pallas(a, b, policy_name=POLICY,
                                             block=block),
             s((1024, 1024)), s((1024, 3072)))


@pytest.mark.parametrize("policy", FUSED_POLICIES)
def test_gemm_compiles_at_largest_candidate_block(one_chip, policy):
    """The largest block ``candidate_blocks`` can return, with the fused
    bias + activation epilogue: the working set that needs the raised
    scoped-VMEM limit."""
    s = _spec(one_chip)
    block = tuning.candidate_blocks(4096, 4096, 4096, policy)[0]
    _compile(lambda a, b, c: tcec_matmul_pallas(
                 a, b, c, policy_name=policy, block=block,
                 activation="silu"),
             s((1024, 1024)), s((1024, 1024)), s((1, 1024)))


def test_flash_attention_compiles(one_chip):
    """Causal prefill at S = T = 256, GQA 16/8, head_dim 128."""
    s = _spec(one_chip)
    block = tuning.attn_heuristic_block(256, 256, 2, 128, 128, POLICY)
    _compile(lambda q, k, v: tcec_attention(q, k, v, policy=POLICY,
                                            block=block, interpret=False),
             s((1, 256, 16, 128)), s((1, 256, 8, 128)), s((1, 256, 8, 128)))


def test_flash_attention_compiles_at_largest_block(one_chip):
    """(512, 512) blocks under the widest policy, with a sliding window."""
    s = _spec(one_chip)
    pol = FUSED_POLICIES[-1]
    _compile(lambda q, k, v: tcec_attention(q, k, v, policy=pol,
                                            block=(512, 512), window=300,
                                            interpret=False),
             s((1, 1024, 16, 128)), s((1, 1024, 8, 128)),
             s((1, 1024, 8, 128)))


def test_paged_decode_compiles(one_chip):
    """One decode token per slot against head-major bf16 page pools,
    page size 16, at the heuristic pages-per-step."""
    s = _spec(one_chip)
    B, maxp, ps, NP = 4, 20, 16, 81
    g = tuning.paged_heuristic_block(maxp, ps, 2, 128, 128, POLICY)
    _compile(lambda q, kp, vp, bt, n: tcec_paged_attention(
                 q, kp, vp, bt, n, policy=POLICY, pages_per_step=g,
                 interpret=False),
             s((B, 16, 128)), s((NP, 8, ps, 128), jnp.bfloat16),
             s((NP, 8, ps, 128), jnp.bfloat16), s((B, maxp), jnp.int32),
             s((B,), jnp.int32))


def test_decode_step_keeps_the_page_pool_in_place(one_chip):
    """The serving engine's decode step at smoke widths but the published
    head_dim (128 lanes: a narrower head pads in the TPU's tiled layout,
    which no deployment has), with a page pool larger than VMEM (which
    small arrays may be copied into) and large enough to dominate the
    step's memory: the stacked pool flows through
    the layer scan in place.  A second copy of the pool among the
    temporaries, or any copy, slice or update of the whole stack, is
    the regression (XLA re-lays-out or re-stacks the pool when the scan
    takes it as ``xs``/``ys`` or scatters into the carried stack)."""
    import dataclasses
    import functools
    import re
    from repro import numerics
    from repro.configs import get_smoke_config
    from repro.models import get_model
    from repro.serving import engine as eng_mod
    cfg = dataclasses.replace(get_smoke_config("qwen3-0.6b"), head_dim=128)
    model = get_model(cfg)
    s = _spec(one_chip)
    on = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    B, maxp, NP, ps = 4, 16, 5001, 16      # 2 x 164 MB of f32 K/V pages
    params = on(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pools = on(jax.eval_shape(functools.partial(
        model.init_paged_cache, NP, ps, dtype=jnp.float32)))
    i32, f32 = jnp.int32, jnp.float32
    with numerics.use(force=True, interpret=False, guard=False):
        compiled = jax.jit(functools.partial(
            eng_mod._decode_and_sample, model=model, cfg=cfg),
            donate_argnums=(1,)).lower(
                params, pools, s((B, maxp), i32), s((B,), i32), s((B,), i32),
                s((B,), f32), s((B,), i32), s((B,), f32),
                s((B, 2), jnp.uint32), s((B,), jnp.bool_)).compile()
    pool = pools["dense_blocks"]["k"]
    pool_bytes = 2 * pool.size * 4                       # K and V
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
    shape = "f32[" + ",".join(map(str, pool.shape)) + "]"
    moves = [ln for ln in compiled.as_text().splitlines()
             if re.search(re.escape(shape) + r"\S* (copy|dynamic-slice|"
                      r"dynamic-update-slice|copy-start)\(", ln)
             or re.search(r"= " + re.escape(shape) + r"\S* fusion\(", ln)]
    assert not moves, moves[:3]
    assert "kv_page_write_pallas" in compiled.as_text()
