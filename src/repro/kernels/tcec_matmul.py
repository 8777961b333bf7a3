"""TCEC matmul Pallas kernel — the paper's CUTLASS integration, TPU-native.

One fused kernel computes an FP32-accurate GEMM on the bf16 MXU:

  * f32 A/B tiles stream HBM -> VMEM exactly once (same traffic as SGEMM —
    the paper's "no extra memory footprint" property: splits are never
    materialized to HBM, they are computed in-register per tile, mirroring
    the paper's "compute Eq (19)-(22) on the registers" CUTLASS change);
  * the split products run as 3 (``tcec_bf16x3``) or 6 (``tcec_bf16x6``)
    bf16 MXU passes per tile with f32 outputs;
  * accumulation across the K grid happens in **f32 VMEM scratch** outside
    the MXU accumulation chain — the paper's RZ-avoidance (Fig. 6) — with
    one scratch accumulator per scale group (Code 3's frag_c / frag_dc);
  * the scaled epilogue folds correction groups smallest-first on the last
    K step (Code 3's ``frag_c.x[i] += frag_dc.x[i]/2048``) and can
    optionally fold a bias add, an output scale, and an activation into
    the same VMEM-resident pass (model layers use this to fuse their
    linear-layer epilogues — no extra HBM round trip for ``act(xW + b)``).

The kernel runs on a 3-D grid ``(M/bm, N/bn, K/bk)`` for 2-D operands and a
4-D grid ``(B, M/bm, N/bn, K/bk)`` for batched operands (``policy_bmm`` /
attention-shaped contractions), with the batch dimension blocked at 1.

Block shapes are BlockSpec parameters; MXU-aligned multiples of 128 are
enforced by the ops.py wrapper, and the VMEM working set is checked against
the per-core budget (the analogue of the paper's shared-memory-capacity
filter in their CUTLASS parameter sweep).  Block *selection* lives in
``kernels/tuning.py`` (measured autotuner) and ``kernels/dispatch.py``
routes framework contractions here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy import PrecisionPolicy, get_policy

# v5e has 128 MiB of VMEM per core, but Mosaic's default scoped limit is
# 16 MiB, which the larger blocks exceed.  VMEM_BUDGET filters blocks by the
# working set the ``*_vmem_bytes`` models estimate; VMEM_LIMIT is the scoped
# limit every kernel asks Mosaic for, with room for the temporaries the
# estimates leave out.
VMEM_BUDGET = 64 * 1024 * 1024
VMEM_LIMIT = 100 * 1024 * 1024


def compiler_params(semantics):
    """Mosaic parameters shared by the TCEC kernels."""
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)

# Activations the fused epilogue supports. These are the exact jnp/jax.nn
# callables the reference (unfused) model path uses, so fusing an epilogue
# never changes numerics — only where it runs.
EPILOGUE_ACTIVATIONS = {
    None: lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
}


def _split_tile(x, n_splits: int, scale_bits: int):
    """In-register split of an f32 tile into bf16 terms (Eqs. 19-22)."""
    scale = jnp.float32(2.0 ** scale_bits)
    parts = []
    r = x
    for i in range(n_splits):
        a = r.astype(jnp.bfloat16)
        parts.append(a)
        if i + 1 < n_splits:
            r = (r - a.astype(jnp.float32)) * scale
    return parts


def _kernel(*refs, policy: PrecisionPolicy, k_steps: int, k_axis: int,
            batched: bool, has_bias: bool, activation: str | None,
            out_scale: float, upcast: bool):
    if has_bias:
        a_ref, b_ref, bias_ref, o_ref, *accs = refs
    else:
        a_ref, b_ref, o_ref, *accs = refs
        bias_ref = None
    k = pl.program_id(k_axis)
    groups = policy.groups

    @pl.when(k == 0)
    def _init():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    a = a_ref[0] if batched else a_ref[...]   # (bm, bk) f32
    b = b_ref[0] if batched else b_ref[...]   # (bk, bn) f32
    sa = _split_tile(a, policy.n_splits, policy.scale_bits)
    sb = _split_tile(b, policy.n_splits, policy.scale_bits)
    if upcast:
        # interpret mode: XLA-CPU lacks bf16 DotThunks for some shapes.
        # bf16 -> f32 is exact and two bf16-valued f32 factors multiply
        # exactly in f32 (8+8 <= 24 mantissa bits), so this is bit-identical
        # to the MXU contract (exact products, f32 RN accumulation).
        sa = [t.astype(jnp.float32) for t in sa]
        sb = [t.astype(jnp.float32) for t in sb]

    for gi, g in enumerate(groups):
        part = None
        for (i, j) in policy.keep:
            if i + j != g:
                continue
            t = jnp.dot(sa[i], sb[j], preferred_element_type=jnp.float32)
            part = t if part is None else part + t
        # f32 VMEM accumulate — outside the MXU chain (RN adds, Fig. 6)
        accs[gi][...] += part

    @pl.when(k == k_steps - 1)
    def _epilogue():
        out = accs[len(groups) - 1][...]
        inv = jnp.float32(2.0 ** (-policy.scale_bits))
        for gi in range(len(groups) - 2, -1, -1):
            out = accs[gi][...] + out * inv
        # fused scaled epilogue: scale -> bias -> activation, all in VMEM
        if out_scale != 1.0:
            out = out * jnp.float32(out_scale)
        if bias_ref is not None:
            out = out + bias_ref[...]          # (1, bn) broadcasts over bm
        out = EPILOGUE_ACTIVATIONS[activation](out)
        if batched:
            o_ref[0] = out
        else:
            o_ref[...] = out


def vmem_bytes(block: tuple[int, int, int], policy: PrecisionPolicy,
               has_bias: bool = False) -> int:
    """VMEM working set of one grid step (the shared-memory-capacity filter)."""
    bm, bn, bk = block
    groups = len(policy.groups)
    tiles = (bm * bk + bk * bn) * 4                      # f32 A/B tiles
    splits = (bm * bk + bk * bn) * 2 * policy.n_splits   # bf16 split terms
    accs = groups * bm * bn * 4                          # f32 accumulators
    out = bm * bn * 4
    bias = bn * 4 if has_bias else 0
    return tiles + splits + accs + out + bias


@functools.partial(jax.jit, static_argnames=("policy_name", "block",
                                             "interpret", "activation",
                                             "out_scale"))
def tcec_matmul_pallas(a: jax.Array, b: jax.Array, bias: jax.Array | None = None,
                       *, policy_name: str,
                       block: tuple[int, int, int] = (128, 128, 128),
                       interpret: bool = False, activation: str | None = None,
                       out_scale: float = 1.0) -> jax.Array:
    """Fused TCEC GEMM on pre-padded operands.

    2-D: ``(M, K) @ (K, N) -> (M, N)`` f32; batched: ``(B, M, K) @ (B, K, N)
    -> (B, M, N)``.  M/N/K must be multiples of ``block``; ``bias`` (if any)
    must be pre-shaped ``(1, N)``.  The optional epilogue computes
    ``act(out * out_scale + bias)`` inside the kernel's final K step.
    """
    policy = get_policy(policy_name)
    assert not policy.is_plain(), "pallas kernel is for split policies"
    assert activation in EPILOGUE_ACTIVATIONS, activation
    batched = a.ndim == 3
    if batched:
        B, M, K = a.shape
        B2, K2, N = b.shape
        assert B == B2, (a.shape, b.shape)
    else:
        M, K = a.shape
        K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = block
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (a.shape, b.shape, block)
    has_bias = bias is not None
    assert vmem_bytes(block, policy, has_bias) <= VMEM_BUDGET, \
        (block, policy.name)
    if has_bias:
        assert bias.shape == (1, N), bias.shape
    groups = policy.groups
    k_steps = K // bk

    if batched:
        grid = (B, M // bm, N // bn, k_steps)
        in_specs = [
            pl.BlockSpec((1, bm, bk), lambda p, i, j, k: (p, i, k)),
            pl.BlockSpec((1, bk, bn), lambda p, i, j, k: (p, k, j)),
        ]
        if has_bias:
            in_specs.append(pl.BlockSpec((1, bn), lambda p, i, j, k: (0, j)))
        out_specs = pl.BlockSpec((1, bm, bn), lambda p, i, j, k: (p, i, j))
        out_shape = jax.ShapeDtypeStruct((B, M, N), jnp.float32)
        semantics = ("parallel", "parallel", "parallel", "arbitrary")
    else:
        grid = (M // bm, N // bn, k_steps)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ]
        if has_bias:
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        out_specs = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
        out_shape = jax.ShapeDtypeStruct((M, N), jnp.float32)
        semantics = ("parallel", "parallel", "arbitrary")

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = compiler_params(semantics)

    kern = functools.partial(
        _kernel, policy=policy, k_steps=k_steps, k_axis=len(grid) - 1,
        batched=batched, has_bias=has_bias, activation=activation,
        out_scale=out_scale, upcast=interpret)
    operands = (a, b, bias) if has_bias else (a, b)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32) for _ in groups],
        interpret=interpret,
        **kwargs,
    )(*operands)
