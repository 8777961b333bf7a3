"""Fused TCEC flash-attention Pallas kernel.

One kernel computes the whole attention inner loop for a `(B, Hkv, q_block)`
grid cell — the paper's "no extra memory footprint" discipline applied one
level up from the GEMM:

  * K/V blocks stream HBM -> VMEM along the last (``arbitrary``) grid axis;
  * ``QK^T`` runs as the TCEC-split bf16 MXU passes (the ``_split_tile`` /
    kept-term schedule of ``tcec_matmul.py``) with the scale-group fold done
    in VMEM — the contraction dim (head_dim) is fully resident, so the fold
    happens immediately, exactly like the XLA term expansion;
  * scale, softcap, and the causal/window/tail mask apply to the scores tile
    **in VMEM** (the additive f32 bias of ``models.layers._mask_bias``);
  * the online softmax keeps running max/sum in VMEM scratch (flash
    attention; Markidis et al. arXiv:1803.04014 is why: Tensor-Core-era
    attention is bandwidth-bound, and the correction passes make the
    HBM round trip of a materialized scores tensor even more expensive);
  * ``P·V`` runs TCEC-split too, into one f32 VMEM accumulator per scale
    group (Code 3's frag_c / frag_dc), folded smallest-first on the last
    K step.

So the ``(S, T)`` scores/probs tensors never touch HBM, and causally
fully-masked K blocks are skipped inside the grid (``@pl.when`` on a
block-level predicate computed from the position tiles — the XLA
``blocked_attention`` fallback visits every chunk).

GQA runs by head grouping: the grid iterates KV heads and each q block
carries all ``rep = H // Hkv`` query heads of the group, so K/V are
fetched once per KV head and never materialized ``rep``-fold. The
``rep·bq`` query rows feed the MXU as one tall matmul.

Numerics contract (tests/test_attention.py): with a single K block covering
the whole (padded) KV length, the kernel normalizes the probs tile before
the ``P·V`` product — the exact operation sequence of the ``mha`` pdot
composition — and is **bit-identical** to it. Multi-block runs use the
online-softmax rescaling (per-group accumulators scaled by
``exp(m_old - m_new)``) and match the fallback to f32 tolerance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy import PrecisionPolicy, get_policy
from .tcec_matmul import VMEM_BUDGET, _split_tile, compiler_params

# Must match models.layers.NEG_INF: the additive mask bias is part of the
# bit-parity contract with the pdot-composition fallback (finite, so
# fully-masked rows produce garbage instead of NaN — same as the fallback).
NEG_INF = -2.0e38


def _contract(a, b, dims, upcast: bool):
    if upcast:
        # interpret mode: bf16 -> f32 is exact and two bf16-valued f32
        # factors multiply exactly in f32 — bit-identical to the MXU
        # contract (see tcec_matmul._kernel).
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _tcec_product(a, b, dims, policy: PrecisionPolicy, upcast: bool):
    """Split-term GEMM with the scale-group fold done immediately.

    Valid when the contraction dim is fully resident in VMEM (true for both
    attention products: head_dim for QK^T, the k-block for P·V within one
    grid step) — the fold order then matches ``_tcec_dot`` bit for bit."""
    sa = _split_tile(a, policy.n_splits, policy.scale_bits)
    sb = _split_tile(b, policy.n_splits, policy.scale_bits)
    parts: dict[int, jax.Array] = {}
    for (i, j) in policy.keep:
        t = _contract(sa[i], sb[j], dims, upcast)
        g = i + j
        parts[g] = t if g not in parts else parts[g] + t
    groups = policy.groups
    inv = jnp.float32(2.0 ** (-policy.scale_bits))
    out = parts[groups[-1]]
    for g in groups[-2::-1]:
        out = parts[g] + out * inv
    return out


# (lhs last dim) x (rhs last dim): QK^T contracts head_dim against head_dim
_QK_DIMS = (((1,), (1,)), ((), ()))
# plain row-major matmul: P (rows, bk) x V (bk, hdv)
_PV_DIMS = (((1,), (0,)), ((), ()))


def _attn_kernel(win_ref, q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref,
                 m_ref, l_ref, *accs, policy: PrecisionPolicy, rep: int,
                 k_steps: int, causal: bool, softcap: float | None,
                 sm_denom: float, t_actual: int, upcast: bool):
    bq, hd = q_ref.shape[3], q_ref.shape[4]
    bk, hdv = k_ref.shape[2], v_ref.shape[3]
    rows = rep * bq
    ki = pl.program_id(3)
    groups = policy.groups

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    qp = qp_ref[...]                     # (bq, 1) i32 query positions
    kp = kp_ref[...]                     # (1, bk) i32 key positions
    win = win_ref[0]                     # traced scalar; 0 = unlimited

    # ---- block-level skip: a K block masked for every (q, k) pair in the
    # tile contributes exactly zero probability mass, so skipping it is
    # numerically identical to the fallback's exp(-2e38 - m) underflow.
    col0 = ki * bk
    run = col0 < t_actual                            # padded KV tail
    if causal:
        run = jnp.logical_and(run, jnp.max(qp) >= jnp.min(kp))
    run = jnp.logical_and(                           # window: all d >= win
        run, jnp.logical_or(win <= 0, jnp.min(qp) - jnp.max(kp) < win))

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].reshape(rows, hd)
        s = _tcec_product(q, k_ref[0, 0], _QK_DIMS, policy, upcast)
        s = s / jnp.float32(sm_denom)
        if softcap:
            cap = jnp.float32(softcap)
            s = cap * jnp.tanh(s / cap)
        # additive f32 mask bias — models.layers._mask_bias, tile-local,
        # plus masking of the zero-padded KV tail
        qpr = jnp.concatenate([qp] * rep, axis=0)    # (rows, 1)
        d = qpr - kp                                 # (rows, bk)
        ok = (d >= 0) if causal else jnp.ones_like(d, dtype=bool)
        ok = jnp.logical_and(ok, jnp.logical_or(win <= 0, d < win))
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        ok = jnp.logical_and(ok, cols < t_actual)
        s = s + jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)

        v = v_ref[0, 0]
        if k_steps == 1:
            # single-block path: the softmax completes here, so normalize
            # the probs tile *before* the split P·V product — the exact op
            # order of the mha fallback (bit-parity case).
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            p = p / jnp.sum(p, axis=-1, keepdims=True)
            for gi, part in enumerate(_pv_parts(p, v, policy, upcast)):
                accs[gi][...] += part
        else:
            m_prev = m_ref[...]                      # (rows, 128) lane-bcast
            l_prev = l_ref[...]
            m_curr = jnp.max(s, axis=-1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_curr)
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next[:, :1])
            l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[...] = m_next
            a_col = alpha[:, :1]
            for gi, part in enumerate(_pv_parts(p, v, policy, upcast)):
                accs[gi][...] = accs[gi][...] * a_col + part

    @pl.when(ki == k_steps - 1)
    def _epilogue():
        inv = jnp.float32(2.0 ** (-policy.scale_bits))
        out = accs[len(groups) - 1][...]
        for gi in range(len(groups) - 2, -1, -1):
            out = accs[gi][...] + out * inv
        if k_steps > 1:
            out = out / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = out.reshape(rep, bq, hdv)


def _pv_parts(p, v, policy: PrecisionPolicy, upcast: bool):
    """Per-scale-group partial P·V products (unfolded: the caller owns the
    cross-K-block accumulators, fold happens in the epilogue)."""
    sp = _split_tile(p, policy.n_splits, policy.scale_bits)
    sv = _split_tile(v, policy.n_splits, policy.scale_bits)
    parts: dict[int, jax.Array] = {}
    for (i, j) in policy.keep:
        t = _contract(sp[i], sv[j], _PV_DIMS, upcast)
        g = i + j
        parts[g] = t if g not in parts else parts[g] + t
    return [parts[g] for g in policy.groups]


def attn_vmem_bytes(block: tuple[int, int], rep: int, hd: int, hdv: int,
                    policy: PrecisionPolicy) -> int:
    """VMEM working set of one attention grid step (the capacity filter the
    autotuner applies — same role as ``vmem_bytes`` for the GEMM kernel).

    ``hd``/``hdv`` are rounded up to the 128-lane MXU here so the filter
    judges the shapes the kernel actually runs — callers may pass unpadded
    model head dims."""
    bq, bk = block
    hd, hdv = _round_up(hd, 128), _round_up(hdv, 128)
    rows = rep * bq
    n = policy.n_splits
    tiles = 4 * (rows * hd + bk * hd + bk * hdv)          # f32 Q/K/V tiles
    splits = 2 * n * (rows * hd + bk * hd + bk * hdv)     # bf16 split terms
    scores = (4 + 2 * n) * rows * bk                      # f32 s/p + splits
    stats = 2 * rows * 128 * 4                            # m/l lane-bcast
    accum = len(policy.groups) * rows * hdv * 4           # f32 group accs
    out = rows * hdv * 4
    return tiles + splits + scores + stats + accum + out


def _round_up(x: int, m: int) -> int:
    return max(m, ((x + m - 1) // m) * m)


@functools.partial(jax.jit, static_argnames=(
    "policy_name", "rep", "block", "causal", "softcap", "sm_denom",
    "t_actual", "interpret"))
def tcec_attention_pallas(q, k, v, q_pos, k_pos, window, *, policy_name: str,
                          rep: int, block: tuple[int, int],
                          causal: bool, softcap: float | None,
                          sm_denom: float, t_actual: int,
                          interpret: bool = False):
    """Fused attention on pre-padded, pre-transposed operands.

    q: (B, Hkv, rep, Sp, hd); k: (B, Hkv, Tp, hd); v: (B, Hkv, Tp, hdv);
    q_pos: (1, Sp) i32; k_pos: (1, Tp) i32; window: (1,) i32 (0 = off).
    Sp/Tp must be multiples of ``block``; returns (B, Hkv, rep, Sp, hdv) f32.
    """
    policy = get_policy(policy_name)
    assert not policy.is_plain(), "attention kernel is for split policies"
    B, Hkv, rep2, Sp, hd = q.shape
    Tp, hdv = k.shape[2], v.shape[3]
    assert rep2 == rep and k.shape[:2] == (B, Hkv) == v.shape[:2]
    bq, bk = block
    assert Sp % bq == 0 and Tp % bk == 0, (q.shape, k.shape, block)
    assert attn_vmem_bytes(block, rep, hd, hdv, policy) <= VMEM_BUDGET, \
        (block, rep, hd, hdv, policy.name)
    k_steps = Tp // bk
    grid = (B, Hkv, Sp // bq, k_steps)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary"))

    kern = functools.partial(
        _attn_kernel, policy=policy, rep=rep, k_steps=k_steps, causal=causal,
        softcap=softcap, sm_denom=sm_denom, t_actual=t_actual,
        upcast=interpret)
    rows = rep * bq
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                   # window
            pl.BlockSpec((1, 1, rep, bq, hd),
                         lambda b, h, qi, ki: (b, h, 0, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, hdv),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((bq, 1), lambda b, h, qi, ki: (qi, 0)),
            pl.BlockSpec((1, bk), lambda b, h, qi, ki: (0, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, bq, hdv),
                               lambda b, h, qi, ki: (b, h, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, Sp, hdv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, 128), jnp.float32),     # running m
                        pltpu.VMEM((rows, 128), jnp.float32)]     # running l
                       + [pltpu.VMEM((rows, hdv), jnp.float32)
                          for _ in policy.groups],
        interpret=interpret,
        **kwargs,
    )(window, q, k, v, q_pos, k_pos)


def _pad_axis(x, axis: int, mult: int):
    p = (-x.shape[axis]) % mult
    if not p:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, p)
    return jnp.pad(x, pads)


def tcec_attention(q, k, v, q_pos=None, k_pos=None, *,
                   policy: str = "tcec_bf16x6", causal: bool = True,
                   window=0, softcap: float | None = None,
                   block: tuple[int, int] | None = None,
                   interpret: bool | None = None) -> jax.Array:
    """Public entry: fused TCEC attention on model-layout operands.

    q: (B, S, H, hd); k: (B, T, Hkv, hd); v: (B, T, Hkv, hdv); GQA via
    ``H = rep * Hkv``. ``q_pos``/``k_pos`` are (B, S)/(B, T) or (S,)/(T,)
    position vectors (batch-uniform, like the model layers; defaults to
    ``arange``). ``window`` may be a traced scalar (0 = unlimited).
    Shapes are padded internally: S/T to the block, head dims to the
    128-lane MXU (zero padding is exact — zero split terms contribute
    zero products, padded K columns are masked, padded V rows are zero).
    Returns (B, S, H, hdv) f32.
    """
    B, S, H, hd = q.shape
    T, Hkv, hdv = k.shape[1], k.shape[2], v.shape[3]
    assert H % Hkv == 0, (H, Hkv)
    rep = H // Hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block is None:
        from . import tuning
        block = tuning.get_attention_block(B, Hkv, rep, S, T, hd, hdv, policy,
                                           causal=causal)
    bq, bk = block

    qt = q.astype(jnp.float32).reshape(B, S, Hkv, rep, hd)
    qt = jnp.transpose(qt, (0, 2, 3, 1, 4))          # (B, Hkv, rep, S, hd)
    kt = jnp.transpose(k.astype(jnp.float32), (0, 2, 1, 3))
    vt = jnp.transpose(v.astype(jnp.float32), (0, 2, 1, 3))
    qt = _pad_axis(_pad_axis(qt, 3, bq), 4, 128)
    kt = _pad_axis(_pad_axis(kt, 2, bk), 3, 128)
    vt = _pad_axis(_pad_axis(vt, 2, bk), 3, 128)

    def pos_row(p, n, mult):
        if p is None:
            p = jnp.arange(n, dtype=jnp.int32)
        p = jnp.asarray(p, jnp.int32)
        if p.ndim == 2:                              # batch-uniform, like mha
            p = p[0]
        return _pad_axis(p.reshape(1, n), 1, mult)

    qp = pos_row(q_pos, S, bq).reshape(-1, 1)       # column: rows of s
    kp = pos_row(k_pos, T, bk)
    win = jnp.asarray(0 if window is None else window, jnp.int32).reshape(1)

    out = tcec_attention_pallas(
        qt, kt, vt, qp, kp, win, policy_name=policy, rep=rep, block=block,
        causal=causal, softcap=(float(softcap) if softcap else None),
        sm_denom=float(np.sqrt(hd)), t_actual=T, interpret=interpret)
    out = jnp.transpose(out, (0, 3, 1, 2, 4))        # (B, Sp, Hkv, rep, hdv)
    return out[:, :S].reshape(B, S, H, out.shape[-1])[..., :hdv]
