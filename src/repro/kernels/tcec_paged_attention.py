"""Fused TCEC paged decode-attention Pallas kernel.

Decode-time attention against a **paged** KV cache: each sequence's keys
and values live in fixed-size pages of a shared pool, addressed through a
per-sequence block table (the vLLM PagedAttention layout, TPU-native).
This is the one serving hot path that still bypassed the TCEC kernels —
and, per Markidis et al. (arXiv:1803.04014), the one where matrix-unit
throughput only materializes if the gather feeds the MMA tiles directly
instead of round-tripping a defragmented copy through HBM.

One kernel invocation computes a whole decode step for a ``(B, Hkv)``
grid cell, streaming the sequence's pages along the last (``arbitrary``)
grid axis:

  * the block table and sequence lengths ride in SMEM via
    ``PrefetchScalarGridSpec`` — the BlockSpec index maps read
    ``block_table[b, step * G + j]`` to DMA the right pages from the pool
    into VMEM, so the gather *is* the tile fetch (no gathered copy of the
    cache is ever materialized in HBM);
  * ``pages_per_step`` (``G``) pages are fetched per grid step — one
    BlockSpec per page — and concatenated in VMEM into a ``(G·ps)``-column
    KV tile, the kernel's tunable (``kernels/tuning.py``, the
    ``backend/paged/...`` cache namespace);
  * ``QK^T`` and ``P·V`` run TCEC-split (``_split_tile`` and the kept-term
    schedule of ``tcec_matmul.py``) with per-scale-group f32 VMEM
    accumulators folded smallest-first in the epilogue — the same
    correction discipline as the prefill flash-attention kernel;
  * the online softmax keeps running max/sum in VMEM scratch; pages wholly
    past the sequence length (or wholly outside the sliding window) are
    skipped via ``@pl.when`` on a block-level predicate.

Numerics: the fallback decode path (``models.layers.attention_decode``)
computes its cache dots in plain bf16 — the query and the probabilities
are *rounded to bf16* before the MXU. This kernel instead splits the f32
query and the f32 probs tile (the cache itself is bf16-valued, so its
first split term is exact and the residual terms vanish), recovering the
precision the dense path discards — the paper's correction applied at
decode time.  Tests assert the kernel sits closer to an f32 oracle than
the bf16 fallback does, and matches the fallback to bf16-level tolerance.

Masking is a **select**, not an additive bias: recycled pages may hold
stale garbage from finished requests, and ``garbage + NEG_INF`` could
stay non-finite (see ``attention_decode``'s O(T) validity select).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy import PrecisionPolicy, get_policy
from .tcec_matmul import VMEM_BUDGET, compiler_params
from .tcec_attention import (NEG_INF, _QK_DIMS, _pv_parts, _round_up,
                             _tcec_product)


def _paged_kernel(tbl_ref, len_ref, win_ref, layer_ref, q_ref, *refs,
                  policy: PrecisionPolicy, rep: int, pages: int,
                  n_steps: int, softcap: float | None, sm_denom: float,
                  upcast: bool):
    k_refs = refs[:pages]
    v_refs = refs[pages:2 * pages]
    o_ref = refs[2 * pages]
    m_ref, l_ref, *accs = refs[2 * pages + 1:]
    ps = k_refs[0].shape[3]
    cols = pages * ps
    b = pl.program_id(0)
    i = pl.program_id(2)
    groups = policy.groups

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    length = len_ref[b]                  # valid tokens incl. current
    cur = length - 1                     # position of the current token
    win = win_ref[0]                     # traced scalar; 0 = unlimited
    col0 = i * cols

    # ---- block-level skip: pages wholly past the sequence length, or
    # wholly older than the sliding window, contribute zero mass.
    run = col0 < length
    run = jnp.logical_and(
        run, jnp.logical_or(win <= 0, cur - (col0 + cols - 1) < win))

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)              # (rep, hd)
        kt = jnp.concatenate(
            [k_refs[j][0, 0, 0].astype(jnp.float32) for j in range(pages)],
            axis=0)                                      # (cols, hd)
        vt = jnp.concatenate(
            [v_refs[j][0, 0, 0].astype(jnp.float32) for j in range(pages)],
            axis=0)                                      # (cols, hdv)
        s = _tcec_product(q, kt, _QK_DIMS, policy, upcast)
        s = s / jnp.float32(sm_denom)
        if softcap:
            cap = jnp.float32(softcap)
            s = cap * jnp.tanh(s / cap)
        # validity select (not an additive bias): recycled pages hold
        # stale finite-or-not garbage that must not leak through
        pos = col0 + jax.lax.broadcasted_iota(jnp.int32, (rep, cols), 1)
        ok = pos <= cur
        ok = jnp.logical_and(ok, jnp.logical_or(win <= 0, cur - pos < win))
        s = jnp.where(ok, s, jnp.float32(NEG_INF))

        if n_steps == 1:
            # single-step: the softmax completes here — normalize the
            # probs tile before the split P·V (the fallback's op order)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            p = p / jnp.sum(p, axis=-1, keepdims=True)
            for gi, part in enumerate(_pv_parts(p, vt, policy, upcast)):
                accs[gi][...] += part
        else:
            m_prev = m_ref[...]                          # (rep, 128)
            l_prev = l_ref[...]
            m_curr = jnp.max(s, axis=-1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_curr)
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next[:, :1])
            l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[...] = m_next
            a_col = alpha[:, :1]
            for gi, part in enumerate(_pv_parts(p, vt, policy, upcast)):
                accs[gi][...] = accs[gi][...] * a_col + part

    @pl.when(i == n_steps - 1)
    def _epilogue():
        inv = jnp.float32(2.0 ** (-policy.scale_bits))
        out = accs[len(groups) - 1][...]
        for gi in range(len(groups) - 2, -1, -1):
            out = accs[gi][...] + out * inv
        if n_steps > 1:
            out = out / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = out


def paged_vmem_bytes(pages_per_step: int, page_size: int, rep: int, hd: int,
                     hdv: int, policy: PrecisionPolicy) -> int:
    """VMEM working set of one paged-attention grid step (the capacity
    filter the ``backend/paged`` autotuner applies).  Head dims and the
    gathered column count are rounded to the 128-lane MXU; the query rows
    to the f32 8-sublane tile."""
    hd, hdv = _round_up(hd, 128), _round_up(hdv, 128)
    rows = _round_up(rep, 8)
    cols = _round_up(pages_per_step * page_size, 128)
    n = policy.n_splits
    pages = 2 * pages_per_step * page_size * (hd + hdv)   # bf16 page tiles
    tiles = 4 * (rows * hd + cols * hd + cols * hdv)      # f32 Q/K/V tiles
    splits = 2 * n * (rows * hd + cols * hd + cols * hdv)
    scores = (4 + 2 * n) * rows * cols                    # f32 s/p + splits
    stats = 2 * rows * 128 * 4                            # m/l lane-bcast
    accum = len(policy.groups) * rows * hdv * 4
    out = rows * hdv * 4
    return pages + tiles + splits + scores + stats + accum + out


@functools.partial(jax.jit, static_argnames=(
    "policy_name", "rep", "pages_per_step", "softcap", "sm_denom",
    "interpret"))
def tcec_paged_attention_pallas(q, k_pages, v_pages, block_tables, lengths,
                                window, layer, *, policy_name: str, rep: int,
                                pages_per_step: int,
                                softcap: float | None, sm_denom: float,
                                interpret: bool = False):
    """Paged decode attention on pool-layout operands.

    q: (B, Hkv, rep, hd) f32; k_pages: (L, NP, Hkv, ps, hd); v_pages:
    (L, NP, Hkv, ps, hdv) — a stack of per-layer pools (any float dtype —
    pages are upcast per tile); block_tables: (B, maxp) i32 page indices,
    ``maxp`` a multiple of ``pages_per_step`` (pad rows with any allocated
    page — masked); lengths: (B,) i32 valid tokens *including* the current
    one; window: (1,) i32 (0 = unlimited); layer: (1,) i32, the stack
    entry to read — it rides in SMEM beside the block table, so the page
    DMAs address the stacked pool directly and no layer is ever sliced
    out of it.  Returns (B, Hkv, rep, hdv) f32; rows with ``length <= 0``
    return zeros.
    """
    policy = get_policy(policy_name)
    assert not policy.is_plain(), "paged kernel is for split policies"
    B, Hkv, rep2, hd = q.shape
    _, NP, Hkv2, ps, hd2 = k_pages.shape
    hdv = v_pages.shape[4]
    assert rep2 == rep and Hkv2 == Hkv and hd2 == hd, (q.shape, k_pages.shape)
    assert v_pages.shape[:4] == k_pages.shape[:4], (k_pages.shape,
                                                    v_pages.shape)
    G = pages_per_step
    maxp = block_tables.shape[1]
    assert block_tables.shape[0] == B and maxp % G == 0, (block_tables.shape,
                                                          G)
    assert paged_vmem_bytes(G, ps, rep, hd, hdv, policy) <= VMEM_BUDGET, \
        (G, ps, rep, hd, hdv, policy.name)
    n_steps = maxp // G
    grid = (B, Hkv, n_steps)

    # one (ps, width) page of one KV head of one layer per block: the
    # trailing block dims equal the pool's, which Mosaic's tiling accepts
    # at any size
    def page_spec(j, width):
        return pl.BlockSpec(
            (1, 1, 1, ps, width),
            lambda b, h, i, tbl, lens, win, lyr, j=j: (
                lyr[0], tbl[b, i * G + j], h, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1, rep, hd),
                               lambda b, h, i, *_: (b, h, 0, 0))]
                 + [page_spec(j, hd) for j in range(G)]
                 + [page_spec(j, hdv) for j in range(G)],
        out_specs=pl.BlockSpec((1, 1, rep, hdv),
                               lambda b, h, i, *_: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rep, 128), jnp.float32),    # running m
                        pltpu.VMEM((rep, 128), jnp.float32)]    # running l
                       + [pltpu.VMEM((rep, hdv), jnp.float32)
                          for _ in policy.groups],
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = compiler_params(
            ("parallel", "parallel", "arbitrary"))
    kern = functools.partial(
        _paged_kernel, policy=policy, rep=rep, pages=G, n_steps=n_steps,
        softcap=softcap, sm_denom=sm_denom, upcast=interpret)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, hdv), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(block_tables, lengths, window, layer,
      q, *([k_pages] * G), *([v_pages] * G))


def tcec_paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                         policy: str = "tcec_bf16x6", window=0,
                         softcap: float | None = None,
                         pages_per_step: int | None = None,
                         layer=None,
                         interpret: bool | None = None) -> jax.Array:
    """Public entry: fused paged decode attention on model-layout operands.

    q: (B, H, hd) — one query token per sequence slot; k_pages/v_pages:
    (NP, Hkv, ps, hd[v]) head-major page pools, or a stack of them (L, NP,
    Hkv, ps, hd[v]) read at entry ``layer`` (an int or traced scalar; a
    single pool is a one-entry stack read at 0); block_tables: (B, maxp)
    i32; lengths: (B,) i32 valid tokens including the current one (the
    current token's K/V must already be written to its page).  GQA via
    ``H = rep * Hkv``; ``window`` may be a traced scalar (0 = unlimited).
    Returns (B, H, hdv) f32.
    """
    if k_pages.ndim == 4:
        assert layer is None, "a single pool has no layer to pick"
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    B, H, hd = q.shape
    _, NP, Hkv, ps, _ = k_pages.shape
    hdv = v_pages.shape[4]
    assert H % Hkv == 0, (H, Hkv)
    rep = H // Hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    maxp = block_tables.shape[1]
    if pages_per_step is None:
        from . import tuning
        pages_per_step = tuning.get_paged_block(B, Hkv, rep, maxp, ps, hd,
                                                hdv, policy)
    G = max(1, min(int(pages_per_step), maxp))
    bt = jnp.asarray(block_tables, jnp.int32)
    pad = (-maxp) % G
    if pad:
        bt = jnp.pad(bt, ((0, 0), (0, pad)))
    win = jnp.asarray(0 if window is None else window, jnp.int32).reshape(1)
    qt = q.astype(jnp.float32).reshape(B, Hkv, rep, hd)
    out = tcec_paged_attention_pallas(
        qt, k_pages, v_pages, bt, jnp.asarray(lengths, jnp.int32), win,
        jnp.asarray(layer, jnp.int32).reshape(1),
        policy_name=policy, rep=rep, pages_per_step=G,
        softcap=(float(softcap) if softcap else None),
        sm_denom=float(np.sqrt(hd)), interpret=interpret)
    return out.reshape(B, H, hdv)
