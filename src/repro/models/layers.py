"""Shared neural layers. Every contraction routes through repro.core.pdot,
so the paper's error-corrected GEMM is a config knob for the whole zoo."""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pdot
from .modules import dense_init, ones, split_keys, zeros

NEG_INF = -2.0e38


# ------------------------------------------------------------------ norms

def rmsnorm(scale, x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))


def layernorm(p, x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


# ------------------------------------------------------------------- rope

def rope(x, positions, theta: float):
    """Rotary embedding. x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    half = d // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def softcap(x, cap):
    return cap * jnp.tanh(x / cap) if cap else x


# ------------------------------------------------------------- attention

def attn_init(key, cfg):
    D, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H, hd), fan_in=D),
        "wk": dense_init(ks[1], (D, Hkv, hd), fan_in=D),
        "wv": dense_init(ks[2], (D, Hkv, hd), fan_in=D),
        "wo": dense_init(ks[3], (H, hd, D), fan_in=H * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros((H, hd))
        p["bk"] = zeros((Hkv, hd))
        p["bv"] = zeros((Hkv, hd))
    if cfg.qk_norm:
        p["q_norm"] = zeros((hd,))
        p["k_norm"] = zeros((hd,))
    return p


def _project_qkv(p, x, cfg, positions):
    pol = cfg.policy
    q = pdot("bsd,dhk->bshk", x, p["wq"], pol)
    k = pdot("bsd,dhk->bshk", x, p["wk"], pol)
    v = pdot("bsd,dhk->bshk", x, p["wv"], pol)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mask_bias(q_pos, k_pos, causal: bool, window):
    """Additive mask from position vectors. window may be a traced scalar
    (0 = unlimited) so local/global layers share one scanned code path."""
    d = q_pos[:, None] - k_pos[None, :]
    ok = (d >= 0) if causal else jnp.ones_like(d, dtype=bool)
    ok &= jnp.where(window > 0, d < window, True)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def mha(q, k, v, cfg, q_pos, k_pos, causal=True, window=0):
    """Materialized-scores attention (short sequences).

    q: (B, S, H, d); k/v: (B, T, Hkv, d). GQA via head grouping — no KV
    repetition is materialized."""
    from repro.parallel import ctx
    B, S, H, hd = q.shape
    Hkv, hdv = k.shape[2], v.shape[3]
    rep = H // Hkv
    qg = q.reshape(B, S, Hkv, rep, hd)
    # context parallelism: shard the q-sequence on the model axis so the
    # S x S score block shrinks 16x per device regardless of kv-head count
    qg = ctx.constrain(qg, ctx.dp_axes(), "model", None, None, None)
    scores = pdot("bqhrd,bkhd->bhrqk", qg, k, cfg.mix_policy)
    scores = ctx.constrain(scores, ctx.dp_axes(), None, None, "model", None)
    scores = scores / np.sqrt(hd)
    scores = softcap(scores, cfg.attn_softcap)
    scores = scores + _mask_bias(q_pos[0], k_pos[0], causal, window)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = pdot("bhrqk,bkhd->bqhrd", probs, v, cfg.mix_policy)
    out = ctx.constrain(out, ctx.dp_axes(), None, None, "model", None)
    return out.reshape(B, S, H, hdv)


def blocked_attention(q, k, v, cfg, q_pos, k_pos, causal=True, window=0,
                      q_chunk=2048, k_chunk=2048):
    """Flash-style attention: O(S·chunk) memory, online softmax over KV
    blocks — required for the 32k prefill cells.

    Causal short-circuit: a KV chunk whose every position lies strictly in
    the causal future of the whole q chunk (``min(k_pos) > max(q_pos)``)
    carries only ``-inf`` scores — its probability mass underflows to
    exactly 0 — so its GEMMs are skipped via ``lax.cond`` inside the scan
    (~2x FLOPs saved on causal prefill, ``ki > qi`` chunks for the models'
    ``arange`` positions).  The predicate is position-based, so it is
    correct for any nondecreasing positions (ties included), works with a
    traced ``window``, and stays reverse-differentiable (``cond``, unlike a
    dynamic-bound ``fori_loop``, has a VJP)."""
    B, S, H, hd = q.shape
    T, Hkv, hdv = k.shape[1], k.shape[2], v.shape[3]
    rep = H // Hkv
    nq, nk = S // q_chunk, T // k_chunk
    assert S % q_chunk == 0 and T % k_chunk == 0, (S, T, q_chunk, k_chunk)
    qg = q.reshape(B, nq, q_chunk, Hkv, rep, hd)
    kg = k.reshape(B, nk, k_chunk, Hkv, hd)
    vg = v.reshape(B, nk, k_chunk, Hkv, hdv)
    qp = q_pos[0].reshape(nq, q_chunk)
    kp = k_pos[0].reshape(nk, k_chunk)
    scale = 1.0 / np.sqrt(hd)

    from repro.parallel import ctx

    def q_block(qi):
        qblk = qg[:, qi]                     # (B, qc, Hkv, rep, hd)
        qblk = ctx.constrain(qblk, ctx.dp_axes(), "model", None, None, None)
        qpos = qp[qi]

        def kv_step(carry, ki):
            def live(c):
                m, l, acc = c
                s = pdot("bqhrd,bkhd->bhrqk", qblk, kg[:, ki],
                         cfg.mix_policy) * scale
                s = ctx.constrain(s, ctx.dp_axes(), None, None, "model",
                                  None)
                s = softcap(s, cfg.attn_softcap)
                s = s + _mask_bias(qpos, kp[ki], causal, window)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                pv = pdot("bhrqk,bkhd->bhrqd", p, vg[:, ki], cfg.mix_policy)
                acc_new = acc * corr[..., None] + pv
                return (m_new, l_new, acc_new)

            if causal:
                needed = jnp.min(kp[ki]) <= jnp.max(qpos)
                return jax.lax.cond(needed, live, lambda c: c, carry), None
            return live(carry), None

        m0 = jnp.full((B, Hkv, rep, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, rep, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, rep, q_chunk, hdv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]     # (B,Hkv,rep,qc,hdv)
        return jnp.transpose(out, (0, 3, 1, 2, 4))        # (B,qc,Hkv,rep,hdv)

    out = jax.lax.map(q_block, jnp.arange(nq))            # (nq,B,qc,Hkv,rep,hdv)
    out = jnp.transpose(out, (1, 0, 2, 3, 4, 5)).reshape(B, S, H, hdv)
    return out


ATTN_BLOCK_THRESHOLD = 8192


def _sdpa_composition(q, k, v, cfg, q_pos, k_pos, causal, window):
    """The pdot-composition path: blocked for long sequences, mha else.

    Blocked needs chunk-divisible S/T; the fused kernel pads internally,
    so shapes reachable only through the fused forward (e.g. its recompute
    backward) fall to mha when the chunk grid doesn't divide."""
    if (q.shape[1] >= ATTN_BLOCK_THRESHOLD
            and q.shape[1] % 2048 == 0 and k.shape[1] % 2048 == 0):
        return blocked_attention(q, k, v, cfg, q_pos, k_pos, causal, window)
    return mha(q, k, v, cfg, q_pos, k_pos, causal, window)


# The Pallas attention kernel has no VJP of its own, so the fused route is
# wrapped in a custom_vjp whose backward *recomputes* attention through the
# pdot composition and differentiates that — the same policy-preserving
# recompute discipline as fused_linear's backward (flash-attention
# backwards recompute the forward anyway; the composition's pdots carry
# their own custom_vjp, so the gradient GEMMs still dispatch to the fused
# GEMM kernel).  Without this, jax.grad through a dispatched attention
# call would fail at trace time on every training step.

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _fused_sdpa(q, k, v, q_pos, k_pos, window, causal, policy_name, softcap):
    from repro.kernels import dispatch
    out = dispatch.attention(q, k, v, policy=policy_name, q_pos=q_pos,
                             k_pos=k_pos, causal=causal, window=window,
                             softcap=softcap)
    assert out is not None, "caller must pre-check dispatch.attention_eligible"
    return out


def _fused_sdpa_fwd(q, k, v, q_pos, k_pos, window, causal, policy_name,
                    softcap):
    out = _fused_sdpa(q, k, v, q_pos, k_pos, window, causal, policy_name,
                      softcap)
    return out, (q, k, v, q_pos, k_pos, window)


def _fused_sdpa_bwd(causal, policy_name, softcap, res, g):
    import types
    q, k, v, q_pos, k_pos, window = res
    cfg = types.SimpleNamespace(mix_policy=policy_name, attn_softcap=softcap)

    def ref(q, k, v):
        return _sdpa_composition(q, k, v, cfg, q_pos, k_pos, causal, window)

    _, vjp = jax.vjp(ref, q, k, v)
    dq, dk, dv = vjp(g.astype(jnp.float32))

    def z(x):   # int operands (positions / window) take float0 cotangents
        return np.zeros(np.shape(x), dtype=jax.dtypes.float0)

    return dq, dk, dv, z(q_pos), z(k_pos), z(window)


_fused_sdpa.defvjp(_fused_sdpa_fwd, _fused_sdpa_bwd)


def sdpa(q, k, v, cfg, q_pos, k_pos, causal=True, window=0):
    """Scaled-dot-product attention router — the single entry every model
    self-attention variant goes through.

    Takes the fused TCEC flash-attention kernel when
    ``kernels.dispatch.attention_eligible`` says so (declines off-TPU
    without force, for plain policies, below ``min_dim``, or under either
    escape hatch), with the recompute backward above; otherwise the pdot
    composition — ``blocked_attention`` for long sequences,
    materialized-scores ``mha`` else.  Under an installed GSPMD mesh the
    fused route runs per device through the ``shard_map`` wrapper
    (``kernels/shmap.py``: heads or q-sequence on ``model``, batch on the
    data axes); specs the wrapper doesn't support — and
    ``use(shard_map=False)`` / ``REPRO_SHARD_MAP=0`` — keep the pdot
    composition, which carries the context-parallel sharding constraints.
    The composition is also the kernel's verification oracle
    (tests/test_attention.py)."""
    from repro.core.policy import get_policy
    from repro.kernels import dispatch
    if dispatch.attention_eligible(q, k, v, policy=cfg.mix_policy):
        return _fused_sdpa(q, k, v, q_pos, k_pos, window, causal,
                           get_policy(cfg.mix_policy).name, cfg.attn_softcap)
    return _sdpa_composition(q, k, v, cfg, q_pos, k_pos, causal, window)


def attention(p, x, cfg, positions, causal=True, window=0):
    """Full attention layer: qkv -> sdpa (fused or blocked) -> out proj."""
    q, k, v = _project_qkv(p, x, cfg, positions)
    o = sdpa(q, k, v, cfg, positions, positions, causal, window)
    return pdot("bshk,hkd->bsd", o, p["wo"], cfg.policy)


def _decode_attend(q, ck, cv, cfg, cur_pos, window=0):
    """One-token attention over a dense-layout cache view.

    q: (B, 1, H, hd); ck/cv: (B, T, Hkv, d) — either the dense cache or a
    page gather (serving).  ``cur_pos`` is the current token's position:
    a scalar (dense decode, batch-uniform) or a (B,) vector (continuous
    batching, one in-flight length per slot).

    Cache dots run in bf16: the cache is already bf16 (splitting it is
    pointless — the residual is exactly zero) and f32 upcasts would copy
    the whole cache per step.
    """
    B, T, Hkv = ck.shape[0], ck.shape[1], ck.shape[2]
    H, hd = q.shape[2], q.shape[3]
    rep = H // Hkv
    qg = q.reshape(B, 1, Hkv, rep, hd)
    s = pdot("bqhrd,bkhd->bhrqk", qg, ck, "bf16")
    s = softcap(s / np.sqrt(hd), cfg.attn_softcap)
    # mask by k_pos <= cur_pos directly: one O(T) validity vector per
    # step (never a (T, T) _mask_bias intermediate).  A select, not an
    # additive bias: the stale cache tail may hold non-finite garbage
    # (inf + NEG_INF = inf, NaN + anything = NaN would leak through).
    cur = jnp.asarray(cur_pos, jnp.int32).reshape(-1, 1)      # (B or 1, 1)
    d = cur - jnp.arange(T, dtype=jnp.int32)[None]            # (B or 1, T)
    ok = d >= 0
    ok &= jnp.where(window > 0, d < window, True)
    s = jnp.where(ok[:, None, None, None, :], s, jnp.float32(NEG_INF))
    pr = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o = pdot("bhrqk,bkhd->bqhrd", pr, cv, "bf16")
    return o.reshape(B, 1, H, cv.shape[3])


def attention_decode(p, x, cfg, cache, cache_index, window=0):
    """One-token decode against a (B, T, Hkv, d) KV cache."""
    B = x.shape[0]
    positions = jnp.full((B, 1), cache_index, dtype=jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions)
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, cache_index, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, cache_index, 0, 0))
    o = _decode_attend(q, ck, cv, cfg, cache_index, window)
    out = pdot("bshk,hkd->bsd", o, p["wo"], cfg.policy)
    return out, {"k": ck, "v": cv}


def attention_prefill(p, x, cfg, positions, window=0):
    """Full attention layer that also returns the K/V it computed, so a
    sequence-level prefill can fill a cache in ONE jitted forward instead
    of S sequential ``attention_decode`` calls.  Same math as
    :func:`attention` (the fused sdpa route included)."""
    q, k, v = _project_qkv(p, x, cfg, positions)
    o = sdpa(q, k, v, cfg, positions, positions, True, window)
    out = pdot("bshk,hkd->bsd", o, p["wo"], cfg.policy)
    return out, {"k": k, "v": v}


def attention_chunk(p, x, cfg, cache, start, window=0):
    """One prefill *chunk* against a dense scratch cache (chunked prefill).

    x: (B, C, d_model) — chunk tokens at absolute positions ``start ..
    start + C``; cache: an :func:`attention_prefill`-layout dense cache
    ``{"k", "v"}`` with leaves (B, T, Hkv, d) holding every earlier
    chunk's exact K/V (and, on a prefix-cache hit, the gathered shared
    pages).  The chunk's own K/V is written in, then attention runs over
    the full [0, T) key range through the same :func:`sdpa` router as the
    monolithic prefill — the causal mask hides positions ``>= start + C``
    (zero-initialized scratch stays finite, so the additive mask bias is
    safe), which makes each chunk row bitwise-equal to the corresponding
    monolithic prefill row when the cache is f32.
    """
    B, C = x.shape[:2]
    positions = jnp.broadcast_to(
        start + jnp.arange(C, dtype=jnp.int32)[None], (B, C))
    q, k, v = _project_qkv(p, x, cfg, positions)
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, start, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, start, 0, 0))
    T = ck.shape[1]
    k_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    o = sdpa(q, ck, cv, cfg, positions, k_pos, True, window)
    out = pdot("bshk,hkd->bsd", o, p["wo"], cfg.policy)
    return out, {"k": ck, "v": cv}


def attention_decode_paged(p, x, cfg, pool, block_tables, lengths, window=0,
                           layer=None):
    """One-token decode against a paged KV cache (serving engine).

    x: (B, 1, d_model) — one token per sequence slot; pool: ``{"k": (NP,
    Hkv, ps, hd), "v": (NP, Hkv, ps, hdv)}`` page arrays shared across
    slots — or, with ``layer`` given, every layer's pools stacked, ``(L,
    NP, Hkv, ps, hd[v])``, of which this layer is entry ``layer``;
    block_tables: (B, maxp) i32 page indices per slot; lengths: (B,) i32
    tokens already cached per slot (the current token's position).

    The new token's K/V is written into its slot's current page, then
    attention runs through ``dispatch.attention_decode`` (the fused paged
    kernel) when eligible, else gathers the block table into a dense view
    and applies exactly the :func:`attention_decode` math — bitwise the
    same attend as the dense cache path, which is what makes the engine's
    greedy output token-identical to the legacy dense ``generate()``.  A
    stacked pool is written in place by the page-write kernel
    (``dispatch.kv_write``) and read at ``layer`` by the paged kernel;
    the caller takes that path only where both are eligible.  Returns the
    output and the updated pool, stacked as it came.
    """
    from repro.kernels import dispatch
    B = x.shape[0]
    positions = lengths[:, None].astype(jnp.int32)            # (B, 1)
    q, k, v = _project_qkv(p, x, cfg, positions)
    ps = pool["k"].shape[-2]
    maxp = block_tables.shape[1]
    page = block_tables[jnp.arange(B), lengths // ps]         # (B,)
    off = lengths % ps
    at = () if layer is None else (layer,)
    written = None
    if layer is not None:
        written = dispatch.kv_write(pool["k"], pool["v"], k[:, 0], v[:, 0],
                                    page, off, layer, policy=cfg.mix_policy)
    if written is not None:
        ck, cv = written
    else:
        ck = pool["k"].at[at + (page, slice(None), off)].set(
            k[:, 0].astype(pool["k"].dtype))
        cv = pool["v"].at[at + (page, slice(None), off)].set(
            v[:, 0].astype(pool["v"].dtype))
    fused = dispatch.attention_decode(q[:, 0], ck, cv, block_tables,
                                      lengths + 1, policy=cfg.mix_policy,
                                      window=window,
                                      softcap=cfg.attn_softcap, layer=layer)
    if fused is not None:
        o = fused[:, None].astype(jnp.float32)                # (B, 1, H, hdv)
    else:
        def gather(pages):                     # -> (B, maxp * ps, Hkv, d)
            if layer is not None:
                pages = pages[layer]
            g = jnp.swapaxes(pages[block_tables], 2, 3)
            return g.reshape((B, maxp * ps) + g.shape[3:])
        kg, vg = gather(ck), gather(cv)
        o = _decode_attend(q, kg, vg, cfg, lengths, window)
    out = pdot("bshk,hkd->bsd", o, p["wo"], cfg.policy)
    return out, {"k": ck, "v": cv}


# ------------------------------------------------------------------- MLP

def mlp_init(key, cfg, d_ff=None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = split_keys(key, 3)
    return {
        "w_gate": dense_init(ks[0], (D, F), fan_in=D),
        "w_up": dense_init(ks[1], (D, F), fan_in=D),
        "w_down": dense_init(ks[2], (F, D), fan_in=F),
    }


def _act(x, kind: str):
    return jax.nn.gelu(x) if kind == "gelu" else jax.nn.silu(x)


# ------------------------------------------------- fused linear epilogue
#
# When the numerics config enables epilogue fusion (REPRO_FUSE_EPILOGUE or
# repro.numerics.use(fuse_epilogue=True)), act(x @ W + b) runs as ONE fused
# Pallas kernel call: the bias add and activation fold into the kernel's
# scaled epilogue on the last K step, so the pre-activation never round-trips
# HBM. The backward stays policy-preserving: it recomputes the pre-activation
# with the same policy GEMM and routes dx/dW through pdot (which itself
# dispatches), exactly like the unfused path's custom_vjp.
#
# NB the fused forward flattens (B, S, D) -> (B*S, D) for the 2-D kernel;
# under GSPMD that reshape can replicate a sharded batch dim, so fusion is
# an opt-in serving/throughput knob, not the training default.

def _epilogue_act(z, activation):
    """The exact activation set the kernel epilogue supports — keyed by the
    same table, so fused and unfused paths can never disagree on semantics
    (``_act``'s anything-but-gelu-means-silu default is NOT safe here)."""
    from repro.kernels.tcec_matmul import EPILOGUE_ACTIVATIONS
    return EPILOGUE_ACTIVATIONS[activation](z)


def _linear_unfused(x, w, b, activation, policy):
    z = pdot("bsd,df->bsf", x, w, policy)
    if b is not None:
        z = z + b
    return _epilogue_act(z, activation)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear(x, w, b, activation, policy):
    """act(x @ w + b) with the epilogue fused into the TCEC kernel when the
    dispatch config allows it; reference pdot path otherwise.

    x: (B, S, D); w: (D, F); b: (F,) or None; activation: None|"gelu"|"silu".
    """
    from repro import numerics
    from repro.kernels import dispatch, ops
    from repro.core.policy import get_policy
    pol = get_policy(policy)
    B, S, D = x.shape
    F = w.shape[-1]
    cfg = numerics.active()
    if (dispatch.epilogue_eligible(pol, cfg)
            and min(B * S, D, F) >= cfg.min_dim):
        x2 = x.reshape(B * S, D)
        block = dispatch.tuned_block(B * S, F, D, pol.name, cfg=cfg)
        out = ops.tcec_matmul(x2, w, policy=pol.name, block=block,
                              interpret=cfg.interpret, bias=b,
                              activation=activation, cfg=cfg)
        return out.reshape(B, S, F)
    return _linear_unfused(x, w, b, activation, policy)


def _fused_linear_fwd(x, w, b, activation, policy):
    return fused_linear(x, w, b, activation, policy), (x, w, b)


def _fused_linear_bwd(activation, policy, res, dy):
    x, w, b = res
    if activation:
        # recompute the pre-activation under the same policy (policy-
        # preserving backward, same discipline as _make_dg's custom_vjp)
        z = _linear_unfused(x, w, b, None, policy)
        _, act_vjp = jax.vjp(lambda t: _epilogue_act(t, activation), z)
        dz = act_vjp(dy)[0]
    else:
        dz = dy
    dx = pdot("bsf,df->bsd", dz, w, policy)
    dw = pdot("bsd,bsf->df", x, dz, policy)
    db = jnp.sum(dz, axis=(0, 1)).astype(b.dtype) if b is not None else None
    return dx.astype(x.dtype), dw.astype(w.dtype), db


fused_linear.defvjp(_fused_linear_fwd, _fused_linear_bwd)


def mlp(p, x, cfg):
    from repro import numerics
    if numerics.active().fuse_epilogue:
        g = fused_linear(x, p["w_gate"], None, cfg.activation, cfg.policy)
        u = fused_linear(x, p["w_up"], None, None, cfg.policy)
        return pdot("bsf,fd->bsd", g * u, p["w_down"], cfg.policy)
    g = pdot("bsd,df->bsf", x, p["w_gate"], cfg.policy)
    u = pdot("bsd,df->bsf", x, p["w_up"], cfg.policy)
    h = _act(g, cfg.activation) * u
    return pdot("bsf,fd->bsd", h, p["w_down"], cfg.policy)


# ------------------------------------------------------------------- MoE

def moe_init(key, cfg):
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = split_keys(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E), fan_in=D),
        "w_gate": dense_init(ks[1], (E, D, F), fan_in=D),
        "w_up": dense_init(ks[2], (E, D, F), fan_in=D),
        "w_down": dense_init(ks[3], (E, F, D), fan_in=F),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], cfg,
                               d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return p


def moe(p, x, cfg):
    """GShard-style top-k MoE with capacity + one-hot dispatch einsums.

    Token groups of ``cfg.moe_groups`` bound the dispatch-tensor size and the
    dispatch-FLOPs overhead (~gs*cf/3F of expert FLOPs; see DESIGN.md)."""
    B, S, D = x.shape
    E, K, F = cfg.n_experts, cfg.moe_top_k, cfg.moe_d_ff
    N = B * S
    gs = min(cfg.moe_groups, N)
    while N % gs:            # largest divisor <= target (MTP runs S-1)
        gs -= 1
    G = N // gs
    xg = x.reshape(G, gs, D)

    logits = pdot("gsd,de->gse", xg, p["router"], "fp32")
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(gates, K)                  # (G, gs, K)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    C = int(np.ceil(gs * K / E * cfg.capacity_factor / 4) * 4)
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32)   # (G, gs, K, E)
    flat = onehot.reshape(G, gs * K, E)
    # position-within-expert over (s, k) slot order. associative_scan, NOT
    # jnp.cumsum: XLA lowers big cumsums to a triangular matmul whose fake
    # FLOPs would dwarf the expert GEMMs (log-depth adds instead).
    pos = jax.lax.associative_scan(jnp.add, flat, axis=1)
    pos_t = ((pos - 1.0) * flat).sum(-1).reshape(G, gs, K)
    keep = (pos_t < C).astype(jnp.bfloat16)               # (G, gs, K)
    posc = jnp.clip(pos_t, 0, C - 1).astype(jnp.int32)
    oh_c = jax.nn.one_hot(posc, C, dtype=jnp.bfloat16)    # (G, gs, K, C)
    oh_e = onehot.astype(jnp.bfloat16)                    # (G, gs, K, E)
    # K-unrolled outer products: only (G, gs, E, C) accumulators live —
    # never a K-expanded (G, gs, K, E, C) tensor.
    dispatch = jnp.zeros((G, gs, E, C), jnp.bfloat16)
    combine = jnp.zeros((G, gs, E, C), jnp.bfloat16)
    for k in range(K):
        t = (oh_e[:, :, k, :, None] * oh_c[:, :, k, None, :]
             * keep[:, :, k, None, None])
        dispatch = dispatch + t
        combine = combine + t * topv[:, :, k, None, None].astype(jnp.bfloat16)

    xe = pdot("gsec,gsd->gecd", dispatch,
              xg.astype(jnp.bfloat16), "bf16")            # all-to-all under EP
    hg = pdot("gecd,edf->gecf", xe, p["w_gate"], cfg.policy)
    hu = pdot("gecd,edf->gecf", xe, p["w_up"], cfg.policy)
    he = _act(hg, cfg.activation) * hu
    ye = pdot("gecf,efd->gecd", he, p["w_down"], cfg.policy)
    y = pdot("gsec,gecd->gsd", combine,
             ye.astype(jnp.bfloat16), "bf16")
    y = y.reshape(B, S, D)

    if cfg.n_shared_experts:
        y = y + mlp(p["shared"], x, cfg)
    # load-balancing auxiliary (GShard aux loss), returned for training
    me = jnp.mean(gates, axis=(0, 1))
    ce = jnp.mean(onehot.sum(2), axis=(0, 1))
    aux = jnp.sum(me * ce) * E
    return y, aux
