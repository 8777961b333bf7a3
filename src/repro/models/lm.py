"""Decoder-only LM assembly: dense (gemma/qwen), MoE (granite/deepseek),
with MLA and local/global attention variants. Layers are stacked with
``lax.scan`` over stacked params (O(1) HLO size at any depth) and
rematerialized per block."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pdot
from . import layers as L
from . import mla as M
from .modules import dense_init, embed_init, split_keys, stack_init, zeros


# --------------------------------------------------------------- blocks

def block_init(key, cfg, *, moe: bool):
    ks = split_keys(key, 4)
    p = {"ln1": zeros((cfg.d_model,)), "ln2": zeros((cfg.d_model,))}
    if cfg.use_mla:
        p["attn"] = M.mla_init(ks[0], cfg)
    else:
        p["attn"] = L.attn_init(ks[0], cfg)
    if moe:
        p["moe"] = L.moe_init(ks[1], cfg)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg)
    if cfg.sandwich_norms:
        p["post_ln1"] = zeros((cfg.d_model,))
        p["post_ln2"] = zeros((cfg.d_model,))
    return p


def block_apply(p, x, cfg, positions, window, *, moe: bool):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        a = M.mla_attention(p["attn"], h, cfg, positions)
    else:
        a = L.attention(p["attn"], h, cfg, positions, causal=True,
                        window=window)
    if cfg.sandwich_norms:
        a = L.rmsnorm(p["post_ln1"], a, cfg.norm_eps)
    x = x + a
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    aux = jnp.float32(0.0)
    if moe:
        m, aux = L.moe(p["moe"], h, cfg)
    else:
        m = L.mlp(p["mlp"], h, cfg)
    if cfg.sandwich_norms:
        m = L.rmsnorm(p["post_ln2"], m, cfg.norm_eps)
    return x + m, aux


def block_prefill(p, x, cfg, positions, window, *, moe: bool):
    """``block_apply`` that also returns the block's cache entries, so a
    sequence-level prefill fills the KV cache in one jitted forward."""
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        a, kv = M.mla_attention_prefill(p["attn"], h, cfg, positions)
    else:
        a, kv = L.attention_prefill(p["attn"], h, cfg, positions,
                                    window=window)
    if cfg.sandwich_norms:
        a = L.rmsnorm(p["post_ln1"], a, cfg.norm_eps)
    x = x + a
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    aux = jnp.float32(0.0)
    if moe:
        m, aux = L.moe(p["moe"], h, cfg)
    else:
        m = L.mlp(p["mlp"], h, cfg)
    if cfg.sandwich_norms:
        m = L.rmsnorm(p["post_ln2"], m, cfg.norm_eps)
    return x + m, aux, kv


def block_decode(p, x, cfg, cache, cache_index, window, *, moe: bool):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        a, new_cache = M.mla_decode(p["attn"], h, cfg, cache, cache_index)
    else:
        a, new_cache = L.attention_decode(p["attn"], h, cfg, cache,
                                          cache_index, window=window)
    if cfg.sandwich_norms:
        a = L.rmsnorm(p["post_ln1"], a, cfg.norm_eps)
    x = x + a
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if moe:
        m, _ = L.moe(p["moe"], h, cfg)
    else:
        m = L.mlp(p["mlp"], h, cfg)
    if cfg.sandwich_norms:
        m = L.rmsnorm(p["post_ln2"], m, cfg.norm_eps)
    return x + m, new_cache


# ------------------------------------------------------------ stacking

def layer_windows(cfg, n_layers: int) -> np.ndarray:
    """Per-layer sliding windows (0 = global) — gemma2's local/global."""
    if cfg.local_global_period and cfg.sliding_window:
        return np.asarray(
            [cfg.sliding_window if i % cfg.local_global_period == 0 else 0
             for i in range(n_layers)], dtype=np.int32)
    if cfg.sliding_window:
        return np.full((n_layers,), cfg.sliding_window, dtype=np.int32)
    return np.zeros((n_layers,), dtype=np.int32)


def stack_apply(stacked, x, cfg, positions, windows, *, moe: bool):
    def body(carry, xs):
        lp, w = xs
        y, aux = block_apply(lp, carry, cfg, positions, w, moe=moe)
        return y, aux

    if cfg.remat:
        body = jax.checkpoint(body)
    x, auxs = jax.lax.scan(body, x, (stacked, jnp.asarray(windows)))
    return x, jnp.sum(auxs)


def stack_prefill(stacked, x, cfg, positions, windows, *, moe: bool):
    """``stack_apply`` that stacks each layer's cache entries as scan ys:
    leaves come back as (n_layers, B, S, ...)."""
    def body(carry, xs):
        lp, w = xs
        y, aux, kv = block_prefill(lp, carry, cfg, positions, w, moe=moe)
        return y, (aux, kv)

    if cfg.remat:
        body = jax.checkpoint(body)
    x, (auxs, kvs) = jax.lax.scan(body, x, (stacked, jnp.asarray(windows)))
    return x, jnp.sum(auxs), kvs


def stack_decode(stacked, x, cfg, caches, cache_index, windows, *, moe: bool):
    def body(carry, xs):
        lp, cache, w = xs
        y, nc = block_decode(lp, carry, cfg, cache, cache_index, w, moe=moe)
        return y, nc

    x, new_caches = jax.lax.scan(body, x, (stacked, caches,
                                           jnp.asarray(windows)))
    return x, new_caches


def block_decode_paged(p, x, cfg, pool, block_tables, lengths, window, *,
                       moe: bool, layer=None):
    """``block_decode`` against one layer's page pool (serving engine), or
    against every layer's pools stacked, read and written at ``layer``."""
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        a, new_pool = M.mla_decode_paged(p["attn"], h, cfg, pool,
                                         block_tables, lengths)
    else:
        a, new_pool = L.attention_decode_paged(p["attn"], h, cfg, pool,
                                               block_tables, lengths,
                                               window=window, layer=layer)
    if cfg.sandwich_norms:
        a = L.rmsnorm(p["post_ln1"], a, cfg.norm_eps)
    x = x + a
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if moe:
        m, _ = L.moe(p["moe"], h, cfg)
    else:
        m = L.mlp(p["mlp"], h, cfg)
    if cfg.sandwich_norms:
        m = L.rmsnorm(p["post_ln2"], m, cfg.norm_eps)
    return x + m, new_pool


def _in_place_query(cfg, batch: int):
    return jax.ShapeDtypeStruct((batch, cfg.n_heads, cfg.head_dim),
                                jnp.float32)


def decode_in_place(cfg, pools, batch: int) -> bool:
    """Whether :func:`decode_step_paged` keeps the page pools in place
    under the active numerics config and mesh: every stack's pools are
    head-major K/V (not MLA's latent pools) and both the page-write and
    the paged attention kernel are eligible for them.  Records nothing;
    the serving engine asks it to count the steps run in place."""
    from repro.kernels import dispatch
    return all(dispatch.kv_write_reason(_in_place_query(cfg, batch), pool,
                                        policy=cfg.mix_policy) == "fused"
               for pool in pools.values())


def stack_decode_paged(stacked, x, cfg, pools, block_tables, lengths,
                       windows, *, moe: bool):
    """Decode through a stack of layers against its page pools.

    In place (both kernels eligible — :func:`decode_in_place`): the
    stacked pools ride in the scan carry beside ``x`` and each layer
    writes its token into them and reads them at its own index, so no
    layer's pool is sliced out, re-laid-out or written back.  Otherwise
    the scan takes each layer's pool as ``xs`` and returns it as ``ys``,
    scattering with XLA."""
    from repro.kernels import dispatch
    if dispatch.kv_write_eligible(_in_place_query(cfg, x.shape[0]), pools,
                                  policy=cfg.mix_policy):
        def body(carry, xs):
            y, pool = carry
            lp, w, layer = xs
            return block_decode_paged(lp, y, cfg, pool, block_tables,
                                      lengths, w, moe=moe,
                                      layer=layer), None

        n = len(windows)
        (x, pools), _ = jax.lax.scan(
            body, (x, pools),
            (stacked, jnp.asarray(windows), jnp.arange(n, dtype=jnp.int32)))
        return x, pools

    def body(carry, xs):
        lp, pool, w = xs
        y, npool = block_decode_paged(lp, carry, cfg, pool, block_tables,
                                      lengths, w, moe=moe)
        return y, npool

    x, new_pools = jax.lax.scan(body, x, (stacked, pools,
                                          jnp.asarray(windows)))
    return x, new_pools


def block_chunk(p, x, cfg, cache, start, window, *, moe: bool):
    """``block_prefill`` for one chunk of the prompt, reading/extending a
    dense scratch cache (chunked prefill — serving engine)."""
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        a, new_cache = M.mla_attention_chunk(p["attn"], h, cfg, cache, start)
    else:
        a, new_cache = L.attention_chunk(p["attn"], h, cfg, cache, start,
                                         window=window)
    if cfg.sandwich_norms:
        a = L.rmsnorm(p["post_ln1"], a, cfg.norm_eps)
    x = x + a
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if moe:
        m, _ = L.moe(p["moe"], h, cfg)
    else:
        m = L.mlp(p["mlp"], h, cfg)
    if cfg.sandwich_norms:
        m = L.rmsnorm(p["post_ln2"], m, cfg.norm_eps)
    return x + m, new_cache


def stack_chunk(stacked, x, cfg, caches, start, windows, *, moe: bool):
    def body(carry, xs):
        lp, cache, w = xs
        y, nc = block_chunk(lp, carry, cfg, cache, start, w, moe=moe)
        return y, nc

    x, new_caches = jax.lax.scan(body, x, (stacked, caches,
                                           jnp.asarray(windows)))
    return x, new_caches


# ----------------------------------------------------------- top level

def init(cfg, key):
    ks = split_keys(key, 4)
    params = {"embed": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model)),
              "ln_f": zeros((cfg.d_model,))}
    nd = cfg.first_dense_layers
    n_moe = (cfg.n_layers - nd) if cfg.n_experts else 0
    n_dense = cfg.n_layers - n_moe
    if n_dense:
        params["dense_blocks"] = stack_init(
            lambda k: block_init(k, cfg, moe=False), ks[1], n_dense)
    if n_moe:
        params["moe_blocks"] = stack_init(
            lambda k: block_init(k, cfg, moe=True), ks[2], n_moe)
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(ks[3], (cfg.d_model, cfg.padded_vocab),
                                       fan_in=cfg.d_model)
    if cfg.mtp:
        params["mtp_block"] = block_init(
            jax.random.fold_in(key, 99), cfg,
            moe=bool(cfg.n_experts))
        params["mtp_proj"] = dense_init(
            jax.random.fold_in(key, 98), (2 * cfg.d_model, cfg.d_model),
            fan_in=2 * cfg.d_model)
    return params


def embed(params, tokens, cfg):
    from repro.parallel import ctx
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embeddings:
        x = x * np.sqrt(cfg.d_model)
    return ctx.constrain(x.astype(jnp.float32), ctx.dp_axes(), None, None)


def unembed_logits(params, x, cfg):
    from repro.parallel import ctx
    w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    pol = cfg.logits_policy or cfg.policy
    logits = pdot("bsd,dv->bsv", x, w, pol)
    logits = ctx.constrain(logits, ctx.dp_axes(), None, "model")
    return L.softcap(logits, cfg.final_softcap)


def backbone(params, tokens, cfg, positions):
    x = embed(params, tokens, cfg)
    aux = jnp.float32(0.0)
    windows = layer_windows(cfg, cfg.n_layers)
    nd = cfg.first_dense_layers
    n_moe = (cfg.n_layers - nd) if cfg.n_experts else 0
    n_dense = cfg.n_layers - n_moe
    if n_dense:
        x, a = stack_apply(params["dense_blocks"], x, cfg, positions,
                           windows[:n_dense], moe=False)
        aux += a
    if n_moe:
        x, a = stack_apply(params["moe_blocks"], x, cfg, positions,
                           windows[n_dense:], moe=True)
        aux += a
    return L.rmsnorm(params["ln_f"], x, cfg.norm_eps), aux


def forward(params, batch, cfg):
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x, aux = backbone(params, tokens, cfg, positions)
    logits = unembed_logits(params, x, cfg)
    return logits, aux, x


def cross_entropy(logits, labels, z_loss_w: float = 1e-4):
    """Masked CE with z-loss; labels < 0 are ignored (one-hot formulation —
    shards cleanly when the vocab dim is model-parallel)."""
    from repro.parallel import ctx
    mask = (labels >= 0).astype(jnp.float32)
    lbl = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(lbl, logits.shape[-1], dtype=jnp.bfloat16)
    onehot = ctx.constrain(onehot, ctx.dp_axes(), None, "model")
    ll = jnp.sum(logits.astype(jnp.float32) * onehot, axis=-1)
    nll = (logz - ll) * mask
    zl = z_loss_w * jnp.square(logz) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll + zl) / denom, denom


def loss_fn(params, batch, cfg):
    logits, aux, x = forward(params, batch, cfg)
    loss, denom = cross_entropy(logits, batch["labels"])
    metrics = {"lm_loss": loss, "aux_loss": aux, "tokens": denom}
    if cfg.n_experts:
        loss = loss + 0.01 * aux
    if cfg.mtp:
        # DeepSeek-V3 multi-token prediction: one extra depth predicting t+2
        h = jnp.concatenate(
            [x[:, :-1], embed(params, batch["tokens"], cfg)[:, 1:]], axis=-1)
        h = pdot("bsd,de->bse", h, params["mtp_proj"], cfg.policy)
        B, S1 = h.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(S1, dtype=jnp.int32)[None], (B, S1))
        h, _ = block_apply(params["mtp_block"], h, cfg, pos, 0,
                           moe=bool(cfg.n_experts))
        mtp_logits = unembed_logits(params, h, cfg)
        mtp_loss, _ = cross_entropy(mtp_logits, batch["labels"][:, 1:])
        metrics["mtp_loss"] = mtp_loss
        loss = loss + 0.3 * mtp_loss
    metrics["loss"] = loss
    return loss, metrics


# ------------------------------------------------------------- serving

def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    def one(_):
        if cfg.use_mla:
            return M.mla_init_cache(cfg, batch, max_len, dtype)
        return {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                               dtype),
                "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                               dtype)}
    nd = cfg.first_dense_layers
    n_moe = (cfg.n_layers - nd) if cfg.n_experts else 0
    n_dense = cfg.n_layers - n_moe
    caches = {}
    if n_dense:
        caches["dense_blocks"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_dense,) + a.shape).copy(), one(0))
    if n_moe:
        caches["moe_blocks"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_moe,) + a.shape).copy(), one(0))
    return caches


def prefill(params, cfg, tokens, positions=None):
    """Sequence-level prefill: ONE jitted forward through the fused sdpa
    route that returns both the logits and every layer's cache entries —
    replacing the legacy O(P) sequential ``decode_step`` prompt loop.

    tokens: (B, P) int32 (right-pad prompts; causal masking keeps padded
    tails from influencing earlier positions).  Returns ``(logits
    (B, P, V), kv)`` where ``kv`` mirrors the :func:`init_cache` tree with
    leaves (n_layers, B, P, ...) — callers place them into a dense cache
    (``launch/serve.py``) or scatter them into pages (``serving/engine``).
    """
    B, P = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None],
                                     (B, P))
    x = embed(params, tokens, cfg)
    windows = layer_windows(cfg, cfg.n_layers)
    nd = cfg.first_dense_layers
    n_moe = (cfg.n_layers - nd) if cfg.n_experts else 0
    n_dense = cfg.n_layers - n_moe
    kv = {}
    if n_dense:
        x, _, kvs = stack_prefill(params["dense_blocks"], x, cfg, positions,
                                  windows[:n_dense], moe=False)
        kv["dense_blocks"] = kvs
    if n_moe:
        x, _, kvs = stack_prefill(params["moe_blocks"], x, cfg, positions,
                                  windows[n_dense:], moe=True)
        kv["moe_blocks"] = kvs
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return unembed_logits(params, x, cfg), kv


def prefill_chunk(params, cfg, cache, tokens, start):
    """One chunk of a chunked prefill: advance every layer's dense scratch
    cache by ``tokens`` (B, C) at absolute positions ``start .. start+C``
    and return the chunk's logits.

    ``cache`` is an :func:`init_cache` tree (leaves (nL, B, T, ...), f32
    for exact parity) holding every earlier chunk's K/V — and, on a
    prefix-cache hit, the gathered shared pages.  Returns ``(logits
    (B, C, V), new_cache)``.  Running all chunks then matches the
    monolithic :func:`prefill` row-for-row (the serving engine's
    chunked-prefill parity contract)."""
    B, C = tokens.shape
    x = embed(params, tokens, cfg)
    windows = layer_windows(cfg, cfg.n_layers)
    nd = cfg.first_dense_layers
    n_moe = (cfg.n_layers - nd) if cfg.n_experts else 0
    n_dense = cfg.n_layers - n_moe
    new_cache = {}
    if n_dense:
        x, nc = stack_chunk(params["dense_blocks"], x, cfg,
                            cache["dense_blocks"], start,
                            windows[:n_dense], moe=False)
        new_cache["dense_blocks"] = nc
    if n_moe:
        x, nc = stack_chunk(params["moe_blocks"], x, cfg,
                            cache["moe_blocks"], start,
                            windows[n_dense:], moe=True)
        new_cache["moe_blocks"] = nc
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return unembed_logits(params, x, cfg), new_cache


def init_paged_cache(cfg, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16):
    """Paged KV cache: the :func:`init_cache` tree with the dense (B, T)
    token axis replaced by a (num_pages, page_size) page pool shared
    across sequences (per-sequence block tables live in the serving
    engine).  Page 0 is the engine's scrap page — inactive slots write
    into it.  Attention K/V pools are head-major, ``(NP, Hkv, ps, hd)``,
    so one page of one head is a ``(ps, hd)`` tile the paged kernel
    fetches whole."""
    def one(_):
        if cfg.use_mla:
            return {"c_kv": jnp.zeros((num_pages, page_size,
                                       cfg.kv_lora_rank), dtype),
                    "k_rope": jnp.zeros((num_pages, page_size,
                                         cfg.qk_rope_dim), dtype)}
        return {"k": jnp.zeros((num_pages, cfg.n_kv_heads, page_size,
                                cfg.head_dim), dtype),
                "v": jnp.zeros((num_pages, cfg.n_kv_heads, page_size,
                                cfg.head_dim), dtype)}
    nd = cfg.first_dense_layers
    n_moe = (cfg.n_layers - nd) if cfg.n_experts else 0
    n_dense = cfg.n_layers - n_moe
    pools = {}
    if n_dense:
        pools["dense_blocks"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_dense,) + a.shape).copy(), one(0))
    if n_moe:
        pools["moe_blocks"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_moe,) + a.shape).copy(), one(0))
    return pools


def decode_step_paged(params, cfg, pools, block_tables, lengths, tokens):
    """One decode step against the paged cache with per-slot lengths.

    tokens: (B,) int32 — one token per sequence slot; block_tables: (B,
    maxp) i32; lengths: (B,) i32 tokens already cached per slot (the
    current token's position — slots at unequal depths decode together,
    which is what continuous batching is).  Returns (logits (B, V),
    new_pools)."""
    x = embed(params, tokens[:, None], cfg)
    windows = layer_windows(cfg, cfg.n_layers)
    nd = cfg.first_dense_layers
    n_moe = (cfg.n_layers - nd) if cfg.n_experts else 0
    n_dense = cfg.n_layers - n_moe
    new_pools = {}
    if n_dense:
        x, npool = stack_decode_paged(params["dense_blocks"], x, cfg,
                                      pools["dense_blocks"], block_tables,
                                      lengths, windows[:n_dense], moe=False)
        new_pools["dense_blocks"] = npool
    if n_moe:
        x, npool = stack_decode_paged(params["moe_blocks"], x, cfg,
                                      pools["moe_blocks"], block_tables,
                                      lengths, windows[n_dense:], moe=True)
        new_pools["moe_blocks"] = npool
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = unembed_logits(params, x, cfg)
    return logits[:, 0], new_pools


def decode_step(params, cfg, cache, tokens, cache_index):
    """One decode step. tokens: (B,) int32; returns (logits, new_cache)."""
    B = tokens.shape[0]
    x = embed(params, tokens[:, None], cfg)
    windows = layer_windows(cfg, cfg.n_layers)
    nd = cfg.first_dense_layers
    n_moe = (cfg.n_layers - nd) if cfg.n_experts else 0
    n_dense = cfg.n_layers - n_moe
    new_cache = {}
    if n_dense:
        x, nc = stack_decode(params["dense_blocks"], x, cfg,
                             cache["dense_blocks"], cache_index,
                             windows[:n_dense], moe=False)
        new_cache["dense_blocks"] = nc
    if n_moe:
        x, nc = stack_decode(params["moe_blocks"], x, cfg,
                             cache["moe_blocks"], cache_index,
                             windows[n_dense:], moe=True)
        new_cache["moe_blocks"] = nc
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = unembed_logits(params, x, cfg)
    return logits[:, 0], new_cache


def forward_logits(params, batch, cfg):
    """Prefill entry: logits only (serving-side forward)."""
    return forward(params, batch, cfg)[0]
