"""Operations and bytes, computed from shapes.

These are the float32 model's operations, one contraction each, and the
bytes a float32 kernel must move at least once, whatever passes or splits
implement them: a policy that runs six bf16 passes does the same work as
one that runs three, and its share of the roofline says how far it is
from what the chip could do.

Model work (:func:`token_flops`, :func:`head_flops`) counts, per token
processed, 2 x the non-embedding matmul parameters and the causal
attention's 4 x context x heads x head_dim per layer; the LM head is
counted only for the positions whose logits the result uses (the last
prompt position and every decoded token).
"""
from __future__ import annotations

import dataclasses

F32 = 4


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int          # the vocabulary the head computes (padded)

    @classmethod
    def of(cls, cfg) -> "Dims":
        return cls(cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                   cfg.head_dim, cfg.d_ff, cfg.padded_vocab)

    @property
    def layer_matmul_params(self) -> int:
        D, H, G, d, F = (self.d_model, self.heads, self.kv_heads,
                         self.head_dim, self.d_ff)
        return D * H * d + 2 * D * G * d + H * d * D + 3 * D * F


# ---------------------------------------------------------------- model

def token_flops(dims: Dims, ctx: int) -> float:
    """One token through every layer, attending to ``ctx`` keys."""
    return dims.layers * (2.0 * dims.layer_matmul_params
                          + 4.0 * ctx * dims.heads * dims.head_dim)


def span_flops(dims: Dims, lo: int, hi: int) -> float:
    """Tokens at positions ``lo .. hi-1`` of one sequence, causal."""
    n = hi - lo
    if n <= 0:
        return 0.0
    ctx_sum = (hi * (hi + 1) - lo * (lo + 1)) // 2
    return dims.layers * (2.0 * dims.layer_matmul_params * n
                          + 4.0 * ctx_sum * dims.heads * dims.head_dim)


def head_flops(dims: Dims, vocab: int) -> float:
    return 2.0 * dims.d_model * vocab


def step_flops(dims: Dims, vocab: int, step: dict) -> float:
    """Model work of one engine step from its record: prefill chunks
    ``(plen, lo, hi)`` (padding past ``plen`` is not work) and new tokens
    ``(plen, j)``, the ``j``-th served token of a request, which for
    ``j >= 1`` was decoded at context ``plen + j``."""
    total = 0.0
    for plen, lo, hi in step["chunk_spans"]:
        total += span_flops(dims, lo, min(hi, plen))
    for plen, j in step["tokens"]:
        total += head_flops(dims, vocab)
        if j >= 1:
            total += token_flops(dims, plen + j)
    return total


# -------------------------------------------------------------- kernels

def gemm(M: int, N: int, K: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one ``(M, K) @ (K, N)`` product: f32 A and B read
    once, C written once."""
    b = F32 * (M * K + K * N + M * N)
    return 2.0 * M * N * K, float(b)


def flash(S: int, T: int, heads: int, kv_heads: int, head_dim: int,
          q_start: int = 0) -> tuple[float, float]:
    """(FLOPs, bytes) of causal attention for ``S`` queries at positions
    ``q_start ..`` over ``T`` keys: the unmasked QK and PV products, and
    f32 Q, K, V and O once."""
    keys = 0
    for p in range(q_start, q_start + S):
        keys += min(p + 1, T)
    flops = 4.0 * keys * heads * head_dim
    b = F32 * (2 * S * heads * head_dim + 2 * T * kv_heads * head_dim)
    return flops, float(b)


def paged(contexts, heads: int, kv_heads: int,
          head_dim: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode-attention call over live slots with
    ``contexts`` keys each, from a float32 page pool: K and V of every
    live position once, the query and output once."""
    keys = sum(contexts)
    flops = 4.0 * keys * heads * head_dim
    b = F32 * (2 * keys * kv_heads * head_dim
               + 2 * len(contexts) * heads * head_dim)
    return flops, float(b)


def per_chip(calls, chips: int) -> list:
    """The kernel events that ``calls`` make where ``chips`` chips share
    every layer: each call is one event on each chip, with a ``1/chips``
    share of the call's operations and bytes.  An even split is the
    model's own work over the chips, whichever of a product's dimensions
    the program shards."""
    return [(flops / chips, nbytes / chips)
            for flops, nbytes in calls for _ in range(chips)]


def step_kernel_calls(dims: Dims, step: dict, chunk: int) -> dict:
    """Per kernel, the ``(flops, bytes)`` of every call the fused kernels
    make in one engine step.  Each prefill chunk runs its seven
    projections per layer and the head as fused GEMMs with M = the chunk,
    and one flash call per layer over the request's scratch (the prompt
    rounded up to whole chunks); the decode step runs one paged call per
    layer over the live slots.  Decode projections (M = live slots, under
    the kernel's 128) and everything else run outside the kernels."""
    D, H, G, d, F = (dims.d_model, dims.heads, dims.kv_heads, dims.head_dim,
                     dims.d_ff)
    out = {"matmul": [], "flash": [], "paged": []}
    for plen, lo, hi in step["chunk_spans"]:
        T = -(-plen // chunk) * chunk
        for c0 in range(lo, hi, chunk):
            for N, K in ((H * d, D), (G * d, D), (G * d, D), (D, H * d),
                         (F, D), (F, D), (D, F)):
                out["matmul"] += [gemm(chunk, N, K)] * dims.layers
            out["matmul"].append(gemm(chunk, dims.vocab, D))
            out["flash"] += [flash(chunk, T, H, G, d, c0)] * dims.layers
    ctxs = [plen + j for plen, j in step["tokens"] if j >= 1]
    if ctxs:
        out["paged"] += [paged(ctxs, H, G, d)] * dims.layers
    return out
