"""Plain float32 reference of the Qwen2/Qwen3 dense decoder.

Follows the published description (hf:Qwen/Qwen3-0.6B, Qwen2.5
technical report): RMSNorm before attention and MLP, per-head RMSNorm on
q and k (Qwen3), q/k/v biases (Qwen2), rotary embedding on the two halves
of each head, grouped-query causal softmax attention scaled by
``1/sqrt(head_dim)``, SwiGLU MLP, a final RMSNorm and the LM head.  It
imports nothing of the program and reads weights by their published
names (``chipbench.weights.hf_view``).

It runs one sequence at a time and one layer at a time, in ``jax.numpy``
with every contraction at an explicit precision, so that it fits beside
nothing else on one chip at any length the traffic sends.  ``precision``
and ``kv_dtype`` exist for the control: ``"high"`` (three bf16 passes)
and a bfloat16 K/V are the two steps below what the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512          # query rows per attention block; sequences pad to it
ROW_BLOCK = 64         # logit rows are computed in multiples of this
PRECISIONS = {"highest": jax.lax.Precision.HIGHEST,
              "high": jax.lax.Precision.HIGH}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta: float):
    """x: (S, H, d); pos: (S,) int32 positions.  In float32 throughout, by
    the published formula: inverse frequencies ``1 / theta ** (i / half)``,
    angles position x frequency, then cos and sin, all computed on the
    device at run time, as a server computes them.  (From a constant
    exponent table XLA would fold the frequencies on the host at compile
    time; on a TPU v5e the device's pow is up to 50 ulp off the host's
    correctly rounded one, which turns the angle at position 4096 by up
    to 3e-3 rad and would swamp what the comparison is for.)"""
    _, _, d = x.shape
    half = d // 2
    inv = 1.0 / (jnp.float32(theta)
                 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("hf", "precision", "kv_dtype"))
def _layer(h, w, pos, *, hf, precision, kv_dtype):
    """One decoder layer over a whole (padded) sequence.  ``hf`` is the
    configuration as a sorted tuple of items.  Returns (h, k, v) with k
    after its norm and rotary embedding, as a K/V cache holds it."""
    c = dict(hf)
    prec = PRECISIONS[precision]
    dot = functools.partial(jnp.matmul, precision=prec)
    S = h.shape[0]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim", c["hidden_size"] // H)
    eps = c["rms_norm_eps"]
    x = _rms(h, w["input_layernorm"], eps)
    q, k, v = dot(x, w["q_proj"]), dot(x, w["k_proj"]), dot(x, w["v_proj"])
    if "q_bias" in w:
        q, k, v = q + w["q_bias"], k + w["k_bias"], v + w["v_bias"]
    q, k, v = (q.reshape(S, H, hd), k.reshape(S, Hkv, hd),
               v.reshape(S, Hkv, hd))
    if "q_norm" in w:
        q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
    # the K/V the attention reads, in the cache's type
    kc = k.astype(kv_dtype).astype(jnp.float32)
    vc = v.astype(kv_dtype).astype(jnp.float32)
    rep = H // Hkv
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, Hkv, rep, hd)
    kpos = jnp.arange(S)

    def block(args):
        qb, i = args
        s = jnp.einsum("qgrd,kgd->grqk", qb, kc, precision=prec)
        s = s / np.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, vc, precision=prec)

    o = jax.lax.map(block, (qg, jnp.arange(S // Q_BLOCK)))
    h = h + dot(o.reshape(S, H * hd), w["o_proj"])
    x = _rms(h, w["post_attention_layernorm"], eps)
    m = jax.nn.silu(dot(x, w["gate_proj"])) * dot(x, w["up_proj"])
    return h + dot(m, w["down_proj"]), k, v


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(h, norm, lm_head, *, eps, precision):
    x = _rms(h, norm, eps)
    return jnp.matmul(x, lm_head, precision=PRECISIONS[precision])


def forward(w: dict, hf: dict, tokens, logit_positions, *,
            precision: str = "highest", kv_dtype=jnp.float32,
            on_kv=None):
    """Run the reference over one sequence.

    w: published-name weights (layers stacked on axis 0); hf: the
    published configuration; tokens: (S,) ids; logit_positions: the
    positions whose next-token logits are wanted.  ``on_kv(layer, k, v)``
    is called with each layer's (S, Hkv, hd) K and V (K after its norm
    and rotary embedding).  Returns the logits, (len(positions), V)."""
    tokens = np.asarray(tokens, np.int32)
    S = len(tokens)
    Sp = -(-S // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((Sp,), np.int32)
    toks[:S] = tokens
    key = tuple(sorted((k, v) for k, v in hf.items()
                       if isinstance(v, (int, float, str, bool))))
    h = jnp.take(w["embed_tokens"], jnp.asarray(toks), axis=0)
    pos = jnp.asarray(np.arange(Sp, dtype=np.int32))
    layer_names = [n for n in w if n not in ("embed_tokens", "norm",
                                             "lm_head")]
    for i in range(hf["num_hidden_layers"]):
        wl = {n: w[n][i] for n in layer_names}
        h, k, v = _layer(h, wl, pos, hf=key, precision=precision,
                         kv_dtype=jnp.dtype(kv_dtype).name)
        if on_kv is not None:
            on_kv(i, k[:S], v[:S])
    pos = np.asarray(logit_positions, np.int32)
    n = len(pos)
    padded = np.full((-(-n // ROW_BLOCK) * ROW_BLOCK,), pos[-1], np.int32)
    padded[:n] = pos
    rows = h[jnp.asarray(padded)]
    return _head(rows, w["norm"], w["lm_head"], eps=hf["rms_norm_eps"],
                 precision=precision)[:n]
