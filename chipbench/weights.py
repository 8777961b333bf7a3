"""Random weights from ``--seed``, made on the device in one jitted call.

The leaves take the program's parameter layout (the tree that
``repro.models.get_model(cfg).init`` returns: projections ``(D, H, hd)``,
layers stacked on a leading axis, RMSNorm gains stored as ``scale`` with
the norm applying ``1 + scale``), in float32, the type they are served
in.  :func:`hf_view` maps the same arrays onto the names and meaning of
the published model, which is what the plain reference reads.

Scales: matrices ``N(0, 1/fan_in)``; the embedding ``N(0, 0.02**2)``;
norm gains ``1 + N(0, 0.1**2)`` and biases ``N(0, 0.02**2)``, so that a
path that dropped a gain or a bias would change the result.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from a seed of any size (more than 32 bits hold)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _scale(path: str, shape, cfg) -> float:
    name = path.rsplit("/", 1)[-1]
    if name in ("ln1", "ln2", "ln_f", "q_norm", "k_norm"):
        return 0.1
    if name in ("bq", "bk", "bv", "embed"):
        return 0.02
    fan_in = {"wq": cfg.d_model, "wk": cfg.d_model, "wv": cfg.d_model,
              "wo": cfg.n_heads * cfg.head_dim, "w_gate": cfg.d_model,
              "w_up": cfg.d_model, "w_down": cfg.d_ff,
              "unembed": cfg.d_model}[name]
    return 1.0 / np.sqrt(fan_in)


def make_params(cfg, seed: int, shardings=None):
    """The program-layout weight tree for ``cfg``, drawn from ``seed``."""
    from repro.models import get_model
    shapes = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    leaves = [s for _, s in flat]

    def build(key):
        out = []
        for i, (path, s) in enumerate(zip(paths, leaves)):
            x = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  jnp.float32)
            out.append((x * _scale(path, s.shape, cfg)).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(build, out_shardings=shardings)
    return fn(seed_key(seed))


def hf_view(params, cfg) -> dict:
    """The same arrays under the published model's names and meaning:
    per layer ``q_proj (D, H*hd)`` and so on, norm weights ``1 + scale``,
    the LM head ``(D, V)`` over the published vocabulary."""
    blocks = params["dense_blocks"]
    a, m = blocks["attn"], blocks["mlp"]
    L, D = cfg.n_layers, cfg.d_model
    out = {
        "embed_tokens": params["embed"][:cfg.vocab_size],
        "norm": 1.0 + params["ln_f"],
        "input_layernorm": 1.0 + blocks["ln1"],
        "post_attention_layernorm": 1.0 + blocks["ln2"],
        "q_proj": a["wq"].reshape(L, D, -1),
        "k_proj": a["wk"].reshape(L, D, -1),
        "v_proj": a["wv"].reshape(L, D, -1),
        "o_proj": a["wo"].reshape(L, -1, D),
        "gate_proj": m["w_gate"], "up_proj": m["w_up"],
        "down_proj": m["w_down"],
    }
    if "q_norm" in a:
        out["q_norm"] = 1.0 + a["q_norm"]
        out["k_norm"] = 1.0 + a["k_norm"]
    if "bq" in a:
        out["q_bias"] = a["bq"].reshape(L, -1)
        out["k_bias"] = a["bk"].reshape(L, -1)
        out["v_bias"] = a["bv"].reshape(L, -1)
    out["lm_head"] = (out["embed_tokens"].T if cfg.tie_embeddings
                      else params["unembed"][:, :cfg.vocab_size])
    return out
