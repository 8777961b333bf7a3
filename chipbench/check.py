"""The comparison that decides ``correct``.

After the window closes, the run hands over what the timed path
produced: the tokens each sampled request was served, and the K/V cache
pages of requests still resident in the engine.  The plain reference of
the configuration (``references/<name>.py``) then runs over each
sampled prompt with its served tokens, in float32 at the highest
precision, and the numbers below are compared with their limits
(``limits/<cell>.json``, set from the readings ``PERF.md`` gives):

- ``token_gap``: the widest gap by which a served token's reference logit
  lies below the reference's best logit at that position.  Every request
  is greedy, so a sound server serves the reference's best token except
  where the two best are tied to rounding;
- ``kv_rel_err``: for the resident requests, the largest over layers and
  over K and V of ``||cache - ref|| / ||ref||`` over every cached
  position.  Prefill (fused GEMM, flash attention) wrote the prompt's
  entries; the decode projections and paged attention wrote the rest;
- ``errored``: requests that finished with an error (limit 0).
"""
from __future__ import annotations

import numpy as np


def sample_finished(done: list, n: int, seed: int) -> list:
    """``n`` finished requests drawn from the seed, with the one that was
    served the most tokens among them."""
    if not done or n <= 0:
        return []
    longest = max(done, key=lambda r: len(r["out"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 7])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def resident_record(engine, req) -> dict:
    """A resident request's prompt, served tokens and a device copy of its
    cached K/V, ``(nL, n, Hkv, hd)`` each, for the first ``n`` tokens of
    prompt + served (all but the newest served token)."""
    import jax.numpy as jnp
    pools = engine.pools["dense_blocks"]
    ps = engine.pool.page_size
    n = int(engine.lengths[req.slot])
    pages = jnp.asarray(req.pages[: -(-n // ps)], jnp.int32)
    kv = {}
    for name in ("k", "v"):
        g = jnp.swapaxes(pools[name][:, pages], 2, 3)  # (nL, np, ps, Hkv, hd)
        kv[name] = g.reshape(g.shape[0], -1, g.shape[3], g.shape[4])[:, :n]
    if n != len(req.prompt) + len(req.out) - 1:
        raise RuntimeError(f"request {req.rid} caches {n} tokens, not its "
                           "prompt and all but its newest served token")
    return {"prompt": list(req.prompt), "out": list(req.out), "kv": kv}


def _rel(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


def compare(ref_mod, w, hf: dict, sample: list, *,
            control: dict | None = None) -> dict:
    """Run the reference over the sample and read the numbers.

    Each entry of ``sample`` holds ``prompt`` and ``out`` (served
    tokens); resident ones also ``kv``.  With ``control``
    (``{"precision": ..., "kv_dtype": ...}``) the reference in that
    precision stands in the program's place: its best token at each
    position is taken as served, and its K/V, stored in ``kv_dtype``, as
    cached."""
    import jax.numpy as jnp
    gaps, kv_errs, v_errs, first, n_tok = [], [], [], [], 0
    for r in sample:
        plen, out = len(r["prompt"]), list(r["out"])
        if not out:
            continue
        seq = list(r["prompt"]) + out[:-1]
        pos = np.arange(plen - 1, plen - 1 + len(out))
        ref_kv = {}
        logits = ref_mod.forward(
            w, hf, seq, pos,
            on_kv=(lambda i, k, v: ref_kv.__setitem__(i, (k, v)))
            if "kv" in r else None)
        served = np.asarray(out)
        cache = None
        if control is not None:
            ctl_kv = {}
            c_logits = ref_mod.forward(
                w, hf, seq, pos, precision=control["precision"],
                kv_dtype=control["kv_dtype"],
                on_kv=(lambda i, k, v: ctl_kv.__setitem__(i, (k, v)))
                if "kv" in r else None)
            served = np.asarray(jnp.argmax(c_logits, -1))
            if "kv" in r:
                dt = control["kv_dtype"]
                cache = {i: tuple(x.astype(dt).astype(jnp.float32)
                                  for x in kv) for i, kv in ctl_kv.items()}
        elif "kv" in r:
            cache = {i: (r["kv"]["k"][i], r["kv"]["v"][i])
                     for i in range(len(ref_kv))}
        lg = np.asarray(logits, np.float64)
        gaps.append(float(np.max(lg.max(-1)
                                 - lg[np.arange(len(served)), served])))
        n_tok += len(served)
        if cache is not None:
            for i, (k, v) in ref_kv.items():
                ck, cv = cache[i]
                ek, ev = _rel(ck, k), _rel(cv, v)
                kv_errs.append(max(ek, ev))
                v_errs.append(ev)
                if i == 0:
                    first.append((ek, ev))
    return {"token_gap": max(gaps) if gaps else None,
            "kv_rel_err": max(kv_errs) if kv_errs else None,
            "tokens_compared": n_tok, "sequences": len(gaps),
            # not compared: V alone, and layer 0's K (after rotary) and V
            "v_rel_err": max(v_errs) if v_errs else None,
            "layer0_k_v": [max(x) for x in zip(*first)] if first else None}
