"""Serving CLI + back-compat ``generate`` over the continuous-batching
engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --batch 4 --prompt-len 16 --gen 32 --temperature 0.8 --top-k 40

Two code paths, one contract:

  * :func:`generate` — the legacy batch API ``(B, P) -> (B, gen_len)``,
    now a thin wrapper over :class:`repro.serving.Engine` (paged KV cache,
    single-shot jitted prefill) for the KV-cache families; its greedy
    output is token-identical to :func:`generate_dense` on smoke configs
    (asserted by tests and the serving benchmark's ``--smoke`` gate).
  * :func:`generate_dense` — the dense-cache reference loop, kept as the
    engine's verification oracle and as the fallback for families without
    a paged decode path (SSM/hybrid/enc-dec/VLM).  Its prompt prefill is
    ONE jitted sequence-level forward (``model.prefill``) where the family
    supports it — not P sequential decode steps.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import numerics
from repro.configs import get_config, get_smoke_config, list_archs
from repro.models import get_model


def fill_dense_cache(cache, kv):
    """Place a sequence-level prefill's K/V (leaves (nL, B, P, ...)) into
    a dense cache tree (leaves (nL, B, max_len, ...))."""
    return jax.tree.map(
        lambda c, k: jax.lax.dynamic_update_slice(
            c, k.astype(c.dtype), (0,) * c.ndim),
        cache, kv)


def generate_dense(cfg, params, prompts, gen_len: int, greedy=True, seed=0):
    """Dense-cache reference: batch of same-length prompts, fixed
    ``gen_len``.  Prefill is one jitted forward when the family supports
    it (KV-cache families), else the legacy decode-step loop."""
    model = get_model(cfg)
    B, P = prompts.shape
    max_len = P + gen_len + 1
    cache = model.init_cache(B, max_len)
    step = jax.jit(model.decode_step)

    if model.prefill is not None:
        logits_all, kv = jax.jit(lambda p, t: model.prefill(p, t))(
            params, prompts)
        cache = fill_dense_cache(cache, kv)
        logits = logits_all[:, -1]
    else:
        logits = None
        for i in range(P):
            logits, cache = step(params, cache, prompts[:, i], i)
    out = []
    key = jax.random.PRNGKey(seed)
    for i in range(gen_len):
        if greedy:
            tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
        else:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits[:, :cfg.vocab_size])
        out.append(tok)
        logits, cache = step(params, cache, tok.astype(jnp.int32), P + i)
    return jnp.stack(out, axis=1)


def generate(cfg, params, prompts, gen_len: int, greedy=True, seed=0):
    """Back-compat batch API: prompts (B, P) int32 -> (B, gen_len).

    Routes through the continuous-batching engine (paged KV cache,
    single-shot prefill) for the KV-cache families; greedy output stays
    token-identical to :func:`generate_dense`.  Families without a paged
    decode path fall back to the dense loop unchanged."""
    from repro.serving import DEFAULT_PAGE_SIZE, Engine, SamplingParams
    model = get_model(cfg)
    if model.decode_step_paged is None:
        return generate_dense(cfg, params, prompts, gen_len, greedy, seed)
    B, P = prompts.shape
    ps = DEFAULT_PAGE_SIZE
    pages_per_seq = -(-(P + gen_len + 1) // ps)
    engine = Engine(cfg, params, max_slots=B,
                    num_pages=1 + B * pages_per_seq, page_size=ps,
                    max_pages_per_slot=pages_per_seq)
    sps = [SamplingParams(temperature=0.0 if greedy else 1.0,
                          max_tokens=gen_len, seed=seed + i)
           for i in range(B)]
    rids = [engine.add_request(np.asarray(prompts[i]), sps[i])
            for i in range(B)]
    out = engine.run()
    return jnp.asarray(np.stack([out[r] for r in rids]), jnp.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--policy", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy (per-request; engine families only)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--max-slots", type=int, default=0,
                    help="decode batch width (0 = --batch): smaller forces "
                         "queueing, exercising continuous batching")
    ap.add_argument("--max-waiting", type=int, default=0,
                    help="bound the waiting queue: requests past it are "
                         "rejected with EngineOverloaded (0 = unbounded)")
    ap.add_argument("--deadline", type=int, default=0,
                    help="per-request deadline in engine steps; expired "
                         "requests finish with reason=timeout (0 = none)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share prompt-prefix KV pages copy-on-write "
                         "across requests (docs/serving.md)")
    ap.add_argument("--chunked-prefill", type=int, default=0, metavar="C",
                    help="prefill prompts in C-token chunks interleaved "
                         "with decode steps (0 = single-shot)")
    ap.add_argument("--async-sched", action="store_true",
                    help="overlap host scheduling with the in-flight "
                         "decode step (block only at consume)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="make the first N prompt tokens identical across "
                         "the batch (exercises the prefix cache)")
    ap.add_argument("--mesh-model", type=int, default=0, metavar="N",
                    help="install a (devices/N, N) (data, model) host mesh: "
                         "the engine shards its page pools (KV heads on "
                         "the model axis) and the paged decode kernel runs "
                         "per shard via shard_map (0 = no mesh; see "
                         "docs/parallel.md)")
    numerics.add_cli_overrides(ap)
    from repro import obs
    obs.add_cli_flags(ap)
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()

    import contextlib
    mesh_scope = contextlib.nullcontext()
    if args.mesh_model:
        from repro.launch.mesh import make_host_mesh
        from repro.parallel import ctx
        mesh = make_host_mesh(model=args.mesh_model)
        print(f"mesh: {dict(mesh.shape)}", flush=True)
        mesh_scope = ctx.use_mesh(mesh)
    with numerics.cli_context(args), mesh_scope, obs.cli_session(args):
        _main(args)


def _main(args):
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    if args.policy:
        cfg = cfg.replace(policy=args.policy)
    if cfg.family in ("vlm", "audio"):
        print("note: serving CLI drives the LM/decoder path of this arch")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts_np = rng.integers(0, cfg.vocab_size,
                              (args.batch, args.prompt_len))
    if args.shared_prefix:
        n = min(args.shared_prefix, args.prompt_len)
        prompts_np[:, :n] = prompts_np[0, :n]
    prompts = jnp.asarray(prompts_np, jnp.int32)

    if model.decode_step_paged is None:
        t0 = time.time()
        out = generate(cfg, params, prompts, args.gen,
                       greedy=args.temperature <= 0)
        dt = time.time() - t0
        print(f"generated {out.shape} in {dt:.2f}s "
              f"({args.batch * args.gen / dt:.1f} tok/s incl. compile)")
        print("sample:", np.asarray(out[0][:16]))
        return

    from repro.serving import (DEFAULT_PAGE_SIZE, Engine, EngineOverloaded,
                               SamplingParams)
    ps = DEFAULT_PAGE_SIZE
    pages = -(-(args.prompt_len + args.gen + 1) // ps)
    slots = args.max_slots or args.batch
    nc = numerics.active()
    if args.prefix_cache or args.chunked_prefill or args.async_sched:
        nc = nc.replace(
            prefix_cache=bool(args.prefix_cache) or nc.prefix_cache,
            chunked_prefill=args.chunked_prefill or nc.chunked_prefill,
            async_sched=bool(args.async_sched) or nc.async_sched)
    engine = Engine(cfg, params, max_slots=slots,
                    num_pages=1 + max(slots, args.batch) * pages,
                    page_size=ps, max_pages_per_slot=pages,
                    max_waiting=args.max_waiting or None,
                    numerics_config=nc)
    t0 = time.time()
    rids = []
    for i in range(args.batch):
        try:
            rids.append(engine.add_request(
                np.asarray(prompts[i]),
                SamplingParams(temperature=args.temperature,
                               top_k=args.top_k, top_p=args.top_p,
                               max_tokens=args.gen, seed=i),
                deadline=args.deadline or None))
        except EngineOverloaded:
            print(f"request {i}: rejected (overloaded — queue at "
                  f"{args.max_waiting})")
    out = engine.run()
    dt = time.time() - t0
    toks = sum(len(v) for v in out.values())
    reasons: dict[str, int] = {}
    for v in out.values():
        reasons[v.finish_reason or "?"] = reasons.get(v.finish_reason
                                                      or "?", 0) + 1
    print(f"engine: {args.batch} requests, {slots} slots, "
          f"{engine.n_prefills} prefills, {engine.n_decode_steps} decode "
          f"steps -> {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s incl. compile)")
    print(f"finish reasons: {reasons}")
    stats = engine.stats()
    resilience = {k: stats[k] for k in
                  ("guard_trips", "fallback_reruns", "rejections",
                   "overloads", "timeouts", "preemptions", "parks")}
    print(f"resilience: {resilience}")
    prefix = {k: stats[k] for k in
              ("prefix_hits", "prefix_tokens_reused", "cow_splits",
               "prefix_evictions", "prefill_chunks")}
    print(f"prefix: {prefix}")
    if rids:
        print("sample:", out[rids[0]][:16])


if __name__ == "__main__":
    main()
