"""Serving throughput: continuous batching + paged KV cache vs the dense
legacy loop.

  PYTHONPATH=src python -m benchmarks.serving_throughput [--smoke]
  PYTHONPATH=src python -m benchmarks.run serving          # smoke mode

Two claims, measured:

  1. **throughput scales with in-flight requests** — the engine decodes
     every resident slot in one jitted step, so tok/s grows with slot
     count while the dense path pays a full same-length batch or nothing;
  2. **parity is free** — with the paged kernel hatch closed, greedy
     engine output is token-identical to the dense reference (the
     ``--smoke`` gate CI runs), and the interpret-mode paged kernel agrees
     with the engine's gather fallback.

Numbers on CPU are for *shape* (scaling trend), not speed — kernels run
interpreted off-TPU.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.models import get_model
from .common import emit, record, record_timed, timed

ARCH = "qwen3-0.6b"


def _requests(cfg, n, seed=0, mixed=True):
    rng = np.random.default_rng(seed)
    lens = (rng.integers(4, 17, n) if mixed else np.full(n, 8))
    return [rng.integers(0, cfg.vocab_size, int(l)) for l in lens]


def _engine_run(cfg, params, prompts, max_slots, max_tokens=8,
                reps=1, warmup=0):
    """Run a fresh engine over ``prompts``; per-rep wall times come from
    the blocking timer (greedy + per-request seeds, so every rep yields
    identical tokens)."""
    from repro.serving import Engine, SamplingParams

    def once():
        engine = Engine(cfg, params, max_slots=max_slots,
                        num_pages=1 + 8 * len(prompts), page_size=8)
        for i, p in enumerate(prompts):
            engine.add_request(p, SamplingParams(max_tokens=max_tokens,
                                                 seed=i))
        return engine.run(), engine

    (out, engine), dt, samples = timed(once, reps=reps, warmup=warmup)
    toks = sum(len(v) for v in out.values())
    return out, toks, dt, samples, engine


def run(smoke: bool = False) -> bool:
    cfg = get_smoke_config(ARCH)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    gen = 8
    ok = True

    # ---- parity gate: greedy engine == dense reference ------------------
    from repro.launch.serve import generate, generate_dense
    prompts_same = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 8)),
        jnp.int32)
    dense = np.asarray(generate_dense(cfg, params, prompts_same, gen))
    eng = np.asarray(generate(cfg, params, prompts_same, gen))
    parity = bool(np.array_equal(dense, eng))
    ok &= parity

    # mixed-length continuous batching vs per-request dense; the timed
    # reps (post-compile) double as the smoke throughput metric
    mixed = _requests(cfg, 3, seed=1)
    out, toks, _, samples, engine = _engine_run(
        cfg, params, mixed, max_slots=2, max_tokens=gen, reps=2, warmup=1)
    record_timed("serving/smoke/tok_per_s", samples, unit="tok/s",
                 higher_is_better=True, transform=lambda s: toks / s)
    mixed_parity = True
    for rid, p in zip(sorted(out), mixed):
        ref = np.asarray(generate_dense(
            cfg, params, jnp.asarray(p, jnp.int32)[None], gen))[0]
        mixed_parity &= bool(np.array_equal(ref, np.asarray(out[rid])))
    ok &= mixed_parity

    # interpret-mode paged kernel vs the engine's gather fallback
    from repro import tcec_paged_attention
    rng = np.random.default_rng(2)
    kp = jnp.asarray(rng.standard_normal((9, 2, 8, 64)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((9, 2, 8, 64)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((2, 8, 64)), jnp.float32)
    bt = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
    lens = jnp.asarray([13, 27], jnp.int32)
    kout = tcec_paged_attention(q, kp, vp, bt, lens, pages_per_step=2,
                                interpret=True)
    from repro.models.layers import _decode_attend
    class _C:
        attn_softcap = None
    kg = jnp.swapaxes(kp[bt], 2, 3).reshape(2, 32, 2, 64)   # head-major
    vg = jnp.swapaxes(vp[bt], 2, 3).reshape(2, 32, 2, 64)   # pools
    fb = _decode_attend(q[:, None], kg, vg, _C(), lens - 1, 0)[:, 0]
    kerr = float(jnp.max(jnp.abs(kout - fb)))
    kernel_ok = kerr < 5e-2
    ok &= kernel_ok

    record("serving/parity/dense", float(parity))
    record("serving/parity/mixed", float(mixed_parity))
    # deterministic in-process, but XLA-CPU reductions vary a little
    # across machines: 50% self-noise keeps the gate on >2.5x blowups
    record("serving/kernel/max_abs_err", kerr, unit="abs",
           higher_is_better=False, noise=0.5 * kerr)
    record("serving/mixed/prefills", engine.n_prefills, unit="count",
           higher_is_better=False)
    record("serving/mixed/decode_steps", engine.n_decode_steps,
           unit="count", higher_is_better=False)
    # resilience counters: all zero on a fault-free run, so a change that
    # starts tripping recovery paths in normal operation moves a gated
    # metric (docs/robustness.md)
    stats = engine.stats()
    for key in ("guard_trips", "fallback_reruns", "numerics_errors",
                "rejections", "overloads", "timeouts", "length_caps",
                "prefill_faults", "preemptions", "parks"):
        record(f"serving/resilience/{key}", float(stats[key]),
               unit="count", higher_is_better=False)
    for key in ("failures", "declined"):
        record(f"serving/resilience/breaker_{key}",
               float(stats["breaker"][key]), unit="count",
               higher_is_better=False)
    rows = [["greedy engine == dense generate (4x8+8)", str(parity)],
            ["mixed-length engine == per-request dense", str(mixed_parity)],
            [f"paged kernel vs gather fallback (max|d|={kerr:.1e})",
             str(kernel_ok)]]
    emit("serving_parity",
         "Serving parity gate — paged continuous batching vs dense legacy",
         ["check", "pass"], rows,
         f"{engine.n_prefills} prefills / {engine.n_decode_steps} decode "
         "steps for the mixed run (continuous batching, 3 requests on 2 "
         "slots)")

    # ---- request latency via tracing (repro.obs) ------------------------
    # One traced engine run feeds the serving/latency/* histograms; the
    # percentiles become gated wall-clock metrics (kind="measured", so the
    # twice-run determinism battery exempts them from bit-identity).  The
    # histograms are reset first: that battery runs this bench twice
    # in-process and the percentiles should describe THIS run.
    from repro import obs
    for name in ("ttft_s", "tpot_s", "queue_wait_s"):
        obs.metrics.histogram(f"serving/latency/{name}").reset()
    with obs.trace():
        _engine_run(cfg, params, mixed, max_slots=2, max_tokens=gen)
    ttft = obs.metrics.histogram("serving/latency/ttft_s")
    tpot = obs.metrics.histogram("serving/latency/tpot_s")
    qwait = obs.metrics.histogram("serving/latency/queue_wait_s")
    lat_ok = ttft.count() == len(mixed) and tpot.count() > 0
    ok &= lat_ok
    for label, hist, p in (("ttft_p50_s", ttft, 50), ("ttft_p99_s", ttft, 99),
                           ("tpot_p50_s", tpot, 50), ("tpot_p99_s", tpot, 99),
                           ("queue_wait_p50_s", qwait, 50)):
        record(f"serving/latency/{label}", hist.percentile(p), unit="s",
               kind="measured", higher_is_better=False)

    # ---- shared-prefix scenario: COW prefix cache on vs off -------------
    # Deterministic trace, mixed lengths, ~70% shared system prompt (the
    # docs/serving.md workload).  A primer request populates the prefix
    # tree, then a burst of 8 requests lands at once: cache-off recomputes
    # the 192 shared tokens per request in its batched monolithic
    # prefills, cache-on prefills only each novel tail against read-only
    # shared pages.  The cache-on run must be token-identical (f32
    # pools), score a nonzero hit-rate, and beat the cache-off TTFT p50.
    from repro import numerics
    from repro.serving import Engine, SamplingParams
    rng = np.random.default_rng(7)
    system = rng.integers(0, cfg.vocab_size, 192)      # shared system prompt
    tails = [rng.integers(0, cfg.vocab_size, int(n))
             for n in rng.choice([64, 96], 8)]         # mixed novel tails
    trace_prompts = [np.concatenate([system, t]) for t in tails]
    shared_frac = len(system) * len(trace_prompts) / sum(
        len(p) for p in trace_prompts)
    pgen = 4

    import time as _time

    def _prefix_run(on):
        # exact per-request TTFT (first burst token after the burst's
        # enqueue) via a manual step loop — the obs histogram's fixed
        # buckets are too coarse to resolve the prefill a hit skips.
        # One engine per mode: each Engine owns fresh jit wrappers, so
        # the first two bursts pay every compile and the third measures
        # warm steady-state (repeat traffic — hits land at plen-1 and
        # COW-split the recomputed last page).
        nc = numerics.active().replace(prefix_cache=on)
        eng = Engine(cfg, params, max_slots=len(trace_prompts),
                     num_pages=353, page_size=8, max_pages_per_slot=40,
                     numerics_config=nc, cache_dtype=jnp.float32)
        eng.add_request(system, SamplingParams(max_tokens=2, seed=99))
        eng.run()                         # primer: inserts the system pages

        def burst():
            rids = [eng.add_request(p, SamplingParams(max_tokens=pgen,
                                                      seed=i))
                    for i, p in enumerate(trace_prompts)]
            t0 = _time.perf_counter()
            first: dict[int, float] = {}
            while eng.sched.has_work or eng._inflight is not None:
                eng.step()
                now = _time.perf_counter()
                for rid in rids:
                    req = eng._requests[rid]
                    if rid not in first and (req.out or req.finished):
                        first[rid] = now - t0
            dt = _time.perf_counter() - t0
            out = eng.results()
            return out, sorted(first.values()), \
                sum(len(out[r]) for r in rids) / dt

        burst(), burst()                               # compile warmup
        out, ttfts, tps = burst()
        return (out, eng, float(np.percentile(ttfts, 50)),
                float(np.percentile(ttfts, 99)), tps)

    out_off, _, p50_off, p99_off, _ = _prefix_run(False)
    out_on, eng_on, p50_on, p99_on, tps_on = _prefix_run(True)
    prefix_parity = all(list(out_off[r]) == list(out_on[r])
                        for r in sorted(out_off))
    pstats = eng_on.stats()
    n_reqs = 1 + 3 * len(trace_prompts)   # primer + three bursts
    hit_rate = pstats["prefix_hits"] / n_reqs
    prefix_ok = prefix_parity and pstats["prefix_hits"] > 0
    ok &= prefix_ok
    record("serving/prefix/parity", float(prefix_parity))
    record("serving/prefix/hit_rate", hit_rate, unit="frac",
           higher_is_better=True)
    record("serving/prefix/tokens_reused",
           float(pstats["prefix_tokens_reused"]), unit="tok",
           higher_is_better=True)
    record("serving/prefix/cow_splits", float(pstats["cow_splits"]),
           unit="count", higher_is_better=False)
    record("serving/prefix/tok_per_s", tps_on, unit="tok/s",
           kind="measured", higher_is_better=True)
    for label, val in (("ttft_p50_s", p50_on), ("ttft_p99_s", p99_on),
                       ("ttft_p50_off_s", p50_off),
                       ("ttft_p99_off_s", p99_off)):
        record(f"serving/prefix/{label}", val, unit="s", kind="measured",
               higher_is_better=False)
    record("serving/prefix/ttft_p50_speedup",
           p50_off / p50_on if p50_on else 1.0, unit="x", kind="measured",
           higher_is_better=True)
    emit("serving_prefix",
         "Shared-prefix serving — COW prefix cache on a deterministic "
         f"trace ({shared_frac:.0%} shared system prompt, mixed lengths)",
         ["metric", "value"],
         [["token parity (cache on == off, f32 pools)", str(prefix_parity)],
          ["prefix hit-rate", f"{hit_rate:.2f}"],
          ["prompt tokens reused", pstats["prefix_tokens_reused"]],
          ["COW splits", pstats["cow_splits"]],
          ["TTFT p50 on/off", f"{p50_on:.3f}s / {p50_off:.3f}s"],
          ["TTFT p50 speedup", f"{p50_off / max(p50_on, 1e-9):.2f}x"]],
         "hits map shared pages read-only and prefill only the novel "
         "tail; the last prompt position always recomputes (COW)")

    if smoke:
        return ok

    # ---- throughput vs in-flight requests -------------------------------
    n_req = 8
    prompts = _requests(cfg, n_req, seed=3)
    rows = []
    for slots in (1, 2, 4, 8):
        _, toks, dt, samples, engine = _engine_run(
            cfg, params, prompts, max_slots=slots, max_tokens=gen,
            reps=2, warmup=1)
        rows.append([slots, toks, f"{dt:.2f}s", f"{toks/dt:.1f}",
                     engine.n_prefills, engine.n_decode_steps])
        record_timed(f"serving/slots{slots}/tok_per_s", samples,
                     unit="tok/s", higher_is_better=True,
                     transform=lambda s: toks / s)
    # dense baseline: same-length batch (the only thing it can do)
    prompts_dense = jnp.asarray(
        np.stack([p[:4] for p in prompts]), jnp.int32)
    _, dt, samples = timed(
        lambda: generate_dense(cfg, params, prompts_dense, gen),
        reps=2, warmup=1)
    rows.append(["dense-XLA batch", n_req * gen, f"{dt:.2f}s",
                 f"{n_req*gen/dt:.1f}", 1, gen])
    record_timed("serving/dense_batch/tok_per_s", samples, unit="tok/s",
                 higher_is_better=True,
                 transform=lambda s: n_req * gen / s)
    emit("serving_throughput",
         "Engine tok/s vs in-flight slots (CPU shape run; post-compile, "
         "blocking reps)",
         ["slots", "tokens", "wall", "tok/s", "prefills", "decode steps"],
         rows,
         "decode steps shrink as slots grow: continuous batching advances "
         "every resident request per jitted step")
    return ok


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    smoke = "--smoke" in args
    return 0 if run(smoke=smoke) else 1


if __name__ == "__main__":
    raise SystemExit(main())
