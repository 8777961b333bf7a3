"""Bring-up check of the serving path on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded phase alone, on four

One chip: builds the published qwen3-0.6b configuration (28 layers,
d_model 1024, vocab 151936, GQA 16/8, head_dim 128) with random weights
from ``PRNGKey(0)`` and an :class:`repro.serving.Engine` the way
``python -m repro.launch.serve --arch qwen3-0.6b`` does, then serves a few
requests whose prompts are long enough for prefill to reach the fused
GEMM and flash-attention kernels; every decode step runs the paged
kernel.  Blocks come from code alone (``tune="off"``) and kernel errors
raise (``guard=False``).  It fails unless the dispatch-explain table shows
fused launches of all three kernels and no kernel failure, open breaker
or off-backend decline; the engine counts no guard trip, fallback re-run
or numerics error; every request finishes by length; and the prefill
logits are finite and agree with the same forward on the XLA
term-expansion path (``numerics.use(enabled=False)``) within
``LOGIT_RTOL`` of the largest logit.

``--four-chips`` runs only the sharded comparison: the engine under a
``(1, 4)`` ("data", "model") mesh with KV heads on ``model`` against the
unsharded engine, by tokens and prefill-logit gap, and a few sharded
training steps against the same steps on one device, by loss.

Runs in one process and exits non-zero, printing no result, where JAX
finds no TPU.  The last line of a passing run is one JSON object naming
the device.  The compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache/`` of the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-0.6b"
N_REQUESTS = 4
PROMPT_LEN = 300        # >= 256: prefill S and T clear min_dim
GEN = 16
#: prefill-logit gap, relative to the largest reference logit
LOGIT_RTOL = 1e-4
#: train-step loss gap, relative
LOSS_RTOL = 1e-4
TRAIN_LAYERS = 4        # depth cut for the four-chip training comparison
TRAIN_STEPS = 3
FORBIDDEN_RULES = ("kernel-failure", "breaker-open", "off-backend")
KERNELS = ("matmul", "attention", "paged_attention")


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def prompts_for(cfg, seed: int = 0):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
            for _ in range(N_REQUESTS)]


def make_engine(cfg, params, nc, mesh=None):
    """An engine sized as ``launch/serve.py`` sizes one."""
    from repro.serving import DEFAULT_PAGE_SIZE, Engine
    ps = DEFAULT_PAGE_SIZE
    pages = -(-(PROMPT_LEN + GEN + 1) // ps)
    return Engine(cfg, params, max_slots=N_REQUESTS,
                  num_pages=1 + N_REQUESTS * pages, page_size=ps,
                  max_pages_per_slot=pages, numerics_config=nc, mesh=mesh)


def serve(engine, prompts):
    """Enqueue ``prompts`` greedily and drain; returns (outputs, seconds)."""
    from repro.serving import SamplingParams
    t0 = time.perf_counter()
    rids = [engine.add_request(p, SamplingParams(temperature=0.0,
                                                 max_tokens=GEN))
            for p in prompts]
    out = engine.run()
    return [out[r] for r in rids], time.perf_counter() - t0


def prefill_logits(cfg, params, prompts, nc, mesh=None):
    """Sequence-level prefill logits of ``prompts`` under the numerics
    config ``nc`` (and ``mesh``), as the engine's prefill computes them."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import numerics
    from repro.models import get_model
    from repro.parallel import ctx
    model = get_model(cfg)
    toks = jnp.asarray(np.stack(prompts), jnp.int32)
    scope = ctx.use_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with numerics.use(nc), scope:
        logits, _ = jax.jit(model.prefill)(params, toks)
    return logits[..., :cfg.vocab_size]


def logit_gap(got, ref) -> tuple[float, float]:
    """(max |got - ref|, max |ref|), both finite-checked."""
    import jax.numpy as jnp
    check(bool(jnp.all(jnp.isfinite(got))), "non-finite prefill logits")
    check(bool(jnp.all(jnp.isfinite(ref))), "non-finite reference logits")
    return (float(jnp.max(jnp.abs(got - ref))), float(jnp.max(jnp.abs(ref))))


def explain_checks(report):
    """Fused launches of every kernel; no forbidden decision."""
    fused = {e["kernel"] for e in report.entries if e["rule"] == "fused"}
    for k in KERNELS:
        check(k in fused, f"no fused {k} launch in the explain table")
    bad = [e["key"] + ":" + e["rule"] for e in report.entries
           if e["rule"] in FORBIDDEN_RULES]
    check(not bad, f"forbidden dispatch decisions: {bad}")
    counts = {k: sum(e["count"] for e in report.entries
                     if e["kernel"] == k and e["rule"] == "fused")
              for k in KERNELS}
    return counts


def engine_checks(engine, outputs):
    stats = engine.stats()
    for k in ("guard_trips", "fallback_reruns", "numerics_errors"):
        check(stats[k] == 0, f"engine {k} = {stats[k]}")
    reasons = [o.finish_reason for o in outputs]
    check(all(r == "length" for r in reasons), f"finish reasons {reasons}")
    check(all(len(o) == GEN for o in outputs),
          f"generated lengths {[len(o) for o in outputs]}")


def base_numerics():
    from repro import numerics
    return numerics.active().replace(tune="off", guard=False)


def one_chip_phase(cfg, nc):
    """Serve at full width; returns the numbers the log line reports."""
    import jax
    from repro import obs
    from repro.models import get_model
    print(f"config: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} policy={cfg.policy}",
          flush=True)
    t0 = time.perf_counter()
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    print(f"init: {time.perf_counter() - t0:.3f} s", flush=True)

    obs.explain(reset=True)
    engine = make_engine(cfg, params, nc)
    prompts = prompts_for(cfg, seed=0)
    outputs, first_s = serve(engine, prompts)
    # same shapes again: nothing left to compile
    outputs2, second_s = serve(engine, prompts_for(cfg, seed=1))
    report = obs.explain()
    print(report, flush=True)
    counts = explain_checks(report)
    engine_checks(engine, outputs + outputs2)
    n_tok = sum(len(o) for o in outputs2)
    print(f"serve: {N_REQUESTS} requests x prompt {PROMPT_LEN} -> {GEN} "
          f"tokens; first run {first_s:.3f} s (compile included), second "
          f"run {second_s:.3f} s ({n_tok / second_s:.1f} tok/s); compile "
          f"~{first_s - second_s:.3f} s", flush=True)
    print(f"sample tokens: {list(outputs[0])}", flush=True)

    fused = prefill_logits(cfg, params, prompts, nc)
    ref = prefill_logits(cfg, params, prompts, nc.replace(enabled=False))
    gap, scale = logit_gap(fused, ref)
    print(f"prefill logit gap vs XLA expansion: max|d| {gap:.6g}, "
          f"max|ref| {scale:.6g}, relative {gap / scale:.6g} "
          f"(limit {LOGIT_RTOL:g})", flush=True)
    check(gap <= LOGIT_RTOL * scale, "prefill logits disagree with the XLA "
          "term expansion")
    return {"explain_fused": counts, "first_run_s": first_s,
            "second_run_s": second_s, "logit_rel_gap": gap / scale}


def four_chip_phase(cfg, nc):
    """Sharded engine and train steps against their one-device runs."""
    import jax
    import numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.models import get_model
    mesh = make_host_mesh(model=4)
    print(f"mesh: {dict(mesh.shape)}; config: {cfg.name} "
          f"layers={cfg.n_layers} d_model={cfg.d_model} kv_heads="
          f"{cfg.n_kv_heads}", flush=True)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    prompts = prompts_for(cfg, seed=0)

    from repro import obs
    obs.explain(reset=True)
    base_out, base_s = serve(make_engine(cfg, params, nc), prompts)
    sharded = make_engine(cfg, params, nc, mesh=mesh)
    shard_out, shard_s = serve(sharded, prompts)
    report = obs.explain()
    print(report, flush=True)
    explain_checks(report)
    engine_checks(sharded, shard_out)
    from repro.kernels import shmap
    print(f"shard_map dispatches: {shmap.counters()}", flush=True)
    same = [list(a) == list(b) for a, b in zip(base_out, shard_out)]
    print(f"engine: unsharded {base_s:.3f} s, sharded {shard_s:.3f} s "
          f"(compile included); token-identical per request: {same}",
          flush=True)
    check(all(same), "sharded engine tokens differ from the unsharded")
    gap, scale = logit_gap(prefill_logits(cfg, params, prompts, nc, mesh),
                           prefill_logits(cfg, params, prompts, nc))
    print(f"prefill logit gap sharded vs unsharded: max|d| {gap:.6g}, "
          f"relative {gap / scale:.6g} (limit {LOGIT_RTOL:g})", flush=True)
    check(gap <= LOGIT_RTOL * scale, "sharded prefill logits disagree")

    from repro import numerics
    from repro.data.pipeline import DataConfig
    from repro.optim import adamw
    from repro.train.loop import TrainLoopConfig, train
    tcfg = cfg.replace(n_layers=TRAIN_LAYERS)
    losses = {}
    for name, m in (("one-device", None), ("sharded", mesh)):
        with numerics.use(nc), tempfile.TemporaryDirectory() as d:
            t0 = time.perf_counter()
            _, hist = train(tcfg, adamw.OptConfig(lr=1e-3),
                            DataConfig(seed=0, global_batch=4, seq_len=256),
                            TrainLoopConfig(total_steps=TRAIN_STEPS,
                                            ckpt_every=10 ** 6),
                            d, mesh=m, log=lambda msg: None)
            losses[name] = [h["loss"] for h in hist]
        print(f"train {name} ({TRAIN_LAYERS} layers, batch 4 x 256): "
              f"losses {losses[name]} in {time.perf_counter() - t0:.3f} s",
              flush=True)
    a, b = np.asarray(losses["sharded"]), np.asarray(losses["one-device"])
    check(bool(np.all(np.isfinite(a))), "non-finite sharded loss")
    rel = float(np.max(np.abs(a - b) / np.abs(b)))
    print(f"train loss gap sharded vs one device: relative {rel:.6g} "
          f"(limit {LOSS_RTOL:g})", flush=True)
    check(rel <= LOSS_RTOL, "sharded training losses disagree")
    return {"logit_rel_gap": gap / scale, "loss_rel_gap": rel}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase, on a (1, 4) mesh")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    kind = devices[0].device_kind
    print(f"device: {platform} {kind} x{len(devices)}; jax "
          f"{jax.__version__}; compile cache {cache_dir}", flush=True)
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    try:
        phase = four_chip_phase if args.four_chips else one_chip_phase
        phase(cfg, base_numerics())
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(f"wall: {time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
