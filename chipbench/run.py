"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up makes the weights on the device from ``--seed``, builds the
serving engine as the cell's configuration and traffic files say, serves
one request of each prompt length so that every program the traffic can
ask for is compiled (or loaded from JAX's persistent compilation cache,
kept in ``.jax_cache/`` of the checkout unless ``JAX_COMPILATION_CACHE_DIR``
says otherwise), and then offers the traffic for a pre-roll so that the
engine reaches steady state.  The measured window follows: ``--seconds``
of open-loop arrivals, each request timed on the host clock from when it
was due.

``--trace 1`` is a run of its own: it profiles a few seconds of the
steady window with ``jax.profiler`` and prints the cell's per-layer
metrics instead of its end-to-end ones.

Every run then compares what it served with the plain reference
(``check.py``) and prints the compared numbers beside their limits, as
the last lines of standard error and under ``check`` in the result, the
last line of standard output.  Without a TPU, or with fewer chips than
the cell asks for, it exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(_ROOT), str(_ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import spec  # noqa: E402

RESIDENT_WAIT_S = 60.0  # longest wait past the window for a resident
TRACE_OFFSET_S = 1.0    # the traced span starts this far into the window
TRACE_S = 3.0           # and lasts about this long
CACHE_DIR = _ROOT / ".jax_cache"
OUT_DIR = _ROOT / ".chipbench_out"


class NoChip(RuntimeError):
    pass


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ----------------------------------------------------------- set-up

def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"needs {n} chips; JAX found {len(devs)}")
    return devs


class CompileCounter:
    """Counts JAX traces, compiles and persistent-cache loads."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_retrieval_time_sec": "loads"}

    def __init__(self):
        import collections
        import jax
        self.counts = {v: 0 for v in self.EVENTS.values()}
        self.programs = collections.Counter()   # compiled or loaded, by name
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        name = self.EVENTS.get(event)
        if name:
            self.counts[name] += 1
            if name == "compiles":
                self.programs[kw.get("fun_name", "?")] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def numerics_for(config: dict, mix: dict):
    from repro import numerics
    n = dict(config["numerics"])
    n.pop("policy", None)
    eng = mix["engine"]
    return numerics.active().replace(
        chunked_prefill=int(eng["chunked_prefill"]), **n)


def engine_sizes(config: dict, mix: dict) -> dict:
    """Slots, page size and a pool large enough that no request is ever
    preempted: every slot can hold the longest prompt and output."""
    eng = mix["engine"]
    ps = int(config["engine"]["page_size"])
    most = max(mix["prompt_lengths"]) + int(mix["output"]["max"]) + 1
    per_slot = -(-most // ps)
    return {"max_slots": int(eng["max_slots"]), "page_size": ps,
            "max_pages_per_slot": per_slot,
            "num_pages": 1 + int(eng["max_slots"]) * per_slot,
            "cache_dtype": config["engine"]["cache_dtype"]}


def validate(mix: dict, sizes: dict):
    """Every prompt is prefilled in whole chunks of one fixed size, so the
    prefill programs are one per prompt length, whatever arrives."""
    C = int(mix["engine"]["chunked_prefill"])
    if C % sizes["page_size"]:
        raise spec.SpecError(f"chunk {C} is not a page multiple")
    for L in mix["prompt_lengths"]:
        if L <= C or L % C:
            raise spec.SpecError(
                f"prompt length {L} must be a multiple of the chunk {C} "
                "above it, or prefill shapes would follow the batch")


def build(cell: dict, seed: int):
    import jax.numpy as jnp
    from repro.serving import Engine
    from chipbench import weights
    config, mix = cell["config"], cell["traffic"]
    cfg = spec.model_config(config)
    sizes = engine_sizes(config, mix)
    validate(mix, sizes)
    params = weights.make_params(cfg, seed)
    engine = Engine(cfg, params, max_slots=sizes["max_slots"],
                    num_pages=sizes["num_pages"],
                    page_size=sizes["page_size"],
                    max_pages_per_slot=sizes["max_pages_per_slot"],
                    numerics_config=numerics_for(config, mix),
                    cache_dtype=jnp.dtype(sizes["cache_dtype"]),
                    max_preemptions=None)
    return cfg, engine, sizes


def warm(engine, mix: dict, vocab: int):
    """Serve one request of every prompt length to completion: compiles
    (or loads) every prefill chunk program, the decode step and the
    host-side sampling ops the traffic can reach."""
    import numpy as np
    from repro.serving import SamplingParams
    rng = np.random.default_rng(0)
    for L in mix["prompt_lengths"]:
        engine.add_request(rng.integers(0, vocab, int(L), dtype=np.int32),
                           SamplingParams(temperature=0.0, max_tokens=3))
    engine.run()


# ------------------------------------------------------------ window

def annotate(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Recorder:
    """Client-side record of every request: when it was due, when it was
    handed to the engine, and when each of its tokens came back."""

    def __init__(self, engine):
        self.engine = engine
        self.reqs = {}            # rid -> record
        self.active = {}          # rid -> record, not yet finished
        self.steps = []           # per engine step, while recording
        self.record_steps = False

    def add(self, r, now: float):
        from repro.serving import SamplingParams
        with annotate("chipbench.admit"):
            rid = self.engine.add_request(
                r.prompt, SamplingParams(temperature=0.0,
                                         max_tokens=r.max_tokens))
        rec = {"rid": rid, "due": r.due_s, "added": now, "prompt": r.prompt,
               "plen": len(r.prompt), "stamps": [], "finish": None,
               "max_tokens": r.max_tokens}
        self.reqs[rid] = rec
        self.active[rid] = rec

    def step(self, clock0: float):
        eng = self.engine
        done0 = ({rid: eng._requests[rid].prefill_done for rid in self.active}
                 if self.record_steps else None)
        chunks0, decodes0 = eng.n_prefill_chunks, eng.n_decode_steps
        t0 = time.perf_counter() - clock0
        with annotate("chipbench.step"):
            eng.step()
        t = time.perf_counter() - clock0
        with annotate("chipbench.poll"):
            self._poll(t, done0)
        if done0 is not None:
            self.steps.append({
                "t0": t0, "t1": t,
                "chunks": eng.n_prefill_chunks - chunks0,
                "decode": eng.n_decode_steps - decodes0,
                "tokens": self._new_tokens, "chunk_spans": self._spans})

    def _poll(self, t: float, done0):
        eng = self.engine
        new_tokens, spans = [], []
        self._new_tokens, self._spans = new_tokens, spans
        for rid, rec in list(self.active.items()):
            req = eng._requests[rid]
            if done0 is not None and req.prefill_done > done0[rid]:
                spans.append((rec["plen"], done0[rid], req.prefill_done))
            n = len(req.out)
            while len(rec["stamps"]) < n:
                new_tokens.append((rec["plen"], len(rec["stamps"])))
                rec["stamps"].append(t)
            if req.finish_reason is not None:
                rec["finish"] = req.finish_reason
                rec["out"] = list(req.out)
                del self.active[rid]


def drive(rec: Recorder, stream, clock0: float, until: float, *,
          lateness: list | None = None, done=None, on_step=None):
    """Offer the stream's arrivals and step the engine until ``until``
    seconds on the stream clock (or until ``done()``)."""
    eng = rec.engine
    while True:
        now = time.perf_counter() - clock0
        if now >= until or (done is not None and done()):
            return
        for r in stream.take_due(now):
            if lateness is not None:
                lateness.append(now - r.due_s)
            rec.add(r, now)
        if eng.sched.has_work:
            rec.step(clock0)
            if on_step is not None:
                on_step(time.perf_counter() - clock0)
        else:
            with annotate("chipbench.wait"):
                time.sleep(max(0.0, min(stream.next_due(), until) - now))


def pct(values, q: float):
    """Nearest-rank percentile (``q`` in 0..100) of a non-empty list."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


# ------------------------------------------------------------ results

def e2e_metrics(rec: Recorder, ws: float, we: float, t_end: float) -> dict:
    """The end-to-end numbers of one window [ws, we) on the stream clock,
    and the rest of what it read.  A request due in the window fails when
    it finished with an error.  Time to first token is read up to
    ``t_end``: a request without one by then counts as served at
    ``t_end``."""
    due = [r for r in rec.reqs.values() if ws <= r["due"] < we]
    ttft = [(r["stamps"][0] if r["stamps"] else t_end) - r["due"]
            for r in due]
    gaps, tokens = [], 0
    for r in rec.reqs.values():
        st = r["stamps"]
        tokens += sum(1 for t in st if ws <= t < we)
        gaps += [b - a for a, b in zip(st, st[1:]) if ws <= b < we]
    failed = sum(1 for r in due if r["finish"] == "error")
    out = {"output_tokens_per_s": tokens / (we - ws),
           "itl_p95_ms": 1e3 * pct(gaps, 95) if gaps else None}
    return out, {"attempted": len(due), "failed": failed,
                 "window_tokens": tokens, "gaps": len(gaps),
                 "ttft_p50_ms": 1e3 * pct(ttft, 50) if ttft else None,
                 "ttft_p95_ms": 1e3 * pct(ttft, 95) if ttft else None,
                 "itl_p50_ms": 1e3 * pct(gaps, 50) if gaps else None}


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:  # noqa: BLE001 — a backend without the stat
            pass
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def check_numbers(cell: dict, cfg, seed: int, sample: list,
                  errored: int) -> tuple[dict, dict]:
    """The compared numbers for the sample, each beside its limit, and
    what :func:`check.compare` read."""
    from chipbench import check, weights
    config = cell["config"]
    ref = spec.load_module(spec.reference_path(config["reference"]),
                           "chipbench_reference")
    params = weights.make_params(cfg, seed)
    w = weights.hf_view(params, cfg)
    del params
    got = check.compare(ref, w, config["hf_config"], sample)
    limits = cell["limits"]["limits"]
    nums = {"token_gap": got["token_gap"], "kv_rel_err": got["kv_rel_err"],
            "errored": errored}
    return ({k: {"value": v, "limit": limits[k]} for k, v in nums.items()},
            got)


def _resident(engine) -> list:
    """Resident requests that have decoded, longest first."""
    return sorted((r for r in engine.sched.running.values()
                   if r.state.value == "running" and len(r.out) > 1),
                  key=lambda r: -(len(r.prompt) + len(r.out)))


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             devices, keep_sample: list | None = None) -> dict:
    """One run of one cell (as :func:`spec.cell` reads it) on
    ``devices``; returns the result object."""
    import jax
    workload = cell["workload"]["name"]
    counter = CompileCounter()
    from chipbench import traffic
    mix = cell["traffic"]
    cfg, engine, sizes = build(cell, seed)
    warm(engine, mix, cfg.vocab_size)
    programs_after_warm = counter.snapshot()

    rec = Recorder(engine)
    preroll = float(mix["preroll_s"])
    ws, we = preroll, preroll + float(seconds)
    stream = traffic.Stream(mix, seed, cfg.vocab_size, (preroll, seconds))
    lateness = []
    clock0 = time.perf_counter()
    drive(rec, stream, clock0, ws, lateness=lateness)
    setup_s = clock0 + ws - PROCESS_T0
    at_window = counter.snapshot()

    trace_dir = None
    tstate = {}
    if trace:
        trace_dir = OUT_DIR / "trace" / workload
        t_on = ws + min(TRACE_OFFSET_S, 0.25 * seconds)
        t_len = min(float(mix.get("trace_s", TRACE_S)), 0.6 * seconds)

        def on_step(now):
            if "start" not in tstate and now >= t_on:
                import shutil
                shutil.rmtree(trace_dir, ignore_errors=True)
                tstate["stats0"] = dict(engine.stats())
                jax.profiler.start_trace(str(trace_dir))
                tstate["note"] = annotate("chipbench.traced")
                tstate["note"].__enter__()
                tstate["start"] = time.perf_counter() - clock0
                rec.record_steps = True
            elif ("start" in tstate and "stop" not in tstate
                  and now >= tstate["start"] + t_len):
                rec.record_steps = False
                tstate["stop"] = time.perf_counter() - clock0
                tstate["stats1"] = dict(engine.stats())
                tstate["note"].__exit__(None, None, None)
                jax.profiler.stop_trace()
    else:
        on_step = None
    drive(rec, stream, clock0, we, lateness=lateness, on_step=on_step)
    in_window = {k: v - at_window[k] for k, v in counter.snapshot().items()}
    t_end = time.perf_counter() - clock0
    # the K/V comparison needs a request resident in the engine: serve on
    # (arrivals continuing) until one has decoded
    drive(rec, stream, clock0, t_end + RESIDENT_WAIT_S,
          done=lambda: bool(_resident(engine)))
    if trace and "stop" not in tstate and "start" in tstate:
        tstate["stop"] = t_end
        tstate["stats1"] = dict(engine.stats())
        tstate["note"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    e2e, extra = e2e_metrics(rec, ws, we, t_end)
    e2e["setup_s"] = setup_s
    dev = device_info(devices)
    stats = engine.stats()

    # ---- what the timed path produced, for the comparison
    from chipbench import check
    finished = [{"prompt": list(r["prompt"]), "out": r["out"]}
                for r in rec.reqs.values()
                if r["finish"] == "length" and r["stamps"]
                and r["stamps"][-1] >= ws]
    nchk = mix.get("check", {})
    sample = check.sample_finished(finished, int(nchk.get("finished", 8)),
                                   seed)
    running = _resident(engine)
    n_res = int(nchk.get("resident", 2))
    resident = running[:1]
    if n_res > 1 and len(running) > 1:
        import numpy as np
        rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 11])
        idx = rng.choice(len(running) - 1, size=min(n_res - 1,
                                                    len(running) - 1),
                         replace=False)
        resident += [running[1 + i] for i in sorted(idx)]
    sample += [check.resident_record(engine, r) for r in resident]
    errored = sum(1 for r in rec.reqs.values() if r["finish"] == "error")

    per_layer = {}
    breakdown = None
    if trace and "start" in tstate:
        from chipbench import layer_metrics
        per_layer, breakdown, busy = layer_metrics.read_all(
            cell, cfg, rec.steps, tstate, trace_dir, devices, sizes)
        dev["busy_s"] = busy["busy_s"]
        dev["window_s"] = busy["window_s"]

    # ---- free the program's state, then run the reference
    del engine, rec.engine
    gc.collect()
    t_chk = time.perf_counter()
    numbers, got = check_numbers(cell, cfg, seed, sample, errored)
    if keep_sample is not None:
        keep_sample += [sample, cfg]
    correct = all(v["value"] is not None and v["value"] <= v["limit"]
                  for v in numbers.values())
    check_s = time.perf_counter() - t_chk

    info = {
        "programs": dict(sorted(counter.programs.items())),
        "programs_at_warm": programs_after_warm,
        "programs_in_window": in_window,
        "setup_s": setup_s, "window_s": we - ws,
        "generator_late_p95_ms": 1e3 * pct(lateness, 95) if lateness else 0,
        "engine": {k: v for k, v in stats.items() if k != "breaker"},
        "check_s": check_s, "compared": got, **extra,
    }
    log("run: " + json.dumps(info, default=str))
    if trace:
        metrics = {m["name"]: {"value": per_layer[m["name"]],
                               "unit": m["unit"]}
                   for m in cell["per_layer"] if m["name"] in per_layer}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]
                   if e2e.get(m["name"]) is not None}
    for name, v in numbers.items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    result = {"correct": bool(correct), "attempted": extra["attempted"],
              "failed": extra["failed"], "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    if keep_sample is not None:     # a caller that reads the sample
        result["compared"] = got
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.cell(args.workload)
        devices = require_chips(int(cell["workload"]["chips"]))
    except (NoChip, spec.SpecError) as exc:
        log(f"chipbench: {exc}")
        return 3
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[: int(cell["workload"]["chips"])])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
