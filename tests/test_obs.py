"""repro.obs: metrics registry, tracing, dispatch explain, numerics health.

Pins the four telemetry layers' contracts:

  * registry semantics — labels, snapshot/diff, thread-safety, and
    reset-keeps-objects (handles stay valid across test resets);
  * span nesting plus Chrome-trace/Perfetto + JSONL export round-trips
    (schema-validated: every event carries name/ph/ts, async request
    events pair ``b``/``e`` by id);
  * dispatch-explain rule slugs — each recorded decline names the rule
    from docs/architecture.md's decision tree (the doc must backtick
    every slug), and every non-fused contraction gets an entry;
  * monitor probe math — ``safe_exponent_range`` and the observed
    (gradual-)underflow fraction against ``core/theory.py``'s closed
    forms (the probe uses round-to-nearest casts where the theory
    assumes RZ, which shifts the closed form by exactly one exponent);
  * the overhead bound — tracing off/on changes nothing about the
    engine's jitted traces (counted), and monitor off leaves the
    contraction jaxpr callback-free;
  * the profiler sink — every engine phase lands in a ``jax.profiler``
    trace as a host event with its args as stats, nested as the engine
    runs them, and garbage collections as ``python.gc``.
"""
import gc
import glob
import json
import os
import subprocess
import sys
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import numerics, obs
from repro.core import theory
from repro.core.policy import get_policy, policy_mm
from repro.obs import metrics
from repro.obs import numerics_health as nh
from repro.obs.explain import RULES
from repro.obs.trace import Tracer, current, last, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


# ============================================================== registry

def test_counter_labels_and_total():
    c = metrics.counter("test/obs/counter")
    c.reset()
    c.inc(kernel="matmul")
    c.inc(2, kernel="paged")
    c.inc()
    assert c.value(kernel="matmul") == 1
    assert c.value(kernel="paged") == 2
    assert c.value() == 1                      # the unlabeled series
    assert c.total() == 4
    items = c.items()
    assert items["test/obs/counter{kernel=paged}"] == 2
    assert items["test/obs/counter"] == 1


def test_gauge_running_extrema():
    g = metrics.gauge("test/obs/gauge")
    g.reset()
    g.set_min(-3.0)
    g.set_min(-1.0)
    g.set_max(5.0)
    g.set_max(2.0)
    assert g.value() == 5.0                     # last set_max won the slot
    g.set(7.0, policy="x")
    assert g.value(policy="x") == 7.0


def test_histogram_buckets_count_sum_percentile():
    h = metrics.histogram("test/obs/hist", buckets=(1.0, 2.0, 4.0))
    h.reset()
    for v in (0.5, 0.5, 1.5, 3.0, 9.0):         # 9.0 -> overflow slot
        h.observe(v)
    assert h.count() == 5
    assert h.sum() == pytest.approx(14.5)
    items = h.items()["test/obs/hist"]
    assert items["counts"] == [2, 1, 1, 1]      # (0,1], (1,2], (2,4], over
    # interpolated: the 50th percentile lands in the (1, 2] bucket
    assert 1.0 <= h.percentile(50) <= 2.0
    assert h.percentile(100) == 4.0             # capped at the top edge
    assert metrics.histogram("test/obs/empty",
                             buckets=(1.0,)).percentile(99) == 0.0


def test_histogram_label_merge():
    h = metrics.histogram("test/obs/hist2", buckets=(1.0, 2.0))
    h.reset()
    h.observe(0.5, policy="a")
    h.observe(1.5, policy="b")
    assert h.count(policy="a") == 1
    assert h.count() == 2                       # no labels -> merged view


def test_registry_kind_conflict_raises():
    metrics.counter("test/obs/kindconflict")
    with pytest.raises(TypeError):
        metrics.gauge("test/obs/kindconflict")


def test_snapshot_diff_omits_unchanged():
    c = metrics.counter("test/obs/diff")
    c.reset()
    c.inc(5)
    old = metrics.snapshot(include_sources=False)
    c.inc(3)
    metrics.observe("test/obs/diffhist", 0.5, buckets=(1.0,))
    new = metrics.snapshot(include_sources=False)
    d = metrics.diff(new, old)
    assert d["counters"]["test/obs/diff"] == 3
    assert "test/obs/counter" not in d["counters"]   # unchanged -> omitted
    assert d["histograms"]["test/obs/diffhist"]["count"] == 1


def test_default_sources_present():
    import repro.serving.engine  # noqa: F401 — registers its source
    snap = obs.snapshot()
    assert "kernels/guard" in snap["sources"]
    assert "allowed" in snap["sources"]["kernels/guard"]
    assert "faults/fired" in snap["sources"]
    assert "serving/engine" in snap["sources"]


def test_thread_safety():
    c = metrics.counter("test/obs/threads")
    c.reset()
    h = metrics.histogram("test/obs/threadhist", buckets=(0.5, 1.0))
    h.reset()

    def work():
        for _ in range(1000):
            c.inc(site="t")
            h.observe(0.25)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(site="t") == 8000
    assert h.count() == 8000


def test_reset_keeps_objects_and_sources():
    c = metrics.counter("test/obs/reset")
    c.inc(9)
    obs.reset()
    assert c.value() == 0
    c.inc()                                     # old handle still works
    assert metrics.counter("test/obs/reset") is c
    assert "kernels/guard" in obs.snapshot()["sources"]


# =============================================================== tracing

def test_span_nesting_with_synthetic_clock():
    ticks = iter(range(100))
    tr = Tracer(clock=lambda: next(ticks))      # 1-second ticks
    with tr.span("outer") as args:
        with tr.span("inner"):
            pass
        args["occupancy"] = 3                   # annotated at exit
    inner, outer = tr.events
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert outer["ph"] == "X" and outer["dur"] > inner["dur"]
    assert outer["args"]["occupancy"] == 3      # mutable-dict annotation
    assert inner["ts"] >= outer["ts"]


def test_trace_context_precedence_and_last():
    assert current() is None
    with trace() as t1:
        assert current() is t1
        with trace() as t2:
            assert current() is t2              # innermost wins
        assert current() is t1
    assert current() is None
    assert last() is t1                         # exported after exit


def test_export_roundtrip_chrome_and_jsonl(tmp_path, monkeypatch):
    tr = Tracer(clock=iter(range(100)).__next__)
    tr.async_begin("request", 7, prompt_len=4)
    with tr.span("engine.step", clock=1):
        tr.instant("fallback-rerun", slots=[0])
    tr.async_end("request", 7, finish="length", tokens=8)

    p = tmp_path / "trace.json"
    tr.export(str(p))
    doc = json.loads(p.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    for ev in evs:                              # minimal chrome schema
        assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
    by_ph = {ev["ph"]: ev for ev in evs}
    assert by_ph["b"]["id"] == by_ph["e"]["id"] == 7
    assert by_ph["e"]["args"]["finish"] == "length"
    assert by_ph["X"]["name"] == "engine.step" and "dur" in by_ph["X"]
    assert by_ph["i"]["s"] == "t"

    pl = tmp_path / "trace.jsonl"
    tr.export(str(pl))
    lines = [json.loads(ln) for ln in pl.read_text().splitlines()]
    assert lines == evs                         # same events, one per line

    # with no tracer ever installed, export has nothing to write
    import sys
    trace_mod = sys.modules["repro.obs.trace"]   # attr is the shadow fn
    monkeypatch.setattr(trace_mod, "_LAST", None)
    with pytest.raises(RuntimeError, match="no tracer"):
        obs.export(str(p))


# ====================================================== dispatch explain

def test_explain_rule_slugs_documented():
    """docs/architecture.md's decision tree must name every rule slug."""
    with open(os.path.join(ROOT, "docs", "architecture.md")) as f:
        doc = f.read()
    for slug in RULES:
        assert f"`{slug}`" in doc, f"rule {slug!r} missing from " \
                                   "docs/architecture.md"


def test_explain_names_declining_rule_per_route():
    obs.reset()
    a, b = _rand((256, 256), 1), _rand((256, 256), 2)
    small = jnp.ones((8, 8), jnp.float32)
    with numerics.use(policy="tcec_bf16x3", force=True, interpret=True):
        policy_mm(a, b)                               # fused
        policy_mm(small, small)                       # below-min-dim
    with numerics.use(policy="tcec_bf16x3", enabled=False):
        policy_mm(a, b)                               # hatch-disabled
    with numerics.use(policy="fp32"):
        policy_mm(a, b)                               # plain-policy
    if jax.default_backend() != "tpu":
        with numerics.use(policy="tcec_bf16x3"):
            policy_mm(a, b)                           # off-backend
    rep = obs.explain()
    rules = {e["rule"] for e in rep.entries}
    expect = {"fused", "below-min-dim", "hatch-disabled", "plain-policy"}
    if jax.default_backend() != "tpu":
        expect.add("off-backend")
    assert expect <= rules, rep.entries
    assert rep.n_fused >= 1 and rep.n_fallback >= 3
    # every non-fused decision names its rule, keyed like the guard
    for e in rep.fallbacks():
        assert e["rule"] in RULES and e["rule"] != "fused"
        assert e["backend"] == jax.default_backend()
        assert e["kernel"] == "matmul"
    # counters carry the same totals
    routes = metrics.counter("kernels/dispatch/route")
    assert routes.value(kernel="matmul", route="fused") == rep.n_fused
    assert (routes.value(kernel="matmul", route="fallback")
            == rep.n_fallback)
    assert str(rep).startswith("dispatch explain:")


def test_explain_policy_ineligible_and_epilogue():
    obs.reset()
    from repro.kernels import dispatch
    pol16 = get_policy("fp16_markidis")
    with numerics.use(policy="fp16_markidis", force=True,
                      fuse_epilogue=True):
        assert not dispatch.epilogue_eligible(pol16)
    with numerics.use(policy="tcec_bf16x6", force=True,
                      fuse_epilogue=True):
        assert dispatch.epilogue_eligible(get_policy("tcec_bf16x6"))
    dec = obs.explain().entries
    epi = [e for e in dec if e["kernel"] == "epilogue"]
    assert {e["rule"] for e in epi} == {"policy-ineligible", "fused"}


def test_explain_report_reset():
    obs.reset()
    from repro.obs.explain import record
    record("matmul", "tcec_bf16x3", (1, 2), "below-min-dim")
    assert obs.explain(reset=True).n_fallback == 1
    assert obs.explain().entries == []
    with pytest.raises(ValueError, match="unknown dispatch rule"):
        record("matmul", "tcec_bf16x3", (), "not-a-rule")


# ======================================================== numerics health

def test_safe_exponent_range_pins_theory():
    """The range's low edge is exactly where the paper's closed-form
    P[u+gu] (Eq. 15) first hits zero."""
    cases = {("bfloat16", 8): (-110, 127),
             ("float16", 11): (-1, 15),
             ("float16", 0): (10, 26)}
    fmts = {"bfloat16": theory.BF16, "float16": theory.FP16}
    for (dtype, sb), expected in cases.items():
        lo, hi = nh.safe_exponent_range(dtype, sb)
        assert (lo, hi) == expected, (dtype, sb)
        fmt = fmts[dtype]
        assert theory.p_underflow_gradual(lo, fmt, sb) == 0.0
        assert theory.p_underflow_gradual(lo - 1, fmt, sb) > 0.0


def test_probe_underflow_fraction_matches_closed_form():
    """Observed gradual-underflow fraction vs Eq. 15.  The probe casts
    round-to-nearest where the closed form assumes RZ, which makes the
    residual one exponent smaller — so the probe at operand exponent
    ``e`` tracks the closed form at ``e - 1``."""
    pol = get_policy("fp16_halfhalf")
    rng = np.random.default_rng(0)
    for e in (-13, -12, -11):
        x = jnp.asarray((2.0 ** e * (1 + rng.random(8192)))
                        .astype(np.float32))
        stats, _, _ = nh._operand_probe(x, pol)
        predicted = theory.p_underflow_gradual(e - 1, theory.FP16,
                                               pol.scale_bits)
        assert float(stats["gu"]) == pytest.approx(predicted, abs=0.02), e
        assert float(stats["oob"]) == 1.0       # e < safe lo = -1
        assert float(stats["emin"]) == e == float(stats["emax"])


def test_probe_healthy_input_is_quiet():
    pol = get_policy("tcec_bf16x3")
    stats, _, _ = nh._operand_probe(_rand((128, 128), 3), pol)
    assert float(stats["gu"]) == 0.0
    assert float(stats["oob"]) == 0.0


def test_monitor_risk_counters_and_output_parity():
    obs.reset()
    x = jnp.asarray((np.random.default_rng(4).standard_normal((128, 128))
                     * 2.0 ** -20).astype(np.float32))
    y = _rand((128, 128), 5)
    with numerics.use(policy="fp16_halfhalf", monitor=True):
        on = policy_mm(x, y)
        on.block_until_ready()
    with numerics.use(policy="fp16_halfhalf"):
        off = policy_mm(x, y)
        off.block_until_ready()
    assert bool(jnp.array_equal(on, off))       # pure observation
    snap = obs.snapshot(include_sources=False)
    risk = metrics.counter("numerics/monitor/underflow_risk")
    assert risk.value(site="mm", policy="fp16_halfhalf") >= 1
    gu = snap["histograms"][
        "numerics/monitor/underflow_frac{policy=fp16_halfhalf}"]
    assert gu["count"] >= 1 and gu["sum"] > 0.5
    assert snap["gauges"][
        "numerics/monitor/exponent_min{policy=fp16_halfhalf}"] < -15


def test_monitor_off_leaves_graph_callback_free():
    a, b = _rand((64, 64), 6), _rand((64, 64), 7)

    def f(a, b):
        return policy_mm(a, b, "fp16_halfhalf")

    with numerics.use(policy="fp16_halfhalf"):
        off = str(jax.make_jaxpr(f)(a, b))
    with numerics.use(policy="fp16_halfhalf", monitor=True):
        on = str(jax.make_jaxpr(f)(a, b))
    assert "callback" not in off
    assert "callback" in on


def test_monitor_sampling_gate():
    nh.configure(sample_every=1000)
    try:
        before = nh._calls
        nh.observe(_rand((8, 8)), _rand((8, 8)),
                   get_policy("tcec_bf16x3"))   # not the sampled call
        assert nh._calls == before + 1
    finally:
        nh.configure(sample_every=1)


def test_monitor_env_knob_registered():
    assert "REPRO_MONITOR" in numerics.ENV_VARS
    cfg = numerics.NumericsConfig.from_env({"REPRO_MONITOR": "1"})
    assert cfg.monitor is True
    assert numerics.NumericsConfig.from_env({}).monitor is False


# ======================================================= engine tracing

_ENGINE_CACHE = {}


def _engine_fixture():
    if not _ENGINE_CACHE:
        from repro.configs import get_smoke_config
        from repro.models import get_model
        cfg = get_smoke_config("qwen3-0.6b")
        model = get_model(cfg)
        _ENGINE_CACHE["v"] = (cfg, model.init(jax.random.PRNGKey(0)))
    return _ENGINE_CACHE["v"]


def _run_engine(n_req=3, max_tokens=4, prompt_len=6, chunk=0):
    from repro.serving import Engine, SamplingParams
    cfg, params = _engine_fixture()
    engine = Engine(cfg, params, max_slots=4, num_pages=64, page_size=8,
                    numerics_config=numerics.active().replace(
                        chunked_prefill=chunk))
    rng = np.random.default_rng(8)
    for i in range(n_req):
        engine.add_request(rng.integers(0, cfg.vocab_size, prompt_len),
                           SamplingParams(max_tokens=max_tokens, seed=i))
    engine.run()
    return engine


def test_engine_trace_exports_request_lifecycle(tmp_path):
    obs.reset()
    n_req, max_tokens = 3, 4
    with trace() as tr:
        _run_engine(n_req, max_tokens)
    p = tmp_path / "serve.json"
    obs.export(str(p))
    doc = json.loads(p.read_text())
    evs = doc["traceEvents"]
    begins = {e["id"] for e in evs
              if e["ph"] == "b" and e["name"] == "request"}
    ends = {e["id"]: e for e in evs
            if e["ph"] == "e" and e["name"] == "request"}
    assert len(begins) == n_req and begins == set(ends)
    for ev in ends.values():
        assert ev["args"]["finish"] == "length"
        assert ev["args"]["tokens"] == max_tokens
    admitted = [e for e in evs
                if e["ph"] == "n" and e["name"] == "admitted"]
    assert len(admitted) == n_req
    steps = [e for e in evs
             if e["ph"] == "X" and e["name"] == "engine.step"]
    assert steps and all({"running", "waiting", "step_num"} <= set(e["args"])
                         for e in steps)
    assert [e["args"]["step_num"] for e in steps] == \
        list(range(1, len(steps) + 1))
    assert any(e["name"] == "engine.prefill" and e["args"]["batch"] >= 1
               for e in evs if e["ph"] == "X")
    assert any(e["name"] == "engine.dispatch" and e["args"]["batch"] >= 1
               for e in evs if e["ph"] == "X")
    for name in ("engine.admit", "engine.ensure_pages", "engine.consume",
                 "engine.sync", "engine.accept"):
        assert any(e["name"] == name for e in evs if e["ph"] == "X"), name
    # latency histograms were fed while the tracer was active
    assert metrics.histogram("serving/latency/ttft_s").count() == n_req
    assert metrics.histogram("serving/latency/queue_wait_s").count() == n_req
    assert metrics.histogram("serving/latency/tpot_s").count() > 0
    assert tr is last()


def test_tracing_off_is_inert_and_adds_no_traces(monkeypatch):
    """With neither a tracer nor a profiler the engine writes no tracer
    event and no latency samples; and tracing on adds ZERO extra jitted
    traces — all instrumentation is host-side (counted via the decode
    trace hook)."""
    from jax.profiler import TraceAnnotation
    from repro.serving import engine as eng_mod
    obs.reset()
    counts = []
    orig = eng_mod._decode_and_sample

    def counting(*a, **kw):
        counts.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(eng_mod, "_decode_and_sample", counting)
    # the most recently exited tracer is where a stray event would land
    idle = Tracer()
    monkeypatch.setattr(sys.modules["repro.obs.trace"], "_LAST", idle)
    assert current() is None and not TraceAnnotation.is_enabled()
    _run_engine()                               # tracing off
    untraced = len(counts)
    assert idle.events == [] and last() is idle
    assert metrics.histogram("serving/latency/ttft_s").count() == 0
    counts.clear()
    with trace() as tr:
        _run_engine()                           # tracing on, same config
    assert len(counts) == untraced              # zero extra jitted traces
    assert metrics.histogram("serving/latency/ttft_s").count() == 3
    assert any(e["name"] == "engine.step" for e in tr.events)


def test_engine_stats_folded_into_snapshot():
    engine = _run_engine()
    src = obs.snapshot()["sources"]["serving/engine"]
    assert src["decode_steps"] >= engine.n_decode_steps
    assert src["prefills"] >= engine.n_prefills
    # the source sums every counter of Engine.stats() but the breaker,
    # which is process-wide and folded in as kernels/guard
    assert set(src) == set(engine.stats()) - {"breaker"}


# ============================================== the profiler's clock

def _profiled_spans(trace_dir) -> list:
    """``(name, start_ns, end_ns, stats)`` of the host events whose names
    start with ``engine.`` or ``python.``, sorted by start."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "python.")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda x: x[1])


def _inside(inner, outers) -> bool:
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


def test_engine_spans_land_in_profiler_trace(tmp_path):
    """Under a jax.profiler session, with no repro.obs tracer, each engine
    phase is a host event nested as the engine runs it, with its args as
    event stats; a garbage collection is a ``python.gc`` event."""
    with jax.profiler.trace(str(tmp_path)):
        engine = _run_engine(n_req=2, max_tokens=3, prompt_len=20, chunk=8)
        gc.collect()
    spans = _profiled_spans(tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    steps = by["engine.step"]
    assert len(steps) == engine.clock
    assert [s[3]["step_num"] for s in steps] == \
        list(range(1, engine.clock + 1))
    assert all({"running", "waiting"} <= set(s[3]) for s in steps)
    # chunked prefill on: no monolithic prefill, 3 chunks per prompt
    assert "engine.prefill" not in by
    assert len(by["engine.prefill_chunk"]) == engine.n_prefill_chunks == 6
    assert all(s[3]["tokens"] == 8 for s in by["engine.prefill_chunk"])
    assert len(by["engine.dispatch"]) == engine.n_decode_steps
    assert all(s[3]["batch"] >= 1 for s in by["engine.dispatch"])
    # every blocking read: two per consume, one per finished prefill
    consumes = by["engine.consume"]
    assert all(sum(_inside(s, [c]) for s in by["engine.sync"]) == 2
               for c in consumes)
    assert all(_inside(s, consumes + by["engine.prefill_chunk"])
               for s in by["engine.sync"])
    assert len(by["engine.sync"]) == 2 * len(consumes) + 2
    for name in ("engine.admit", "engine.ensure_pages", "engine.dispatch",
                 "engine.consume", "engine.prefill_chunk"):
        assert all(_inside(s, steps) for s in by[name]), name
    assert all(_inside(s, consumes) for s in by["engine.accept"])
    assert any(s[3]["generation"] == 2 for s in by["python.gc"])


def test_gc_hook_installed_once():
    """However many engines are built, one ``gc.callbacks`` hook records
    each collection (here in the repro.obs tracer)."""
    trace_mod = sys.modules["repro.obs.trace"]  # attr is the shadow fn
    for _ in range(3):
        _run_engine(n_req=1, max_tokens=1)
    hooks = [cb for cb in gc.callbacks if cb is trace_mod._on_gc]
    assert len(hooks) == 1
    with trace() as tr:
        gc.collect()
    (ev,) = [e for e in tr.events if e["name"] == "python.gc"]
    assert ev["ph"] == "X" and ev["args"]["generation"] == 2


def test_obs_imports_no_jax():
    """The engine and dispatcher import repro.obs at module scope, so it
    stays JAX-free until a span first records."""
    code = ("import sys, repro.obs; from repro.obs import trace; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT,
                       env={**os.environ,
                            "PYTHONPATH": os.path.join(ROOT, "src")})
    assert r.returncode == 0, r.stderr[-2000:]


# =============================================================== cli glue

def test_cli_session_exports(tmp_path, capsys):
    import argparse
    obs.reset()
    ap = argparse.ArgumentParser()
    obs.add_cli_flags(ap)
    tr_path = str(tmp_path / "t.json")
    m_path = str(tmp_path / "m.json")
    args = ap.parse_args(["--trace", tr_path, "--metrics-out", m_path])
    with obs.cli_session(args):
        tr = current()
        assert tr is not None
        tr.instant("tick")
    out = capsys.readouterr().out
    assert "telemetry: trace ->" in out
    assert "telemetry: metrics ->" in out
    assert "dispatch explain:" in out
    assert json.loads(open(tr_path).read())["traceEvents"]
    assert "counters" in json.loads(open(m_path).read())
