"""The program's host spans: step self time, idle gaps labelled by the
innermost phase, and a reader that finds nothing in a trace without
them."""
import pathlib
import shutil
import types

import pytest

from chipbench import spans, spec, trace_reduce as tr

E = tr.Event
RECORDED = pathlib.Path(__file__).parent / "data" / "decode.xplane.pb"


def _reader():
    return spec.load_module(spec.metric_path("engine.host_ms"),
                            "chipbench_metric_engine.host_ms")


def test_step_self_time_subtracts_nested_syncs():
    evs = [E("engine.step", 0, 100e6), E("engine.consume", 60e6, 95e6),
           E("engine.sync", 60e6, 90e6), E("engine.sync", 10e6, 12e6),
           E("engine.step", 100e6, 150e6),          # no sync: all host
           E("engine.sync", 160e6, 170e6),          # outside any step
           E("engine.step", 190e6, 260e6)]          # crosses the window
    got = spans.step_self_ms(evs, 0, 250e6)
    assert got == pytest.approx([100 - 30 - 2, 50])
    assert spans.step_self_ms([E("chipbench.step", 0, 1e6)], 0, 2e6) == []


def test_idle_by_phase_takes_the_innermost_program_span():
    ops = [E("fusion", 0, 10), E("fusion", 30, 60), E("fusion", 70, 100)]
    notes = [E("chipbench.traced", 0, 100), E("chipbench.step", 5, 65),
             E("chipbench.poll", 65, 72)]
    program = [E("engine.step", 6, 64), E("engine.consume", 8, 25),
               E("engine.sync", 8, 12), E("engine.accept", 12, 24)]
    ctx = types.SimpleNamespace(trace=tr.Trace({0: ops}, notes),
                                win=(0, 100))
    idle = spans.idle_by_phase(ctx, program)
    # gap (10, 30): middle 20 lies in engine.accept, inside consume and
    # step; gap (60, 70): middle 65 in chipbench.poll, after the step
    assert idle == pytest.approx({"engine.accept": 20e-9,
                                  "chipbench.poll": 10e-9})
    # without the program's spans the benchmark's annotation labels it
    assert spans.idle_by_phase(ctx, []) == pytest.approx(
        {"chipbench.step": 20e-9, "chipbench.poll": 10e-9})


def test_reader_finds_nothing_without_program_spans(tmp_path, monkeypatch):
    """The recorded trace holds the benchmark's annotations but no
    program span, as a program without them gives: no reading."""
    dst = tmp_path / "code" / "plugins" / "profile" / "run"
    dst.mkdir(parents=True)
    shutil.copy(RECORDED, dst / "host.xplane.pb")
    monkeypatch.setattr(spans, "TRACE_ROOT", tmp_path)
    win, found = spans.load(dst / "host.xplane.pb")
    assert win == tr.window(tr.load(RECORDED).annotations)
    assert found == []
    assert _reader().read(types.SimpleNamespace(win=win)) is None


def test_reader_reads_a_profiled_engine(tmp_path, monkeypatch):
    """A smoke-width engine profiled on the CPU inside a
    ``chipbench.traced`` annotation: the reader finds its steps, and
    only while the window matches."""
    import jax
    import numpy as np
    from repro import numerics
    from repro.configs import get_smoke_config
    from repro.models import get_model
    from repro.serving import Engine, SamplingParams
    cfg = get_smoke_config("qwen3-0.6b")
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    engine = Engine(cfg, params, max_slots=2, num_pages=32, page_size=8,
                    numerics_config=numerics.active().replace(
                        chunked_prefill=8))
    rng = np.random.default_rng(0)
    for i in range(2):
        engine.add_request(rng.integers(0, cfg.vocab_size, 16),
                           SamplingParams(max_tokens=4, seed=i))
    monkeypatch.setattr(spans, "TRACE_ROOT", tmp_path)
    with jax.profiler.trace(str(tmp_path / "cell")):
        with jax.profiler.TraceAnnotation("chipbench.traced"):
            engine.run()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    win, found = spans.load(path)
    steps = [s for s in found if s.name == "engine.step"]
    assert len(steps) == engine.clock
    ctx = types.SimpleNamespace(win=win, trace=tr.Trace({}, []))
    host_ms = _reader().read(ctx)
    self_ms = spans.step_self_ms(found, *win)
    assert len(self_ms) == engine.clock
    assert host_ms == pytest.approx(sum(self_ms) / len(self_ms))
    assert 0 < host_ms < max(s.dur for s in steps) * 1e-6
    ctx.win = (win[0], win[1] + 1)
    assert _reader().read(ctx) is None
