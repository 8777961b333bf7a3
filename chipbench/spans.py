"""The program's own host spans in a traced run's profile.

The serving engine records each phase of ``Engine.step`` as a host event
of the profiler's trace (``engine.step``, and below it ``engine.admit``,
``engine.prefill_chunk``, ``engine.dispatch``, ``engine.consume``,
``engine.sync`` where the host blocks on the device, ...), and each
garbage collection as ``python.gc``.  This module finds the traced run's
``.xplane.pb``, reads those events as :class:`trace_reduce.Event`, and
attributes the device's idle gaps to the innermost span open at their
middle.  A program that records no such spans gives none, and what is
built on them returns None.
"""
from __future__ import annotations

import collections
import glob
import os

from chipbench import spec, trace_reduce

PREFIXES = ("engine.", "python.")
TRACE_ROOT = spec.ROOT / ".chipbench_out" / "trace"
WINDOW = "chipbench.traced"


def load(path) -> tuple:
    """``(window, spans)`` of one profile: the span of its
    ``chipbench.traced`` annotation on the trace clock (None without
    one), and its program spans sorted by start."""
    from jax.profiler import ProfileData
    win, out = None, []
    for plane in ProfileData.from_file(str(path)).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append(trace_reduce.Event(
                        e.name, e.start_ns, e.start_ns + e.duration_ns))
                elif e.name == WINDOW:
                    win = (e.start_ns, e.start_ns + e.duration_ns)
    out.sort(key=lambda e: e.start)
    return win, out


def program_spans(ctx) -> list:
    """The program spans of the traced run ``ctx`` reads: those of the
    newest profile under :data:`TRACE_ROOT`, if its traced window is
    ``ctx.win``; else none."""
    paths = glob.glob(os.path.join(str(TRACE_ROOT), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return []
    win, spans = load(max(paths, key=os.path.getmtime))
    return spans if win == tuple(ctx.win) else []


def step_self_ms(spans, lo: float, hi: float) -> list[float]:
    """Host self time, in ms, of each ``engine.step`` span lying wholly
    inside [lo, hi): its length less the ``engine.sync`` spans nested in
    it."""
    syncs = [s for s in spans if s.name == "engine.sync"]
    out = []
    for st in spans:
        if st.name != "engine.step" or st.start < lo or st.end > hi:
            continue
        blocked = sum(s.dur for s in syncs
                      if s.start >= st.start and s.end <= st.end)
        out.append((st.dur - blocked) * 1e-6)
    return out


def idle_by_phase(ctx, spans) -> dict:
    """Seconds of device idle time in the traced span by the innermost
    host span open at each gap's middle (the benchmark's ``chipbench.*``
    annotations merged with the program's spans), averaged over chips,
    largest first."""
    notes = sorted(list(ctx.trace.annotations) + list(spans),
                   key=lambda e: e.start)
    lo, hi = ctx.win
    chips = max(1, len(ctx.trace.ops))
    idle = collections.Counter()
    for evs in ctx.trace.ops.values():
        for s, t in trace_reduce.gaps(evs, lo, hi):
            idle[trace_reduce.label(notes, s, t)] += (t - s) * 1e-9 / chips
    return dict(idle.most_common())
