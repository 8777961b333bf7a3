"""From a profiler trace (``.xplane.pb``) to device intervals and labels.

Read with ``jax.profiler.ProfileData``.  A device plane is one whose name
is ``/device:TPU:`` followed by a number; its operations are the events
of its ``XLA Ops`` line, named by the op's own HLO name (the part of the
event's text before `` = ``).  Host annotations are the events whose
names start with ``chipbench.`` on any host line.  All times are in
nanoseconds on the trace's own clock, which both kinds of plane share.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ANNOTATION = "chipbench."


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict            # device id -> [Event], sorted by start
    annotations: list    # [Event] host annotations, sorted by start


def op_name(text: str) -> str:
    """An op's own name: the trace gives ``%name = type op(operands)``,
    whose operands may name other ops."""
    return text.split(" = ", 1)[0].lstrip("%")


def leaves(events) -> list:
    """The events that enclose no other event (a ``while`` loop on the
    ops line encloses the ops of its body)."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    out = []
    for i, e in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1].start < e.end:
            continue
        out.append(e)
    return out


def find_xplane(trace_dir) -> str:
    paths = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, notes = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    Event(op_name(e.name), e.start_ns,
                          e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not m:
                notes.extend(Event(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(ANNOTATION))
    for evs in ops.values():
        evs.sort(key=lambda e: e.start)
    notes.sort(key=lambda e: e.start)
    return Trace(ops, notes)


def union(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The merged intervals covered by ``events``, clipped to [lo, hi)."""
    out = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def busy(events, lo: float, hi: float) -> float:
    return sum(t - s for s, t in union(events, lo, hi))


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals of [lo, hi) in which no event runs."""
    out, cur = [], lo
    for s, t in union(events, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if cur < hi:
        out.append((cur, hi))
    return out


def label(annotations, s: float, t: float) -> str:
    """The innermost ``chipbench.*`` annotation open at the middle of
    [s, t), or ``"none"``."""
    mid = 0.5 * (s + t)
    best = None
    for a in annotations:
        if a.start > mid:
            break
        if a.end > mid and (best is None or a.dur < best.dur):
            best = a
    return best.name if best else "none"


def window(annotations, name: str = "chipbench.traced") -> tuple:
    """The traced window: the span of the annotation ``name``."""
    for a in annotations:
        if a.name == name:
            return a.start, a.end
    raise ValueError(f"no {name!r} annotation in the trace")
