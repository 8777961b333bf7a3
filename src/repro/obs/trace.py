"""Spans on two clocks, and the Chrome-trace event buffer.

:func:`span` is the one helper every instrumentation point calls.  It
records one phase of the program in two sinks:

  * the JAX profiler's own trace, always, as a
    ``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` when given
    a ``step_num``), on the clock the device ops share, with the span's
    args as event stats.  With no profiler session running this costs
    one C++ ``TraceMe`` check;
  * the :class:`Tracer` installed by ``trace()``, when there is one, as a
    Chrome-trace complete event on that tracer's clock.

:func:`install_gc_hook` adds every garbage collection to both sinks as a
``python.gc`` span, from a ``gc.callbacks`` hook installed once per
process.  ``jax.profiler`` is imported on first use, so importing this
module pulls in no JAX.

``trace()`` installs a :class:`Tracer` for its dynamic extent (innermost
wins — the same precedence discipline as ``numerics.use`` and
``faults.use``); instrumented code asks :func:`current` for the active
tracer, and request events and latency samples are skipped when there
isn't one.

Tracer events follow the Chrome trace event format (the JSON Perfetto
and ``chrome://tracing`` load directly):

  * ``Tracer.span(name)`` — a ``ph:"X"`` complete event with microsecond
    ``ts``/``dur``.  The context manager yields a mutable args dict, so
    annotations computed *inside* the block land on the exported event.
  * ``instant(name)`` — a ``ph:"i"`` thread-scoped instant.
  * ``async_begin/instant/end(name, id)`` — ``ph:"b"/"n"/"e"`` async
    events keyed by id: one per *request*, spanning its whole lifetime
    across engine steps (enqueue -> admission -> ... -> finish), however
    many spans interleave in between.

Export: :meth:`Tracer.export` writes ``{"traceEvents": [...]}`` JSON, or
one event per line when the path ends in ``.jsonl``.  The engine's
latency *distributions* (queue-wait, TTFT, TPOT) are not derived from
the events — instrumentation records them straight into
``obs.metrics`` histograms while the tracer is active.

The tracer's clock is injectable (``Tracer(clock=...)``) so tests drive
spans deterministically; the default is ``time.perf_counter``.
"""
from __future__ import annotations

import contextlib
import gc
import json
import threading
import time

_TLS = threading.local()
_LAST_LOCK = threading.Lock()
_LAST = None


class Tracer:
    """An event buffer plus the clock it timestamps against."""

    def __init__(self, clock=None):
        self._clock = clock if clock is not None else time.perf_counter
        self._t0 = self._clock()
        self._lock = threading.Lock()
        self._tids: dict[int, int] = {}
        self.events: list[dict] = []

    def now(self) -> float:
        """Seconds on this tracer's clock — what instrumentation uses for
        latency arithmetic (monotonic; not wall time)."""
        return self._clock()

    def _ts(self) -> float:
        return (self._clock() - self._t0) * 1e6     # microseconds

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            return self._tids.setdefault(ident, len(self._tids))

    def _emit(self, ev: dict):
        with self._lock:
            self.events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "span", **args):
        """Complete-event span; yields the event's mutable args dict."""
        t0 = self._ts()
        a = dict(args)
        try:
            yield a
        finally:
            self.complete(name, t0, cat, a)

    def complete(self, name: str, t0: float, cat: str, args: dict):
        """A complete event from ``t0`` (this tracer's microseconds) to
        now."""
        self._emit({"name": name, "cat": cat, "ph": "X", "ts": t0,
                    "dur": self._ts() - t0, "pid": 0,
                    "tid": self._tid(), "args": args})

    def instant(self, name: str, cat: str = "event", **args):
        self._emit({"name": name, "cat": cat, "ph": "i", "s": "t",
                    "ts": self._ts(), "pid": 0, "tid": self._tid(),
                    "args": dict(args)})

    def async_begin(self, name: str, aid, cat: str = "request", **args):
        self._emit({"name": name, "cat": cat, "ph": "b", "id": aid,
                    "ts": self._ts(), "pid": 0, "tid": self._tid(),
                    "args": dict(args)})

    def async_instant(self, name: str, aid, cat: str = "request", **args):
        self._emit({"name": name, "cat": cat, "ph": "n", "id": aid,
                    "ts": self._ts(), "pid": 0, "tid": self._tid(),
                    "args": dict(args)})

    def async_end(self, name: str, aid, cat: str = "request", **args):
        self._emit({"name": name, "cat": cat, "ph": "e", "id": aid,
                    "ts": self._ts(), "pid": 0, "tid": self._tid(),
                    "args": dict(args)})

    def chrome(self) -> dict:
        """The buffer as a Chrome-trace/Perfetto JSON object."""
        with self._lock:
            return {"traceEvents": list(self.events),
                    "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the events to ``path``: Chrome-trace JSON, or JSONL (one
        event per line) when the path ends in ``.jsonl``."""
        path = str(path)
        if path.endswith(".jsonl"):
            with self._lock:
                events = list(self.events)
            with open(path, "w") as f:
                for ev in events:
                    f.write(json.dumps(ev, sort_keys=True, default=str))
                    f.write("\n")
        else:
            with open(path, "w") as f:
                json.dump(self.chrome(), f, sort_keys=True, default=str)
        return path


# ----------------------------------------------------------- the context

def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


@contextlib.contextmanager
def trace(tracer: Tracer | None = None, clock=None):
    """Install a tracer for the dynamic extent; yields it.  On exit the
    tracer becomes the process's *last* tracer so ``obs.export(path)``
    can write it out after the traced region ends."""
    global _LAST
    tr = tracer if tracer is not None else Tracer(clock=clock)
    st = _stack()
    st.append(tr)
    try:
        yield tr
    finally:
        st.pop()
        with _LAST_LOCK:
            _LAST = tr


def current() -> Tracer | None:
    """The innermost active tracer on this thread, or None — the gate
    every instrumentation point checks first."""
    st = getattr(_TLS, "stack", None)
    return st[-1] if st else None


def last() -> Tracer | None:
    """The active tracer if any, else the most recently exited one."""
    cur = current()
    if cur is not None:
        return cur
    with _LAST_LOCK:
        return _LAST


# ------------------------------------------------------ two-sink spans

_PROFILER = None


def _profiler():
    """``jax.profiler``, imported on first use."""
    global _PROFILER
    if _PROFILER is None:
        from jax import profiler
        _PROFILER = profiler
    return _PROFILER


class _Span:
    __slots__ = ("_note", "_obs", "args")

    def __init__(self, name: str, step_num, args: dict):
        prof = _profiler()
        if step_num is None:
            self._note = prof.TraceAnnotation(name, **args)
        else:
            self._note = prof.StepTraceAnnotation(name, step_num=step_num,
                                                  **args)
            args["step_num"] = step_num
        tr = current()
        self._obs = None if tr is None else tr.span(name, "engine", **args)
        self.args = args

    def __enter__(self) -> dict:
        self._note.__enter__()
        if self._obs is None:
            return self.args
        return self._obs.__enter__()

    def __exit__(self, *exc):
        try:
            if self._obs is not None:
                self._obs.__exit__(*exc)
        finally:
            self._note.__exit__(*exc)
        return False


def span(name: str, *, step_num: int | None = None, **args) -> _Span:
    """A span around one phase of the program, in the profiler's trace
    always and in the active :class:`Tracer` when there is one.

    ``args`` become the profiler event's stats and the tracer event's
    args; they are built whether or not either sink records, so pass
    only what is cheap to compute.  ``step_num`` makes the profiler
    event a step annotation.  The context manager yields the tracer
    event's mutable args dict (a throwaway dict when no tracer is
    active)."""
    return _Span(name, step_num, args)


# ------------------------------------------------ garbage collections

_GC_OPEN = None     # (profiler note or None, tracer or None, tracer ts)


def _on_gc(phase: str, info: dict):
    global _GC_OPEN
    if phase == "start":
        prof = _profiler()
        note = None
        if prof.TraceAnnotation.is_enabled():
            note = prof.TraceAnnotation("python.gc",
                                        generation=info["generation"])
            note.__enter__()
        tr = current()
        if note is not None or tr is not None:
            _GC_OPEN = (note, tr, None if tr is None else tr._ts())
        return
    if _GC_OPEN is None:
        return
    note, tr, t0 = _GC_OPEN
    _GC_OPEN = None
    if tr is not None:
        tr.complete("python.gc", t0, "gc",
                    {"generation": info["generation"],
                     "collected": info["collected"]})
    if note is not None:
        note.__exit__(None, None, None)


def install_gc_hook():
    """Record every garbage collection as a ``python.gc`` span (arg
    ``generation``) in both sinks.  Idempotent: the hook is installed
    once per process however often this is called."""
    if _on_gc not in gc.callbacks:
        _profiler()         # never import from inside a collection
        gc.callbacks.append(_on_gc)
