"""Reads the per-layer metrics of a traced run.

Builds one :class:`Ctx` from the run's step records (host clock, taken
around each ``Engine.step``), the engine's counters at the start and end
of the traced span, and the reduced device trace; then calls the reader
``read(ctx)`` of each per-layer metric of the cell, found by name in
``metrics/<name>.py``.  A reader that finds nothing returns None and its
metric is left out of the result line.
"""
from __future__ import annotations

import collections
import dataclasses
import re
import sys

from chipbench import peaks, spec, trace_reduce, work

#: how the fused kernels appear among a device's ``XLA Ops``
KERNEL_OPS = {
    "matmul": re.compile(r"^tcec_matmul_pallas(\.\d+)?$"),
    "flash": re.compile(r"^tcec_attention_pallas(\.\d+)?$"),
    "paged": re.compile(r"^tcec_paged_attention_pallas(\.\d+)?$"),
}


@dataclasses.dataclass
class Ctx:
    steps: list              # step records inside the traced span
    dims: work.Dims
    vocab: int               # the published vocabulary
    chunk: int               # prefill chunk, tokens
    peak: dict               # peaks of one chip
    chips: int
    trace: trace_reduce.Trace
    win: tuple               # traced span on the trace clock, ns
    stats0: dict             # engine counters at the start of the span
    stats1: dict             # and at its end

    @property
    def window_s(self) -> float:
        return (self.win[1] - self.win[0]) * 1e-9

    def step_flops(self, steps) -> float:
        return sum(work.step_flops(self.dims, self.vocab, s) for s in steps)

    def kernel_min_s(self, kernel: str) -> tuple[float, int]:
        """Least time the chips could take for ``kernel``'s work in the
        span, summed event by event over every chip
        (:func:`work.per_chip`), and the number of events."""
        total, events = 0.0, 0
        for s in self.steps:
            calls = work.step_kernel_calls(self.dims, s, self.chunk)[kernel]
            for flops, nbytes in work.per_chip(calls, self.chips):
                total += max(flops / self.peak["bf16_flops_per_s"],
                             nbytes / self.peak["hbm_bytes_per_s"])
                events += 1
        return total, events

    def kernel_events(self, kernel: str) -> list:
        pat = KERNEL_OPS[kernel]
        lo, hi = self.win
        return [e for evs in self.trace.ops.values() for e in evs
                if pat.search(e.name) and e.start >= lo and e.end <= hi]

    def roofline(self, kernel: str):
        """Share (%) of the roofline: least time over device time, both
        summed over the cell's chips.  None where the trace holds another
        number of the kernel's events than :func:`work.step_kernel_calls`
        counts calls, times the chips: the program then sends it other
        work than that model of its dispatch knows, and the share would
        count work the kernel did not do, or miss work it did."""
        evs = self.kernel_events(kernel)
        if not evs:
            return None
        min_s, events = self.kernel_min_s(kernel)
        if events != len(evs):
            print(f"chipbench: {kernel}: {len(evs)} events in the trace, "
                  f"{events} in the dispatch model over {self.chips} "
                  "chip(s); no roofline", file=sys.stderr, flush=True)
            return None
        dev_s = sum(e.dur for e in evs) * 1e-9
        return 100.0 * min_s / dev_s

    def idle_share(self):
        lo, hi = self.win
        shares = [1.0 - trace_reduce.busy(evs, lo, hi) / (hi - lo)
                  for evs in self.trace.ops.values()]
        return 100.0 * sum(shares) / len(shares) if shares else None


def decode_steps(steps):
    return [s for s in steps if s["chunks"] == 0 and s["decode"] > 0]


def prefill_steps(steps):
    return [s for s in steps if s["chunks"] > 0]


def breakdown(ctx: Ctx) -> dict:
    lo, hi = ctx.win
    per_op = collections.Counter()
    for evs in ctx.trace.ops.values():
        for e in trace_reduce.leaves(evs):
            if e.start >= lo and e.end <= hi:
                per_op[e.name] += e.dur * 1e-9 / len(ctx.trace.ops)
    idle = []
    for evs in ctx.trace.ops.values():
        for s, t in trace_reduce.gaps(evs, lo, hi):
            idle.append((trace_reduce.label(ctx.trace.annotations, s, t),
                         (t - s) * 1e-9))
    idle.sort(key=lambda x: -x[1])
    return {"device_ops": [[n, v] for n, v in per_op.most_common(10)],
            "idle_gaps": [[n, v] for n, v in idle[:10]]}


def read_all(cell: dict, cfg, steps: list, tstate: dict, trace_dir,
             devices, sizes: dict):
    """(metrics, breakdown, {"busy_s", "window_s"}) of a traced run."""
    tr = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    ids = sorted(tr.ops)[: len(devices)]
    tr.ops = {i: tr.ops[i] for i in ids}
    ctx = Ctx(steps=steps, dims=work.Dims.of(cfg), vocab=cfg.vocab_size,
              chunk=int(cell["traffic"]["engine"]["chunked_prefill"]),
              peak=peaks.for_kind(devices[0].device_kind),
              chips=len(devices), trace=tr,
              win=trace_reduce.window(tr.annotations),
              stats0=tstate["stats0"], stats1=tstate["stats1"])
    out = {}
    for m in cell["per_layer"]:
        mod = spec.load_module(spec.metric_path(m["name"]),
                               "chipbench_metric_" + m["name"])
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = float(v)
    lo, hi = ctx.win
    busy = sum(trace_reduce.busy(evs, lo, hi) for evs in tr.ops.values())
    busy_s = busy * 1e-9 / max(1, len(tr.ops))
    return out, breakdown(ctx), {"busy_s": busy_s, "window_s": ctx.window_s}
