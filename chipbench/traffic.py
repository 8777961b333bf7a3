"""The one traffic generator: turns a mix's parameters into requests.

A mix file (``traffic/<name>.json``) gives the arrival process, the
prompt lengths with their weights and the output-length distribution.
Requests come in blocks, one per span of the run: the pre-roll, the
measured window, then as many blocks as the window's size as the run
needs after it (until a request is resident for the comparison).  A block of ``n`` requests holds prompt lengths in the mix's
proportions, output lengths at ``n`` stratified quantiles of their
distribution and inter-arrival gaps at ``n`` stratified quantiles of the
exponential, scaled to fill the span, in an order the mix's
``schedule_seed`` fixes; the run's seed draws only the token ids.  So
every seed offers the window the same requests at the same times, and
runs differ only in the tokens (and weights) the seed draws.

Arrivals are open-loop: request ``i`` is due at ``due_s`` seconds after
the stream starts, whether or not earlier requests have finished.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Req:
    due_s: float
    prompt: np.ndarray          # int32 token ids
    max_tokens: int


def _counts(weights, n: int) -> list[int]:
    """Largest-remainder apportionment of ``n`` over ``weights``."""
    w = np.asarray(weights, float) / float(np.sum(weights))
    raw = w * n
    base = np.floor(raw).astype(int)
    order = np.argsort(-(raw - base), kind="stable")
    for i in order[: n - int(base.sum())]:
        base[i] += 1
    return [int(x) for x in base]


def _output_quantiles(spec: dict, n: int) -> list[int]:
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo + 1)
    elif spec["dist"] == "loguniform":
        x = np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo)))
    else:
        raise ValueError(f"unknown output distribution {spec['dist']!r}")
    return [int(min(hi, max(lo, math.floor(v)))) for v in x]


def _gaps(rate: float, n: int) -> np.ndarray:
    """Exponential inter-arrival gaps at stratified quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


class Stream:
    """An endless, seeded request stream for one traffic mix, in blocks
    that fill the spans ``spans_s`` (seconds) one after another, the last
    span repeating."""

    def __init__(self, mix: dict, seed: int, vocab_size: int,
                 spans_s=(10.0,)):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab_size)
        self.rate = float(mix["rate_per_s"])
        self.spans = [float(s) for s in spans_s]
        self._buf: list[Req] = []
        self._next_block = 0
        self._t = 0.0

    def _fill(self):
        b = self._next_block
        self._next_block += 1
        span = self.spans[min(b, len(self.spans) - 1)]
        n = max(1, round(self.rate * span))
        lengths = []
        for L, c in zip(self.mix["prompt_lengths"],
                        _counts(self.mix["prompt_weights"], n)):
            lengths += [int(L)] * c
        gaps = _gaps(self.rate, n)
        gaps *= span / gaps.sum()
        order = np.random.default_rng([int(self.mix["schedule_seed"]), b])
        lengths = order.permutation(lengths)
        outs = order.permutation(_output_quantiles(self.mix["output"], n))
        gaps = order.permutation(gaps)
        offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        rng = np.random.default_rng([self.seed & 0xFFFFFFFFFFFFFFFF, b])
        for j in range(n):
            prompt = rng.integers(0, self.vocab, int(lengths[j]),
                                  dtype=np.int32)
            self._buf.append(Req(self._t + float(offsets[j]), prompt,
                                 int(outs[j])))
        self._t += span

    def take_due(self, t_s: float) -> list[Req]:
        """Every request due at or before ``t_s`` not yet taken."""
        out = []
        while True:
            if not self._buf:
                self._fill()
            if self._buf[0].due_s > t_s:
                return out
            out.append(self._buf.pop(0))

    def next_due(self) -> float:
        if not self._buf:
            self._fill()
        return self._buf[0].due_s
