"""Measured block-shape autotuner for the TCEC kernel (paper §V discipline).

The paper's headline throughput only materializes after sweeping kernel
parameters under the shared-memory-capacity constraint (their Table 3 /
CUTLASS parameter sweep).  This module is the TPU analogue:

  * :func:`candidate_blocks` enumerates MXU-aligned ``(bm, bn, bk)`` triples
    that survive the VMEM-capacity filter (``vmem_bytes <= VMEM_BUDGET``);
  * :func:`autotune` times each surviving candidate on the real kernel
    (compiled on TPU; injectable measure function elsewhere) and picks the
    fastest;
  * winners persist to an on-disk JSON cache keyed by
    ``(backend, policy, shape-bucket)`` with an in-memory LRU in front, so
    tuned choices are reused across calls *and across processes*.

Cache format (see docs/kernels.md — "Autotuner cache"):

    {"version": 1,
     "entries": {"cpu/tcec_bf16x6/b1_m256_n256_k256":
                   {"block": [128, 128, 256], "ms": 0.41,
                    "source": "measured"}}}

Invalidation: delete the file, point the cache elsewhere, or bump
``CACHE_VERSION`` (version-mismatched files are ignored wholesale).

Tuning knobs live on :class:`repro.numerics.NumericsConfig` (see the env
registry in ``repro/numerics.py`` for the corresponding ``REPRO_TUNE*``
variables):

  * ``tune_cache`` — cache file path (default
    ``~/.cache/repro/tcec_autotune.json``).
  * ``tune="force"`` — measure even off-TPU (tests/bench).
  * ``tune="off"``   — never measure; heuristic only.

Measurement never runs inside a trace (``jit``, ``grad``, ``shard_map``):
there a kernel call returns tracers, so the timer would time tracing.  A
lookup from inside a trace that misses the cache gets the heuristic block,
unpersisted; sweep outside the trace to persist a winner.
"""
from __future__ import annotations

import json
import os
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp

from repro import numerics
from repro.core.policy import get_policy
from .tcec_matmul import VMEM_BUDGET, tcec_matmul_pallas, vmem_bytes

CACHE_VERSION = 1
CANDIDATE_TILES = (128, 256, 512)


def cache_path(cfg=None) -> str:
    return (cfg or numerics.active()).tune_cache


def _round_up(x: int, m: int) -> int:
    return max(m, ((x + m - 1) // m) * m)


def shape_bucket(B: int, M: int, N: int, K: int) -> tuple[int, int, int, int]:
    """Shapes are bucketed to their 128-padded dims: the kernel pads anyway,
    so two problems with the same padded shape share one tuned block."""
    return (max(1, B), _round_up(M, 128), _round_up(N, 128), _round_up(K, 128))


def heuristic_block(M: int, N: int, K: int,
                    policy_name: str) -> tuple[int, int, int]:
    """Largest MXU-aligned block that fits VMEM and divides the padded shape.

    The static fallback used when no measurement is available (and the
    baseline the benchmarks compare tuned choices against).
    """
    policy = get_policy(policy_name)
    best = (128, 128, 128)
    for bm in (512, 256, 128):
        for bn in (512, 256, 128):
            for bk in (512, 256, 128):
                if vmem_bytes((bm, bn, bk), policy) > VMEM_BUDGET:
                    continue
                # prefer blocks that don't overshoot the problem
                if bm <= max(M, 128) and bn <= max(N, 128) and bk <= max(K, 128):
                    cand = (bm, bn, bk)
                    if cand > best:
                        best = cand
    return best


def candidate_blocks(M: int, N: int, K: int, policy_name: str,
                     budget: int = VMEM_BUDGET) -> list[tuple[int, int, int]]:
    """MXU-aligned candidates under the VMEM budget, largest-first.

    Candidates overshooting the (128-padded) problem in any dim are dropped —
    they only add padding FLOPs, never throughput.
    """
    policy = get_policy(policy_name)
    _, pm, pn, pk = shape_bucket(1, M, N, K)
    out = []
    for bm in CANDIDATE_TILES:
        if bm > pm:
            continue
        for bn in CANDIDATE_TILES:
            if bn > pn:
                continue
            for bk in CANDIDATE_TILES:
                if bk > pk:
                    continue
                if vmem_bytes((bm, bn, bk), policy, has_bias=True) <= budget:
                    out.append((bm, bn, bk))
    out.sort(key=lambda b: (-(b[0] * b[1] * b[2]), b))
    return out or [(128, 128, 128)]


def valid_entry(entry) -> bool:
    """Schema check for one cache entry: ``{"block": [1-3 positive ints],
    "ms": None | number, ...}``.

    The cache file is shared, hand-editable state on disk — a truncated
    write, a stale schema, or plain corruption must read as a *miss* (the
    tuner re-derives the block), never as a malformed block tuple that
    trips the kernel's divisibility asserts inside a jit trace."""
    if not isinstance(entry, dict):
        return False
    block = entry.get("block")
    if not isinstance(block, (list, tuple)) or not 1 <= len(block) <= 3:
        return False
    if not all(type(v) is int and v > 0 for v in block):
        return False
    ms = entry.get("ms")
    return ms is None or isinstance(ms, (int, float))


class BlockCache:
    """On-disk JSON cache of measured block choices + in-memory LRU front.

    Reads are guarded: entries failing :func:`valid_entry` (and entries
    corrupted by the ``tuning.cache`` fault-injection site) are dropped
    and read as misses."""

    def __init__(self, path: str | None = None, capacity: int = 256):
        self.path = path or cache_path()
        self.capacity = capacity
        self._mem: OrderedDict[str, dict] = OrderedDict()
        self._disk: dict[str, dict] | None = None   # loaded lazily
        self._dirty: set[str] = set()               # keys THIS process wrote

    # ------------------------------------------------------------- disk io

    def _read_file(self) -> dict[str, dict]:
        try:
            with open(self.path) as f:
                data = json.load(f)
            if data.get("version") == CACHE_VERSION:
                return dict(data.get("entries", {}))
        except (OSError, ValueError):
            pass   # absent or corrupt file == empty cache
        return {}

    def _load_disk(self) -> dict[str, dict]:
        if self._disk is None:
            self._disk = self._read_file()
        return self._disk

    def _flush(self):
        # merge-on-write: re-read the file and overlay only the keys this
        # process measured, so concurrent tuners don't clobber each other's
        # entries (last-writer-wins per KEY, not per file)
        ours = self._load_disk()
        fresh = self._read_file()
        for key in self._dirty:
            if key in ours:
                fresh[key] = ours[key]
        fresh.update({k: v for k, v in ours.items() if k not in fresh})
        self._disk = fresh
        tmp = f"{self.path}.tmp.{os.getpid()}"
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": fresh}, f, indent=1)
        os.replace(tmp, self.path)   # atomic: concurrent readers see old/new

    # ------------------------------------------------------------- lookups

    def get(self, key: str) -> dict | None:
        if key in self._mem:
            self._mem.move_to_end(key)
            entry = self._mem[key]
        else:
            entry = self._load_disk().get(key)
        if entry is not None:
            from repro import faults
            if faults.poke("tuning.cache") is not None:
                entry = {"block": "corrupt"}   # injected corruption
            if not valid_entry(entry):
                # corrupt entry == miss: drop it from both views so the
                # tuner re-derives (and eventually re-persists) the block
                self._mem.pop(key, None)
                self._load_disk().pop(key, None)
                return None
            if key not in self._mem:
                self._put_mem(key, entry)
        return entry

    def _put_mem(self, key: str, entry: dict):
        self._mem[key] = entry
        self._mem.move_to_end(key)
        while len(self._mem) > self.capacity:
            self._mem.popitem(last=False)

    def put(self, key: str, entry: dict, persist: bool):
        self._put_mem(key, entry)
        if persist:
            self._load_disk()[key] = entry
            self._dirty.add(key)
            self._flush()


_caches: dict[str, BlockCache] = {}


def get_cache(cfg=None) -> BlockCache:
    """One shared BlockCache per path: configs with different
    ``tune_cache`` paths interleave without thrashing each other's
    in-memory LRU."""
    path = cache_path(cfg)
    cache = _caches.get(path)
    if cache is None:
        cache = _caches[path] = BlockCache(path=path)
    return cache


def _ns(backend: str, namespace: str | None) -> str:
    """Key prefix: ``backend`` or ``backend/shmap`` (per-shard tuning —
    under a mesh the kernel runs the *local* tile, so winners live in
    their own namespace and never collide with same-shaped global
    problems)."""
    return backend if namespace is None else f"{backend}/{namespace}"


def cache_key(B: int, M: int, N: int, K: int, policy_name: str,
              backend: str, namespace: str | None = None) -> str:
    b, m, n, k = shape_bucket(B, M, N, K)
    return f"{_ns(backend, namespace)}/{policy_name}/b{b}_m{m}_n{n}_k{k}"


# ------------------------------------------------------------- measurement

def _tracing() -> bool:
    """True inside a JAX trace, where array ops return tracers."""
    return isinstance(jnp.zeros(()), jax.core.Tracer)


def _should_measure(cfg=None) -> bool:
    mode = (cfg or numerics.active()).tune
    if mode == "off":
        return False
    if mode == "force":
        return True
    return jax.default_backend() == "tpu"


def _measure_block(B, M, N, K, policy_name, block, reps: int = 3,
                   interpret: bool | None = None) -> float:
    """Wall-clock one padded kernel call (ms, best of ``reps``)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bm, bn, bk = block
    m, n, k = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    shape_a = (B, m, k) if B > 1 else (m, k)
    shape_b = (B, k, n) if B > 1 else (k, n)
    a = jnp.ones(shape_a, jnp.float32)
    b = jnp.ones(shape_b, jnp.float32)
    run = lambda: tcec_matmul_pallas(a, b, policy_name=policy_name,
                                     block=block, interpret=interpret)
    jax.block_until_ready(run())   # compile / warm up
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


def _autotune_protocol(key: str, heuristic, candidates, measure,
                       cache: BlockCache | None,
                       max_candidates: int | None) -> tuple[tuple, dict]:
    """The shared cache/measure/persist protocol behind every tuner:
    cache hit -> heuristic short-circuit (never persisted, so a later TPU
    process still measures) -> candidate sweep -> persist the winner.
    ``heuristic``/``candidates`` are thunks; ``measure`` is ``block -> ms``
    or None (meaning: measurement unavailable here, as inside a trace)."""
    hit = cache.get(key)
    if hit is not None:
        return tuple(hit["block"]), {**hit, "source": "cache"}

    if measure is None or _tracing():
        block = heuristic()
        entry = {"block": list(block), "ms": None, "source": "heuristic"}
        cache.put(key, entry, persist=False)
        return block, entry

    cands = candidates()
    if max_candidates:
        cands = cands[:max_candidates]
    timings = {blk: measure(blk) for blk in cands}
    block = min(timings, key=timings.get)
    entry = {"block": list(block), "ms": timings[block], "source": "measured"}
    cache.put(key, entry, persist=True)
    return block, {**entry, "timings": {str(k): v for k, v in timings.items()}}


def autotune(B: int, M: int, N: int, K: int, policy_name: str, *,
             measure=None, cache: BlockCache | None = None, reps: int = 3,
             max_candidates: int | None = None,
             interpret: bool | None = None,
             cfg=None, namespace: str | None = None
             ) -> tuple[tuple[int, int, int], dict]:
    """Pick a block for ``(B, M, N, K)`` under ``policy_name``.

    Returns ``(block, meta)`` where ``meta["source"]`` is one of
    ``"cache"`` (hit, in-memory or disk), ``"measured"`` (fresh sweep,
    persisted), or ``"heuristic"`` (no measurement available — not
    persisted, so a later TPU process still gets to measure).

    ``measure`` is injectable: a callable ``block -> milliseconds``.  When
    ``None``, real wall-clock measurement runs iff on TPU or the numerics
    config says ``tune="force"`` (env: ``REPRO_TUNE=1``).  ``cfg`` is the
    :class:`repro.numerics.NumericsConfig` governing tune mode and cache
    path (default: the active context).
    """
    if measure is None and _should_measure(cfg):
        measure = lambda blk: _measure_block(B, M, N, K, policy_name, blk,
                                             reps=reps, interpret=interpret)
    return _autotune_protocol(
        cache_key(B, M, N, K, policy_name, jax.default_backend(), namespace),
        heuristic=lambda: heuristic_block(M, N, K, policy_name),
        candidates=lambda: candidate_blocks(M, N, K, policy_name),
        measure=measure, cache=cache or get_cache(cfg),
        max_candidates=max_candidates)


def get_block(M: int, N: int, K: int, policy_name: str,
              batch: int = 1, cfg=None,
              namespace: str | None = None) -> tuple[int, int, int]:
    """The dispatch-facing entry: tuned block if available, else heuristic.

    ``namespace="shmap"`` keys the lookup on the per-shard shape under a
    mesh (``kernels/shmap.py`` passes the local tile dims here)."""
    block, _ = autotune(batch, M, N, K, policy_name, cfg=cfg,
                        namespace=namespace)
    return block


# ----------------------------------------------------- attention namespace
#
# The fused flash-attention kernel (kernels/tcec_attention.py) has its own
# (q_block, k_block) parameter space and its own VMEM working-set model
# (attn_vmem_bytes: Q/K/V tiles + split terms + the scores tile + per-group
# accumulators).  Entries share the same JSON cache file under a distinct
# "attn" key namespace, so GEMM and attention winners never collide.

ATTN_CANDIDATE_TILES = (128, 256, 512)


def attn_heuristic_block(S: int, T: int, rep: int, hd: int, hdv: int,
                         policy_name: str) -> tuple[int, int]:
    """Largest VMEM-feasible (bq, bk) — the static fallback when no
    measurement is available.  One definition of 'feasible': the head of
    the same filtered list the tuner sweeps."""
    return attn_candidate_blocks(S, T, rep, hd, hdv, policy_name)[0]


def attn_candidate_blocks(S: int, T: int, rep: int, hd: int, hdv: int,
                          policy_name: str,
                          budget: int = VMEM_BUDGET) -> list[tuple[int, int]]:
    """VMEM-feasible (bq, bk) candidates, largest-first."""
    from .tcec_attention import attn_vmem_bytes
    policy = get_policy(policy_name)
    ps, pt = _round_up(S, 128), _round_up(T, 128)
    out = []
    for bq in ATTN_CANDIDATE_TILES:
        if bq > ps:
            continue
        for bk in ATTN_CANDIDATE_TILES:
            if bk > pt:
                continue
            if attn_vmem_bytes((bq, bk), rep, hd, hdv, policy) <= budget:
                out.append((bq, bk))
    out.sort(key=lambda b: (-(b[0] * b[1]), b))
    return out or [(128, 128)]


def attn_cache_key(B: int, Hkv: int, rep: int, S: int, T: int, hd: int,
                   hdv: int, policy_name: str, backend: str,
                   causal: bool = True, namespace: str | None = None) -> str:
    s, t = _round_up(S, 128), _round_up(T, 128)
    d, dv = _round_up(hd, 128), _round_up(hdv, 128)
    # causal is part of the key: the kernel's block-level causal skip
    # halves the work, so causal and non-causal sweeps favor different
    # blocks for the same shape
    return (f"{_ns(backend, namespace)}/attn/{policy_name}/"
            f"b{max(1, B)}_h{max(1, Hkv)}_r{rep}_s{s}_t{t}_d{d}_v{dv}"
            f"_c{int(causal)}")


def _measure_attention(B, Hkv, rep, S, T, hd, hdv, policy_name, block,
                       reps: int = 3, interpret: bool | None = None,
                       causal: bool = True) -> float:
    """Wall-clock one padded attention kernel call (ms, best of ``reps``)."""
    from .tcec_attention import tcec_attention
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    a = jnp.ones((B, S, Hkv * rep, hd), jnp.float32)
    k = jnp.ones((B, T, Hkv, hd), jnp.float32)
    v = jnp.ones((B, T, Hkv, hdv), jnp.float32)
    run = lambda: tcec_attention(a, k, v, policy=policy_name, block=block,
                                 causal=causal, interpret=interpret)
    jax.block_until_ready(run())   # compile / warm up
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


def autotune_attention(B: int, Hkv: int, rep: int, S: int, T: int, hd: int,
                       hdv: int, policy_name: str, *, causal: bool = True,
                       measure=None, cache: BlockCache | None = None,
                       reps: int = 3, max_candidates: int | None = None,
                       interpret: bool | None = None, cfg=None,
                       namespace: str | None = None
                       ) -> tuple[tuple[int, int], dict]:
    """Attention-kernel analogue of :func:`autotune`: same cache file and
    protocol (``_autotune_protocol``), attention-specific key/candidates/
    measurement."""
    if measure is None and _should_measure(cfg):
        measure = lambda blk: _measure_attention(
            B, Hkv, rep, S, T, hd, hdv, policy_name, blk, reps=reps,
            interpret=interpret, causal=causal)
    return _autotune_protocol(
        attn_cache_key(B, Hkv, rep, S, T, hd, hdv, policy_name,
                       jax.default_backend(), causal, namespace),
        heuristic=lambda: attn_heuristic_block(S, T, rep, hd, hdv,
                                               policy_name),
        candidates=lambda: attn_candidate_blocks(S, T, rep, hd, hdv,
                                                 policy_name),
        measure=measure, cache=cache or get_cache(cfg),
        max_candidates=max_candidates)


def get_attention_block(B: int, Hkv: int, rep: int, S: int, T: int, hd: int,
                        hdv: int, policy_name: str,
                        causal: bool = True, cfg=None,
                        namespace: str | None = None) -> tuple[int, int]:
    """Dispatch-facing entry for the attention kernel's (bq, bk).
    ``namespace="shmap"`` keys on the per-shard shape (local tile)."""
    block, _ = autotune_attention(B, Hkv, rep, S, T, hd, hdv, policy_name,
                                  causal=causal, cfg=cfg,
                                  namespace=namespace)
    return block


# ------------------------------------------------------- paged namespace
#
# The paged decode-attention kernel (kernels/tcec_paged_attention.py) has a
# single tunable: ``pages_per_step`` — how many KV pages each grid step
# gathers through the block table into one (G*page_size)-column VMEM tile.
# Bigger G means larger MXU tiles and fewer grid steps but a bigger VMEM
# working set (paged_vmem_bytes is the capacity filter).  Winners share the
# same JSON cache file under the ``backend/paged/...`` key namespace.

PAGED_CANDIDATE_STEPS = (1, 2, 4, 8, 16, 32)


def paged_candidate_blocks(maxp: int, ps: int, rep: int, hd: int, hdv: int,
                           policy_name: str,
                           budget: int = VMEM_BUDGET) -> list[int]:
    """VMEM-feasible pages-per-step candidates, largest-first."""
    from .tcec_paged_attention import paged_vmem_bytes
    policy = get_policy(policy_name)
    out = [g for g in PAGED_CANDIDATE_STEPS
           if g <= max(1, maxp)
           and paged_vmem_bytes(g, ps, rep, hd, hdv, policy) <= budget]
    out.sort(reverse=True)
    return out or [1]


def paged_heuristic_block(maxp: int, ps: int, rep: int, hd: int, hdv: int,
                          policy_name: str) -> int:
    """Largest feasible G whose gathered tile reaches the 128-lane MXU
    (``G*ps >= 128`` when the page budget allows), else the feasible head."""
    cands = paged_candidate_blocks(maxp, ps, rep, hd, hdv, policy_name)
    aligned = [g for g in cands if g * ps >= 128]
    return (aligned[-1] if aligned else cands[0])


def paged_cache_key(B: int, Hkv: int, rep: int, maxp: int, ps: int, hd: int,
                    hdv: int, policy_name: str, backend: str,
                    namespace: str | None = None) -> str:
    d, dv = _round_up(hd, 128), _round_up(hdv, 128)
    return (f"{_ns(backend, namespace)}/paged/{policy_name}/"
            f"b{max(1, B)}_h{max(1, Hkv)}_r{rep}_p{max(1, maxp)}_ps{ps}"
            f"_d{d}_v{dv}")


def _measure_paged(B, Hkv, rep, maxp, ps, hd, hdv, policy_name, g,
                   reps: int = 3, interpret: bool | None = None) -> float:
    """Wall-clock one paged decode-attention call (ms, best of ``reps``)."""
    from .tcec_paged_attention import tcec_paged_attention
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    NP = max(2, B * maxp + 1)
    q = jnp.ones((B, Hkv * rep, hd), jnp.float32)
    kp = jnp.ones((NP, Hkv, ps, hd), jnp.bfloat16)
    vp = jnp.ones((NP, Hkv, ps, hdv), jnp.bfloat16)
    bt = (jnp.arange(B * maxp, dtype=jnp.int32).reshape(B, maxp) % (NP - 1)
          + 1)
    lens = jnp.full((B,), maxp * ps, jnp.int32)
    run = lambda: tcec_paged_attention(q, kp, vp, bt, lens,
                                       policy=policy_name, pages_per_step=g,
                                       interpret=interpret)
    jax.block_until_ready(run())   # compile / warm up
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


def autotune_paged(B: int, Hkv: int, rep: int, maxp: int, ps: int, hd: int,
                   hdv: int, policy_name: str, *, measure=None,
                   cache: BlockCache | None = None, reps: int = 3,
                   max_candidates: int | None = None,
                   interpret: bool | None = None,
                   cfg=None, namespace: str | None = None
                   ) -> tuple[int, dict]:
    """Paged-kernel analogue of :func:`autotune`: same cache file and
    protocol, pages-per-step candidate space.  Entries store the winner as
    a one-element ``block`` list so the JSON schema stays uniform."""
    if measure is None and _should_measure(cfg):
        measure = lambda g: _measure_paged(B, Hkv, rep, maxp, ps, hd, hdv,
                                           policy_name, g, reps=reps,
                                           interpret=interpret)
    wrapped = None if measure is None else (lambda blk: measure(blk[0]))
    block, meta = _autotune_protocol(
        paged_cache_key(B, Hkv, rep, maxp, ps, hd, hdv, policy_name,
                        jax.default_backend(), namespace),
        heuristic=lambda: (paged_heuristic_block(maxp, ps, rep, hd, hdv,
                                                 policy_name),),
        candidates=lambda: [(g,) for g in paged_candidate_blocks(
            maxp, ps, rep, hd, hdv, policy_name)],
        measure=wrapped, cache=cache or get_cache(cfg),
        max_candidates=max_candidates)
    return block[0], meta


def get_paged_block(B: int, Hkv: int, rep: int, maxp: int, ps: int, hd: int,
                    hdv: int, policy_name: str, cfg=None,
                    namespace: str | None = None) -> int:
    """Dispatch-facing entry for the paged kernel's pages-per-step.
    ``namespace="shmap"`` keys on the per-shard shape (local tile)."""
    g, _ = autotune_paged(B, Hkv, rep, maxp, ps, hd, hdv, policy_name,
                          cfg=cfg, namespace=namespace)
    return g
