"""Continuous-batching serving engine over the paged KV cache.

The legacy ``launch/serve.py`` loop was a research artifact: same-length
prompts only, prefill via P sequential decode steps, a dense per-batch
cache, one batch-wide sampling mode, and exactly ``gen_len`` tokens for
everyone.  This engine serves a *stream* of requests:

  * **admission**: waiting requests are admitted FIFO whenever a slot and
    enough pages are free (``scheduler.py``); admissions with the same
    padded prompt length prefill together as one batch;
  * **prefill**: ONE jitted sequence-level forward (``models.lm.prefill``,
    through the fused sdpa route) returns last-token logits and every
    layer's K/V, which are scattered into freshly allocated pages — no
    more O(P) decode-step prompt loops;
  * **decode**: one jitted step advances *every* in-flight slot — whatever
    mix of requests, depths, and sampling parameters is resident — through
    ``models.lm.decode_step_paged`` (the paged TCEC kernel via
    ``dispatch.attention_decode`` when eligible, the page-gather fallback
    otherwise) and one vectorized :func:`serving.sampling.sample` call;
  * **completion**: stop tokens / ``max_tokens`` finish a request on the
    host; its slot and pages recycle into the next admission immediately —
    the batch never drains to a barrier;
  * **preemption**: when the pool runs dry, the youngest running request
    is evicted (recompute-style: its pages are freed, its tokens kept) and
    re-admitted later.

Numerics contract (tests/test_serving.py): with the paged kernel hatch
closed (CPU default), greedy engine output is **token-identical** to the
dense-cache ``launch.serve.generate_dense`` path — the page gather feeds
bitwise the same attend as the dense cache.

Resilience contract (docs/robustness.md, tests/test_faults.py): requests
finish with a :class:`~repro.serving.errors.FinishReason`; admission is
bounded (``max_waiting`` -> :class:`EngineOverloaded`) and validated
(:class:`RequestRejected`); per-request deadlines are enforced against
the engine's step clock; the jitted decode step returns a per-slot
``isfinite`` guard bit, and a tripped step re-runs ONCE under the
XLA-fallback numerics scope before any slot is failed with
``finish_reason="error"``; a preemption storm parks its victims
(``max_preemptions``) instead of livelocking.  Every recovery path is
fault-injectable via :mod:`repro.faults`.
"""
from __future__ import annotations

import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults, numerics
from repro.models import get_model
from repro.obs import metrics as _obs_metrics
from repro.obs.trace import current as _current_tracer
from repro.obs.trace import install_gc_hook, span as _span
from . import sampling
from .errors import (EngineOverloaded, FinishReason, RequestRejected,
                     RequestResult)
from .kv_cache import (DEFAULT_PAGE_SIZE, PagePool, inverse_permutation,
                       load_pages_into_scratch, permute_pages,
                       write_prompt_pages, write_span_pages)
from .prefix_cache import PrefixCache
from .sampling import SamplingParams
from .scheduler import Request, RequestState, Scheduler


# live engines, summed into repro.obs snapshots at read time (weak refs:
# registering here never keeps a dropped engine's cache pools alive)
_LIVE_ENGINES: "weakref.WeakSet[Engine]" = weakref.WeakSet()


def _engines_source() -> dict:
    out: dict[str, int] = {}
    for eng in list(_LIVE_ENGINES):
        for k, v in eng.stats().items():
            if k != "breaker":          # process-wide: its own source
                out[k] = out.get(k, 0) + int(v)
    return out


_obs_metrics.register_source("serving/engine", _engines_source)


def _pool_spec(shape, mesh):
    """PartitionSpec for one page-pool leaf: K/V leaves ``(nL, NP, Hkv,
    ps, hd)`` put KV heads on ``model`` when divisible (the paged plan's
    layout), else head_dim (always a multiple of 16 in the zoo —
    ``parallel/sharding.py``'s cache convention); latent leaves ``(nL,
    NP, ps, d)`` shard ``d``; anything that does not divide stays
    replicated."""
    from jax.sharding import PartitionSpec as P
    msize = mesh.shape["model"] if "model" in mesh.axis_names else 1
    dims = [None] * len(shape)
    if msize > 1:
        if len(shape) == 5 and shape[2] % msize == 0:
            dims[2] = "model"
        elif shape[-1] % msize == 0:
            dims[-1] = "model"
    return P(*dims)


class Engine:
    """Continuous-batching engine for the KV-cache model families
    (``dense``/``moe``, including MLA and sliding-window variants).

    max_slots: decode batch width (static — inactive slots are masked).
    num_pages: pool size including the reserved scrap page 0.
    page_size: tokens per page.
    max_pages_per_slot: block-table width; a request that outgrows it is
        finished early (length cap), like any server's max context.
    max_waiting: waiting-queue bound; ``add_request`` past it raises
        :class:`EngineOverloaded` (None = unbounded).
    max_preemptions: evictions before a request is parked as a
        preemption-storm victim (None = never park).
    cache_dtype: page-pool element dtype (None = the family default,
        bfloat16).  The shared-prefix parity contract needs float32: a
        reused page's K/V must be bitwise what a fresh prefill would
        compute, and the bf16 round-trip loses that.

    Three serving knobs ride on the pinned
    :class:`~repro.numerics.NumericsConfig` (``REPRO_PREFIX_CACHE``,
    ``REPRO_CHUNKED_PREFILL``, ``REPRO_ASYNC_SCHED``); all default off,
    and with all three off every code path below is byte-identical to
    the legacy single-shot engine.
    """

    def __init__(self, cfg, params, *, max_slots: int = 4,
                 num_pages: int | None = None,
                 page_size: int = DEFAULT_PAGE_SIZE,
                 max_pages_per_slot: int | None = None,
                 max_waiting: int | None = None,
                 max_preemptions: int | None = 8,
                 numerics_config: numerics.NumericsConfig | None = None,
                 cache_dtype=None,
                 mesh=None):
        # the engine's kernel-dispatch recipe is pinned at construction:
        # every jitted step runs under this scope, so an ambient
        # numerics.use(...) entered mid-serve can't flip an in-flight
        # trace's dispatch decisions out from under the KV cache
        self.numerics_config = numerics_config or numerics.active()
        # likewise the mesh: captured from the installed context (or taken
        # explicitly) at construction.  Every jitted step then traces
        # under it, so paged decode routes through the shard_map wrapper
        # (kernels/shmap.py) and the page pools live sharded on device —
        # KV heads on the "model" axis, tables/lengths device-local.
        from repro.parallel import ctx as _pctx
        self.mesh = mesh if mesh is not None else _pctx.current_mesh()
        model = get_model(cfg)
        if model.decode_step_paged is None:
            raise ValueError(
                f"family {cfg.family!r} has no paged decode path; use "
                "launch.serve.generate_dense")
        if num_pages is None:
            num_pages = 1 + max_slots * 32
        if max_pages_per_slot is None:
            max_pages_per_slot = min(64, num_pages - 1)
        self.cfg = cfg
        self.params = params
        self.model = model
        self.pool = PagePool(num_pages, page_size)
        self.sched = Scheduler(self.pool, max_slots,
                               max_preemptions=max_preemptions)
        self.max_slots = max_slots
        self.max_pages_per_slot = max_pages_per_slot
        self.max_waiting = max_waiting
        # the deadline clock: one tick per step() (plus injected
        # decode.slow penalties) — deterministic, no wall-clock reads
        self.clock = 0
        # the one-shot re-run recipe for non-finite decode steps: same
        # policy math on the XLA term-expansion path, no fused kernels
        self._fallback_numerics = self.numerics_config.replace(enabled=False)
        self._stats = {"guard_trips": 0, "fallback_reruns": 0,
                       "numerics_errors": 0, "rejections": 0, "overloads": 0,
                       "timeouts": 0, "length_caps": 0, "prefill_faults": 0,
                       "prefix_hits": 0, "prefix_tokens_reused": 0,
                       "cow_splits": 0, "prefix_evictions": 0}
        kw = {} if cache_dtype is None else {"dtype": cache_dtype}
        self.pools = model.init_paged_cache(num_pages, page_size, **kw)
        if self.mesh is not None:
            self.pools = jax.device_put(self.pools, self._pool_shardings())
        # host mirrors of the per-slot device state
        self.block_tables = np.zeros((max_slots, max_pages_per_slot),
                                     np.int32)
        self.lengths = np.zeros((max_slots,), np.int32)
        self.next_tok = np.zeros((max_slots,), np.int32)
        self.temps = np.zeros((max_slots,), np.float32)
        self.topks = np.zeros((max_slots,), np.int32)
        self.topps = np.ones((max_slots,), np.float32)
        self.keys = jnp.zeros((max_slots, 2), jnp.uint32)
        self._requests: dict[int, Request] = {}
        # donate the pool buffers (arg 1): every step rebinds self.pools,
        # so off-CPU the page update runs in place instead of copying the
        # whole cache per token (CPU XLA lacks donation and would warn)
        donate = () if jax.default_backend() == "cpu" else (1,)
        self._decode = jax.jit(functools.partial(_decode_and_sample,
                                                 model=model, cfg=cfg),
                               donate_argnums=donate)
        self._prefill = jax.jit(lambda p, toks: model.prefill(p, toks))
        self._prefill_chunk = jax.jit(
            lambda p, cache, toks, start: model.prefill_chunk(
                p, cache, toks, start))
        # serving knobs ride on the pinned numerics config; chunk size is
        # rounded UP to a page multiple so every chunk boundary is also a
        # page boundary (the span scatter stays whole-page)
        self.chunk_tokens = 0
        if self.numerics_config.chunked_prefill > 0:
            self.chunk_tokens = (-(-self.numerics_config.chunked_prefill
                                   // page_size)) * page_size
        self.async_sched = bool(self.numerics_config.async_sched)
        self.prefix = (PrefixCache(self.pool)
                       if self.numerics_config.prefix_cache else None)
        if self.prefix is not None:
            self.sched.evict_cb = self._evict_prefix
        # async overlap: the dispatched-but-unconsumed decode step, plus
        # double-buffered host staging for its integer/float inputs (the
        # mirrors may be mutated for step N+1 while step N is in flight)
        self._inflight = None
        self._staging = [
            {name: np.zeros_like(getattr(self, name))
             for name in ("block_tables", "lengths", "next_tok",
                          "temps", "topks", "topps")}
            for _ in range(2)]
        self.n_decode_steps = 0
        self.n_decode_steps_in_place = 0
        # numerics config -> whether its decode program keeps the page
        # pools in place (a trace-time decision, fixed per config)
        self._in_place: dict = {}
        self.n_prefills = 0
        self.n_prefill_chunks = 0
        _LIVE_ENGINES.add(self)
        install_gc_hook()

    def _pool_shardings(self):
        """Multi-device pool layout: shard each page pool's KV-head dim
        on the ``model`` axis when it divides — the same layout
        ``kernels/shmap.py``'s paged plan shards the kernel over, so the
        decode step never reshards the cache.  When the head count does
        not divide (kv_heads < model size), fall back to sharding head_dim
        (axis -1) — the KV-cache convention of ``parallel/sharding.py`` —
        so pool capacity still scales with TP; the fused kernel declines
        for that layout and the XLA gather fallback carries the sharding.
        Everything else (page and token dims) stays replicated."""
        from jax.sharding import NamedSharding
        return jax.tree.map(
            lambda leaf: NamedSharding(
                self.mesh, _pool_spec(leaf.shape, self.mesh)),
            self.pools)

    def _scopes(self):
        """The construction-pinned numerics + mesh scopes every engine
        step (prefill and decode) runs under."""
        import contextlib
        from repro.parallel import ctx as _pctx
        scope = contextlib.ExitStack()
        scope.enter_context(numerics.use(self.numerics_config))
        if self.mesh is not None:
            scope.enter_context(_pctx.use_mesh(self.mesh))
        return scope

    # ------------------------------------------------------- observability
    #
    # Each engine phase is an ``obs.trace.span``: always a profiler
    # annotation (one C++ check when no profiler session runs), and a
    # tracer event when a repro.obs tracer is active.  Request events and
    # latency histograms below are gated on an active tracer: with none
    # installed there are no wall-clock reads and no histogram writes
    # (the overhead test pins zero extra jitted traces either way).

    @staticmethod
    def _observe_latency(name: str, seconds: float):
        _obs_metrics.observe(f"serving/latency/{name}", seconds)

    def _trace_request_end(self, req: Request):
        tr = _current_tracer()
        if tr is not None:
            tr.async_end("request", req.rid, finish=req.finish_reason,
                         tokens=len(req.out))

    def _trace_preempt(self, req: Request):
        tr = _current_tracer()
        if tr is not None:
            tr.async_instant("preempted", req.rid,
                             n_preemptions=req.n_preemptions)

    # ------------------------------------------------------------ intake

    def add_request(self, prompt, params: SamplingParams | None = None,
                    deadline: int | None = None) -> int:
        """Enqueue a request; returns its rid.

        Raises :class:`RequestRejected` for requests that can never be
        served and :class:`EngineOverloaded` when the waiting queue is at
        ``max_waiting`` (backpressure — retry later).  ``deadline`` is a
        step budget: the request must finish within that many engine
        clock ticks or it is timed out (``finish_reason="timeout"``).
        """
        params = params or SamplingParams()
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if params.max_tokens < 1:                # was an assert: -O-unsafe
            self._stats["rejections"] += 1
            raise RequestRejected(
                f"max_tokens must be >= 1, got {params.max_tokens}")
        need = self.pool.pages_for(len(prompt) + 1)
        if need > min(self.max_pages_per_slot, self.pool.num_pages - 1):
            self._stats["rejections"] += 1
            raise RequestRejected(
                f"prompt needs {need} pages; engine caps at "
                f"{self.max_pages_per_slot} per slot")
        if deadline is not None and deadline < 1:
            self._stats["rejections"] += 1
            raise RequestRejected(f"deadline must be >= 1, got {deadline}")
        if (self.max_waiting is not None
                and len(self.sched.waiting) >= self.max_waiting):
            self._stats["overloads"] += 1
            raise EngineOverloaded(
                f"waiting queue is at max_waiting={self.max_waiting}")
        req = self.sched.add(prompt, params)
        req.key = jax.random.PRNGKey(params.seed)
        if deadline is not None:
            req.deadline = self.clock + deadline
        self._requests[req.rid] = req
        tr = _current_tracer()
        if tr is not None:
            req.t_enqueue = tr.now()
            tr.async_begin("request", req.rid, prompt_len=len(prompt),
                           max_tokens=params.max_tokens)
        return req.rid

    # ----------------------------------------------------------- prefill

    def _admit(self) -> list[Request]:
        """Admission: finish requests that outgrew a slot, admit what the
        scheduler allows and set up their chunked prefills; returns the
        admitted requests."""
        # a preempted request may have *generated* its way past the per-slot
        # cap (add_request only guards prompts): finish it from the queue —
        # re-admitting would need more pages than a block-table row holds
        for req in [r for r in list(self.sched.waiting) + list(self.sched.parked)
                    if self.pool.pages_for(len(r.full_sequence) + 1)
                    > min(self.max_pages_per_slot, self.pool.num_pages - 1)]:
            self._stats["length_caps"] += 1
            req.finish_reason = FinishReason.LENGTH_CAP.value
            self.sched.drop(req)
            self._trace_request_end(req)
        plan = (self._plan_admission
                if (self.prefix is not None or self.chunk_tokens) else None)
        admitted = self.sched.admit(plan)
        for req in admitted:
            if req.state is RequestState.PREFILLING:
                if req.shared_pages:
                    self._stats["prefix_hits"] += 1
                    self._stats["prefix_tokens_reused"] += req.prefill_done
                self._start_chunked_prefill(req)
        tr = _current_tracer()
        if tr is not None:
            now = tr.now()
            for req in admitted:
                tr.async_instant("admitted", req.rid, clock=self.clock)
                if req.t_enqueue is not None and req.n_preemptions == 0:
                    self._observe_latency("queue_wait_s",
                                          now - req.t_enqueue)
        return admitted

    def _prefill_admitted(self, admitted: list[Request]):
        """Monolithic prefill of the RUNNING admissions, one batched call
        per padded length, and each request's first token."""
        ps = self.pool.page_size
        # same padded length -> one batched prefill call (PREFILLING
        # admissions advance chunk-by-chunk in _prefill_chunk_step instead)
        groups: dict[int, list[Request]] = {}
        for req in admitted:
            if req.state is not RequestState.RUNNING:
                continue
            seq = req.full_sequence
            padded = max(1, -(-len(seq) // ps)) * ps
            groups.setdefault(padded, []).append(req)
        for padded, reqs in sorted(groups.items()):
            with _span("engine.prefill", batch=len(reqs),
                       tokens=len(reqs) * padded):
                toks = np.zeros((len(reqs), padded), np.int32)
                for i, req in enumerate(reqs):
                    toks[i, :len(req.full_sequence)] = req.full_sequence
                try:
                    faults.raise_if("prefill")
                    logits, kv = self._prefill(self.params, jnp.asarray(toks))
                except Exception as exc:  # noqa: BLE001 — rolled back below
                    self._on_prefill_failure(reqs, exc)
                    continue
                self.n_prefills += 1
                n_prompt_pages = padded // ps
                pages = np.asarray([req.pages[:n_prompt_pages]
                                    for req in reqs], np.int32)
                self.pools = write_prompt_pages(self.pools, kv,
                                                jnp.asarray(pages))
                if self.prefix is not None:
                    # register full pages before the accept loop: a request
                    # finishing on its first token frees its own refs, but
                    # the tree's references keep the pages alive
                    for req in reqs:
                        self.prefix.insert(req.full_sequence, req.pages)
                for i, req in enumerate(reqs):
                    plen = len(req.full_sequence)
                    self.lengths[req.slot] = plen
                    self._sync_slot(req)
                    row = jnp.asarray(logits[i, plen - 1,
                                             :self.cfg.vocab_size],
                                      jnp.float32)
                    req.key, sub = jax.random.split(req.key)
                    tok = sampling.sample_one(row, req.params, sub)
                    with _span("engine.sync"):
                        tok = int(tok)
                    self._accept_token(req, tok)

    # a request whose prefill fails this many times finishes with
    # finish_reason="error" instead of retrying forever
    MAX_PREFILL_FAULTS = 3

    def _on_prefill_failure(self, reqs: list[Request], exc: Exception):
        """Roll a failed prefill group back: nothing landed on device yet
        (the failure happened before ``write_prompt_pages``), so each
        request is un-admitted back to the head of the queue for a clean
        retry next step.  Persistent failers finish with
        ``finish_reason="error"`` after :data:`MAX_PREFILL_FAULTS`
        attempts.  Real (non-injected) errors propagate when the guard
        knob is off."""
        if (not isinstance(exc, faults.FaultInjected)
                and not self.numerics_config.guard):
            raise exc
        self._stats["prefill_faults"] += 1
        # reversed: appendleft-ing restores the group's original FIFO order
        for req in reversed(reqs):
            req.n_prefill_faults += 1
            if req.n_prefill_faults >= self.MAX_PREFILL_FAULTS:
                self._stats["numerics_errors"] += 1
                self._finish(req, FinishReason.ERROR)
            else:
                self.sched.unadmit(req)

    # ------------------------------------- shared prefixes / chunked prefill

    def _evict_prefix(self, n: int) -> int:
        """Scheduler eviction hook: reclaim ``n`` pages from the prefix
        cache's LRU tail when the pool runs dry."""
        freed = self.prefix.evict_for(n)
        self._stats["prefix_evictions"] += freed
        return freed

    def _plan_admission(self, req: Request):
        """Admission plan for :meth:`Scheduler.admit` when the prefix
        cache and/or chunked prefill is on.

        Returns None for the legacy single-shot route, else ``(shared,
        start, reserve)``: the cached pages to map at the head of the
        block table, the token offset prefill resumes from, and how many
        pages to allocate for the first chunk's span.  The last prompt
        position is always recomputed (its logits seed the first sampled
        token), so a full-prompt hit still rewrites the final page — the
        deterministic copy-on-write trigger.
        """
        ps = self.pool.page_size
        seq = req.full_sequence
        plen = len(seq)
        padded = max(1, -(-plen // ps)) * ps
        shared, start = [], 0
        if self.prefix is not None:
            pages, matched = self.prefix.match(seq)
            hit = min(matched, plen - 1)
            # resume on the chunk grid; the overlap [start, matched) is
            # recomputed bitwise-identically and COW-splits its pages
            grid = self.chunk_tokens or ps
            start = (hit // grid) * grid
            shared = pages if start > 0 else []
            if not shared:
                start = 0
        if not shared and not (self.chunk_tokens
                               and plen > self.chunk_tokens):
            return None
        end = (min(start + self.chunk_tokens, padded)
               if self.chunk_tokens else padded)
        reserve = max(0, -(-end // ps) - len(shared))
        return shared, start, reserve

    def _start_chunked_prefill(self, req: Request):
        """Set up a PREFILLING admission: a per-request float32 dense
        scratch cache sized to the chunk grid, pre-populated with the
        shared prefix's K/V.  Chunk attention reads earlier chunks' exact
        f32 values from here, so the math matches a monolithic prefill
        bitwise; only finished whole pages are scattered to the pool."""
        ps = self.pool.page_size
        plen = len(req.full_sequence)
        padded = max(1, -(-plen // ps)) * ps
        T = padded
        if self.chunk_tokens:
            T = (-(-padded // self.chunk_tokens)) * self.chunk_tokens
        req.scratch = self.model.init_cache(1, T, dtype=jnp.float32)
        n_load = req.prefill_done // ps
        if n_load:
            req.scratch = load_pages_into_scratch(
                req.scratch, self.pools,
                jnp.asarray(req.pages[:n_load], jnp.int32))

    def _preempt_prefilling(self, req: Request):
        """A dry pool mid-chunk: recompute-preempt the request itself (a
        re-admission replans, re-matching the prefix cache cleanly) —
        unless the pool could never hold it, which finishes it instead of
        livelocking."""
        slot = req.slot
        if len(req.pages) + 1 >= self.pool.num_pages:
            self._finish(req, FinishReason.ERROR)
            return
        self.sched.preempt(req)
        self._clear_slot(slot)
        self._trace_preempt(req)

    def _prefill_chunk_step(self):
        """Advance every PREFILLING request by one chunk (admission
        order), interleaved with the batched decode step — a long prompt
        no longer stalls every resident decode for its whole prefill, and
        two prefix hits admitted together both emit their first token in
        the admission step, like the monolithic batched path."""
        cands = sorted((r for r in self.sched.running.values()
                        if r.state is RequestState.PREFILLING),
                       key=lambda r: self.sched._admitted_at[r.rid])
        for req in cands:
            if not self._advance_chunk(req):
                return

    def _advance_chunk(self, req: Request) -> bool:
        """One chunk of one request; False stops this step's chunk phase
        (pool pressure or an injected fault — retry next step)."""
        ps = self.pool.page_size
        seq = req.full_sequence
        plen = len(seq)
        padded = max(1, -(-plen // ps)) * ps
        start = req.prefill_done
        C = self.chunk_tokens or (padded - start)
        with _span("engine.prefill_chunk", rid=req.rid, start=start,
                   tokens=C):
            # pages this chunk scatters back: whole pages in
            # [start, min(start+C, padded)) — the grid-rounded final
            # chunk's pure-padding tail is never materialized
            span_lo = start // ps
            span_hi = -(-min(start + C, padded) // ps)
            need = span_hi - len(req.pages)
            if need > 0 and self.sched.reserve(req, need) is None:
                self._preempt_prefilling(req)
                return False
            # copy-on-write: never write a page someone else references
            for idx in range(span_lo, span_hi):
                if self.pool.refcount(req.pages[idx]) > 1:
                    got = self.sched._alloc(1)
                    if got is None:
                        self._preempt_prefilling(req)
                        return False
                    old = req.pages[idx]
                    req.pages[idx] = got[0]
                    self.pool.free([old])
                    self._stats["cow_splits"] += 1
            toks = np.zeros((1, C), np.int32)
            toks[0, :min(plen, start + C) - start] = seq[start:start + C]
            try:
                faults.raise_if("prefill.chunk")
                logits, req.scratch = self._prefill_chunk(
                    self.params, req.scratch, jnp.asarray(toks),
                    jnp.int32(start))
            except Exception as exc:  # noqa: BLE001 — rolled back below
                self._on_prefill_failure([req], exc)
                return False
            self.n_prefill_chunks += 1
            self.pools = write_span_pages(
                self.pools, req.scratch, jnp.int32(start),
                jnp.asarray(req.pages[span_lo:span_hi], jnp.int32))
            req.prefill_done = start + C
            if req.prefill_done < padded:
                return True
            # prompt fully prefilled: this chunk contains position
            # plen-1, whose logits seed the first sampled token (same
            # draw convention as the monolithic path)
            req.scratch = None
            req.state = RequestState.RUNNING
            if self.prefix is not None:
                self.prefix.insert(seq, req.pages)
            self.lengths[req.slot] = plen
            self._sync_slot(req)
            row = jnp.asarray(logits[0, plen - 1 - start,
                                     :self.cfg.vocab_size], jnp.float32)
            req.key, sub = jax.random.split(req.key)
            tok = sampling.sample_one(row, req.params, sub)
            with _span("engine.sync"):
                tok = int(tok)
            self._accept_token(req, tok)
        return True

    def _sync_slot(self, req: Request):
        """Push a request's page list and sampling knobs into its slot."""
        s = req.slot
        self.block_tables[s] = 0
        self.block_tables[s, :len(req.pages)] = req.pages
        self.temps[s] = req.params.temperature
        self.topks[s] = req.params.top_k
        self.topps[s] = req.params.top_p
        self.keys = self.keys.at[s].set(req.key)

    def _clear_slot(self, slot: int):
        self.block_tables[slot] = 0
        self.lengths[slot] = 0
        self.next_tok[slot] = 0
        self.temps[slot] = 0.0
        self.topks[slot] = 0
        self.topps[slot] = 1.0

    def _accept_token(self, req: Request, tok: int) -> bool:
        """Host-side completion logic; returns True while still running."""
        tr = _current_tracer()
        if tr is not None and req.t_enqueue is not None:
            now = tr.now()
            if req.t_last_token is None:
                self._observe_latency("ttft_s", now - req.t_enqueue)
            else:
                self._observe_latency("tpot_s", now - req.t_last_token)
            req.t_last_token = now
        if tok in req.params.stop_tokens:
            self._finish(req, FinishReason.STOP)
            return False
        req.out.append(tok)
        if len(req.out) >= req.params.max_tokens:
            self._finish(req, FinishReason.LENGTH)
            return False
        self.next_tok[req.slot] = tok
        return True

    def _finish(self, req: Request, reason: FinishReason):
        req.finish_reason = (reason.value if isinstance(reason, FinishReason)
                             else str(reason))
        slot = req.slot
        self.sched.finish(req)
        self._clear_slot(slot)
        self._trace_request_end(req)

    # ------------------------------------------------------------ decode

    def _ensure_pages(self):
        """Every running slot must own the page its next token writes to;
        grow (possibly preempting) before the step, not during it."""
        ps = self.pool.page_size
        for req in sorted(self.sched.running.values(),
                          key=lambda r: self.sched._admitted_at[r.rid]):
            if req.slot is None:        # preempted by an earlier grow
                continue
            if req.state is not RequestState.RUNNING:
                continue                # PREFILLING: pages come per chunk
            page_idx = int(self.lengths[req.slot]) // ps
            if page_idx >= self.max_pages_per_slot:
                self._stats["length_caps"] += 1
                self._finish(req, FinishReason.LENGTH_CAP)
                continue
            if page_idx >= len(req.pages):
                before = {r.rid: r.slot for r in self.sched.running.values()}
                if not self.sched.grow(req):
                    slot = req.slot
                    if len(req.pages) + 1 >= self.pool.num_pages:
                        # the pool cannot hold even this one request:
                        # finish it gracefully (its tokens so far are
                        # still valid) instead of crashing the engine
                        self._finish(req, FinishReason.ERROR)
                    else:
                        # transient exhaustion (an injected alloc fault,
                        # or pages freed off-schedule): requeue and retry
                        # — recompute-preemption of self, not a failure
                        self.sched.preempt(req)
                        self._clear_slot(slot)
                        self._trace_preempt(req)
                    for rid, s in before.items():
                        r = self._requests[rid]
                        if r.slot is None and rid != req.rid:
                            self._clear_slot(s)
                            self._trace_preempt(r)
                    continue
                for rid, slot in before.items():
                    r = self._requests[rid]
                    if r.slot is None:          # got preempted: mask slot
                        self._clear_slot(slot)
                        self._trace_preempt(r)
                self.block_tables[req.slot] = 0
                self.block_tables[req.slot, :len(req.pages)] = req.pages

    def _poison_mask(self) -> np.ndarray:
        """Poll the ``decode.nonfinite`` fault site: a (max_slots,) bool
        mask of slots whose logits this step will NaN-poison (all-False
        keeps the jitted step's logits bitwise identical — zero parity
        cost on the fault-free path)."""
        poison = np.zeros((self.max_slots,), bool)
        spec = faults.poke("decode.nonfinite")
        if spec is not None:
            if spec.arg < 0:
                poison[:] = True
            else:
                poison[spec.arg % self.max_slots] = True
        return poison

    def _decode_dispatch(self):
        """Launch the jitted decode step for every RUNNING slot and return
        the in-flight record (None when nothing is running).  The host
        inputs are snapshotted into an alternating staging buffer, so the
        mirrors are free to mutate for the NEXT step while this one is on
        device; nothing here blocks on the result."""
        running = [r for r in self.sched.running.values()
                   if r.state is RequestState.RUNNING]
        if not running:
            return None
        with _span("engine.dispatch", batch=len(running)):
            buf = self._staging[self.n_decode_steps % 2]
            for name, host in buf.items():
                np.copyto(host, getattr(self, name))
            args = (self.params, jnp.asarray(buf["block_tables"]),
                    jnp.asarray(buf["lengths"]), jnp.asarray(buf["next_tok"]),
                    jnp.asarray(buf["temps"]), jnp.asarray(buf["topks"]),
                    jnp.asarray(buf["topps"]))
            prev_keys = self.keys    # NOT donated: reusable for the re-run
            toks, finite, pools, keys = self._decode(
                args[0], self.pools, *args[1:], prev_keys,
                jnp.asarray(self._poison_mask()))
            self.n_decode_steps += 1
            self.n_decode_steps_in_place += self._decode_in_place()
            self.pools, self.keys = pools, keys
            return {"running": running, "args": args, "prev_keys": prev_keys,
                    "toks": toks, "finite": finite}

    def _decode_in_place(self) -> bool:
        """Whether the decode program of the active numerics config runs
        in place (``models.lm.decode_in_place``), asked once per config."""
        nc = numerics.active()
        if nc not in self._in_place:
            self._in_place[nc] = self.model.decode_in_place(
                self.pools, self.max_slots)
        return self._in_place[nc]

    def _decode_consume(self, inflight):
        """Block on a dispatched step's results and apply them — the only
        device sync in the loop.  Sync mode runs this right after the
        dispatch; async mode runs it at the top of the NEXT step, so the
        host's scheduling work for step N overlaps the device executing
        step N-1.  Either way the consume happens before any other
        mutation of that step, so the engine-state update order (and thus
        every sampled token) is identical across modes."""
        running, args = inflight["running"], inflight["args"]
        prev_keys = inflight["prev_keys"]
        toks, finite = inflight["toks"], inflight["finite"]
        with _span("engine.consume", batch=len(running)):
            with _span("engine.sync"):
                finite = np.asarray(finite)
            bad = [r for r in running if not finite[r.slot]]
            if bad and self.numerics_config.guard:
                # one-shot re-run of the whole step under the XLA-fallback
                # numerics scope.  Safe to replay against the post-step
                # pools (self.pools — nothing else has touched them since
                # the dispatch): the step only writes the current
                # position's K/V, which the re-run overwrites before
                # reading.  prev_keys keeps every fault-free slot's
                # sampling stream from advancing twice.
                self._stats["guard_trips"] += 1
                self._stats["fallback_reruns"] += 1
                tr = _current_tracer()
                if tr is not None:
                    tr.instant("fallback-rerun", cat="engine",
                               slots=[r.slot for r in bad])
                with numerics.use(self._fallback_numerics):
                    toks, finite, pools, keys = self._decode(
                        args[0], self.pools, *args[1:], prev_keys,
                        jnp.asarray(self._poison_mask()))
                with _span("engine.sync"):
                    finite = np.asarray(finite)
                self.pools, self.keys = pools, keys
            with _span("engine.sync"):
                toks = np.asarray(toks)
            with _span("engine.accept"):
                for req in running:
                    if not finite[req.slot]:
                        # the fallback tripped too (or the guard is off):
                        # fail THIS request; its batch neighbours are
                        # unharmed
                        self._stats["numerics_errors"] += 1
                        self._finish(req, FinishReason.ERROR)
                        continue
                    self.lengths[req.slot] += 1  # input token now cached
                    req.key = self.keys[req.slot]
                    self._accept_token(req, int(toks[req.slot]))

    # ------------------------------------------------------------- drive

    def _expire_deadlines(self):
        """Time out requests (running or queued) whose deadline tick has
        passed.  Runs at the top of every step, so a timed-out request
        never consumes another prefill or decode."""
        for req in list(self.sched.running.values()):
            if req.deadline is not None and self.clock > req.deadline:
                self._stats["timeouts"] += 1
                self._finish(req, FinishReason.TIMEOUT)
        for req in [r for r in
                    list(self.sched.waiting) + list(self.sched.parked)
                    if r.deadline is not None and self.clock > r.deadline]:
            self._stats["timeouts"] += 1
            req.finish_reason = FinishReason.TIMEOUT.value
            self.sched.drop(req)
            self._trace_request_end(req)

    def step(self):
        """One engine iteration: consume any in-flight async decode,
        tick the deadline clock, expire deadlines, admit + prefill,
        advance one prefill chunk, then dispatch one decode step for
        whatever is in flight — under the construction-time numerics and
        mesh scopes.  Sync mode (default) consumes the dispatch inline;
        async mode leaves it in flight until the next step."""
        # step_num: the clock tick this step takes
        with self._scopes(), _span("engine.step", step_num=self.clock + 1,
                                   running=len(self.sched.running),
                                   waiting=len(self.sched.waiting)):
            if self._inflight is not None:
                inflight, self._inflight = self._inflight, None
                self._decode_consume(inflight)
            self.clock += 1
            spec = faults.poke("decode.slow")
            if spec is not None:         # injected slowdown: burn ticks
                self.clock += max(1, spec.arg)
            with _span("engine.admit"):
                self._expire_deadlines()
                admitted = self._admit()
            self._prefill_admitted(admitted)
            self._prefill_chunk_step()
            with _span("engine.ensure_pages"):
                self._ensure_pages()
            inflight = self._decode_dispatch()
            if inflight is not None:
                if self.async_sched:
                    self._inflight = inflight
                else:
                    self._decode_consume(inflight)

    def run(self, prompts=None, params=None) -> dict[int, RequestResult]:
        """Convenience driver: optionally enqueue ``prompts`` (with one
        :class:`SamplingParams` each, or one shared), run to drain, and
        return :meth:`results` for everything enqueued since
        construction."""
        if prompts is not None:
            if params is None:
                params = [None] * len(prompts)
            elif isinstance(params, SamplingParams):
                params = [params] * len(prompts)
            for prompt, sp in zip(prompts, params):
                self.add_request(prompt, sp)
        while self.sched.has_work or self._inflight is not None:
            self.step()
        return self.results()

    def results(self) -> dict[int, RequestResult]:
        """``{rid: RequestResult}`` — generated tokens (list-compatible)
        plus ``finish_reason`` — for every request seen so far."""
        return {rid: RequestResult(req.out, req.finish_reason)
                for rid, req in self._requests.items()}

    def stats(self) -> dict:
        """Resilience and throughput counters: engine counters (guard
        trips, fallback re-runs, rejections, overloads, timeouts, length
        caps, prefill faults, numerics errors, decode steps and those run
        with the page pools in place), scheduler counters
        (preemptions, parks), and the kernel circuit breaker's global
        totals.  All zero on a healthy fault-free run — the serving bench
        snapshot records them so CI gates on exactly that."""
        from repro.kernels import guard
        return {**self._stats,
                "clock": self.clock,
                "prefills": self.n_prefills,
                "prefill_chunks": self.n_prefill_chunks,
                "decode_steps": self.n_decode_steps,
                "decode_steps_in_place": self.n_decode_steps_in_place,
                "preemptions": self.sched.n_preemptions,
                "parks": self.sched.n_parks,
                "breaker": guard.counters()}

    # ------------------------------------------------------------ defrag

    def defragment(self):
        """Compact live pages to the low end of the pool: permutes the
        device page arrays and re-indexes every running request's block
        table, prefix-cache node, and in-flight chunked prefill.  Safe
        between steps; output-invariant (tests assert)."""
        if self._inflight is not None:       # async: land the step first
            inflight, self._inflight = self._inflight, None
            with self._scopes():
                self._decode_consume(inflight)
        mapping = self.pool.defrag()
        perm = inverse_permutation(mapping, self.pool.num_pages)
        self.pools = permute_pages(self.pools, perm)
        if self.prefix is not None:
            self.prefix.remap(mapping)
        for req in self.sched.running.values():
            req.pages = [mapping[p] for p in req.pages]
            if req.state is RequestState.RUNNING:
                # PREFILLING slots keep zeroed (masked) block tables
                self.block_tables[req.slot] = 0
                self.block_tables[req.slot, :len(req.pages)] = req.pages


def _decode_and_sample(params, pools, block_tables, lengths, toks, temps,
                       topks, topps, keys, poison, *, model, cfg):
    """The jitted engine step: paged model decode + vectorized sampling +
    per-slot key advance, one dispatch for the whole slot array.

    Returns ``(tokens, finite, new_pools, new_keys)`` where ``finite`` is
    the per-slot isfinite guard bit — False means this slot's logits
    contain a non-finite value and its sampled token must not be trusted
    (the engine re-runs the step under the XLA-fallback scope).
    ``poison`` is the ``decode.nonfinite`` fault mask: poisoned slots get
    their logits NaN'd *after* the model forward, so an all-False mask is
    bitwise identical to the unpoisoned computation.
    """
    logits, new_pools = model.decode_step_paged(params, pools, block_tables,
                                                lengths, toks)
    logits = logits[:, :cfg.vocab_size].astype(jnp.float32)
    logits = jnp.where(poison[:, None], jnp.nan, logits)
    finite = jnp.all(jnp.isfinite(logits), axis=-1)   # (B,) guard bit
    # split convention must match the prefill draw (`key, sub = split(key)`:
    # carry row 0, sample with row 1) — otherwise a preemption's re-prefill
    # would resume a request's stream on the wrong side of the split
    split = jax.vmap(jax.random.split)(keys)          # (B, 2, 2)
    out = sampling.sample(logits, temps, topks, topps, split[:, 1])
    return out, finite, new_pools, split[:, 0]
