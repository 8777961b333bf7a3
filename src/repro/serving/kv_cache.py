"""Paged KV-cache pool: fixed-size pages, per-sequence block tables.

The legacy serving path allocated a dense ``(B, max_len)`` cache per batch
— every request paid for the longest possible sequence, and requests of
different lengths could not share a batch.  Here the cache is a pool of
fixed-size pages shared by every in-flight request: a request holds
``ceil(len / page_size)`` pages, listed in its block-table row, and frees
them the moment it completes.  Fragmentation is bounded to one partial
page per sequence (the vLLM PagedAttention memory model).

Split of responsibilities:

  * :class:`PagePool` — the host-side allocator: free-list bookkeeping
    only, no device arrays.  Page 0 is reserved as the **scrap page**:
    inactive engine slots point their block tables at it, so their masked
    decode writes land somewhere harmless.
  * the device-side page arrays live in the model tree
    (``models.lm.init_paged_cache``) and are updated functionally inside
    the jitted decode step; :func:`write_prompt_pages` scatters a
    sequence-level prefill's K/V into freshly allocated pages, and
    :func:`permute_pages` applies a defrag permutation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .errors import PagePoolError

DEFAULT_PAGE_SIZE = 16


def _jit_donating_pools(fn):
    """``jax.jit`` that donates the pools (argument 0).  The pool arrays are
    the dominant serving allocation and every update rebinds them, so the
    update runs in place — except on CPU, where XLA doesn't implement
    donation and would warn per compile.  The backend is asked at the
    first call, never at import."""
    @functools.cache
    def jitted():
        donate = () if jax.default_backend() == "cpu" else (0,)
        return jax.jit(fn, donate_argnums=donate)

    @functools.wraps(fn)
    def call(*args):
        return jitted()(*args)
    return call


class PagePool:
    """Host-side page allocator over ``num_pages`` fixed-size pages.

    LIFO free list: recently freed pages are reused first, which keeps the
    hot working set small.  ``alloc`` is all-or-nothing — a partial grant
    would deadlock two growing requests against each other.

    Pages are **refcounted** so the prefix cache can share them across
    requests (and hold its own reference): ``alloc`` hands out pages at
    refcount 1, :meth:`share` adds owners, and :meth:`free` only returns a
    page to the free list when its last owner lets go.  Uniquely-owned
    pages behave exactly as before — the refcounts are invisible to
    callers that never share.
    """

    def __init__(self, num_pages: int, page_size: int = DEFAULT_PAGE_SIZE):
        # real checks, not asserts: these guard user-supplied sizing and
        # must survive python -O
        if num_pages < 2:
            raise ValueError("need at least the scrap page + one real page; "
                             f"got num_pages={num_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.num_pages = num_pages
        self.page_size = page_size
        # page 0 is the scrap page — never handed out
        self._free = list(range(num_pages - 1, 0, -1))
        self._ref: dict[int, int] = {}          # live page -> owner count

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_live(self) -> int:
        return self.num_pages - 1 - len(self._free)

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` (+ nothing: callers add their
        own growth headroom)."""
        return max(1, -(-n_tokens // self.page_size))

    def alloc(self, n: int) -> list[int] | None:
        """Pop ``n`` pages, or None (and no change) if they don't fit.

        The ``pool.alloc`` fault site injects transient exhaustion here
        (returns None with pages available) — the same signal callers
        must already handle, so every alloc site is chaos-testable.
        """
        from repro import faults
        if faults.poke("pool.alloc") is not None:
            return None
        if n > len(self._free):
            return None
        taken = self._free[-n:][::-1]
        del self._free[-n:]
        for p in taken:
            self._ref[p] = 1
        return taken

    def share(self, pages: list[int]) -> None:
        """Add one owner to each page (prefix-cache sharing).  Only live
        pages can gain owners — sharing a free page is a bookkeeping bug
        of the same severity as a double free."""
        for p in pages:
            if self._ref.get(p, 0) < 1:
                raise PagePoolError(f"share of non-live page {p}")
        for p in pages:
            self._ref[p] += 1

    def refcount(self, p: int) -> int:
        """Current owner count of page ``p`` (0 = free)."""
        return self._ref.get(p, 0)

    def free(self, pages: list[int]) -> None:
        """Drop one owner per page; pages reaching zero owners return to
        the free list.  Freeing a page that has no owners is still a
        double free."""
        for p in pages:
            if not 0 < p < self.num_pages:
                raise PagePoolError(f"free of out-of-range page {p} "
                                    f"(pool has {self.num_pages})")
            if self._ref.get(p, 0) < 1:
                raise PagePoolError(f"double free of page {p}")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                del self._ref[p]
                self._free.append(p)

    def defrag(self) -> dict[int, int]:
        """Compact live pages onto the lowest indices.

        Returns the ``{old: new}`` mapping for live pages (identity
        entries included) and rebuilds the free list above them.  Callers
        must re-index their block tables and apply the same permutation
        to the device page arrays (:func:`permute_pages`) — the pool only
        does the bookkeeping.
        """
        live = sorted(set(range(1, self.num_pages)) - set(self._free))
        mapping = {old: new for new, old in enumerate(live, start=1)}
        self._free = list(range(self.num_pages - 1, len(live), -1))
        # refcounts travel with their pages: a shared page moves ONCE and
        # every owner's mapping update finds the same count at the new slot
        self._ref = {mapping[p]: c for p, c in self._ref.items()}
        return mapping


# ------------------------------------------------------- device helpers
#
# Pool leaves are (nL, NP, Hkv, ps, hd) for attention K/V (head-major, see
# ``models.lm.init_paged_cache``) and (nL, NP, ps, d) for latent caches;
# prefill and scratch leaves are token-major, (nL, B, T, ...).


def _swap_head_token(x):
    """(nL, n, ps, Hkv, hd) <-> (nL, n, Hkv, ps, hd) for K/V page blocks;
    latent page blocks (no head axis) pass through."""
    return jnp.swapaxes(x, 2, 3) if x.ndim == 5 else x


@_jit_donating_pools
def write_prompt_pages(pools, kv, pages):
    """Scatter a sequence-level prefill's K/V into allocated pages.

    pools: the ``init_paged_cache`` tree;
    kv: the matching ``prefill`` tree, leaves (nL, B, P, ...) with ``P``
    a multiple of ``ps`` (right-pad prompts to the page size — padded
    positions are masked by the sequence length and overwritten as decode
    proceeds); pages: (B, P // ps) i32 page indices per sequence.
    """
    flat = pages.reshape(-1)

    def one(pool, k):
        nL, B, P = k.shape[:3]
        ps = pool.shape[-2]
        kp = k.reshape((nL, B * (P // ps), ps) + k.shape[3:])
        return pool.at[:, flat].set(_swap_head_token(kp).astype(pool.dtype))

    return jax.tree.map(one, pools, kv)


@jax.jit
def load_pages_into_scratch(scratch, pools, pages):
    """Gather cached prefix pages into the head of a per-request dense
    scratch cache (chunked prefill over a prefix-cache hit).

    scratch: an ``init_cache(batch=1, ...)`` tree, leaves (nL, 1, T, ...);
    pools: the page-pool tree; pages: (n,) i32
    with ``n * ps <= T``.  The gathered tokens land at positions
    ``[0, n * ps)`` — the prefix the tail chunks attend over.
    """
    def one(s, pool):
        g = _swap_head_token(pool[:, pages])          # (nL, n, ps, ...)
        g = g.reshape((g.shape[0], 1, g.shape[1] * g.shape[2]) + g.shape[3:])
        return jax.lax.dynamic_update_slice(s, g.astype(s.dtype),
                                            (0,) * s.ndim)

    return jax.tree.map(one, scratch, pools)


@_jit_donating_pools
def write_span_pages(pools, scratch, start, pages):
    """Scatter one chunk's token span from the scratch cache into pages.

    pools: the page-pool tree; scratch: leaves (nL, 1, T, ...);
    start: i32 token index of the span (page-aligned); pages: (n,) i32 —
    the span covers tokens ``[start, start + n * ps)``.  The f32 scratch
    values cast to the pool dtype exactly as a monolithic prefill's
    ``write_prompt_pages`` would, so chunked and single-shot prefill land
    bitwise-identical pages.
    """
    def one(pool, s):
        nL = s.shape[0]
        ps = pool.shape[-2]
        n = pages.shape[0]
        span = jax.lax.dynamic_slice_in_dim(s[:, 0], start, n * ps, axis=1)
        sp = span.reshape((nL, n, ps) + span.shape[2:])
        return pool.at[:, pages].set(_swap_head_token(sp).astype(pool.dtype))

    return jax.tree.map(one, pools, scratch)


@_jit_donating_pools
def permute_pages(pools, perm):
    """Apply a defrag permutation to the device page arrays.

    perm: (NP,) i32 with ``perm[new] = old`` (identity off the live set) —
    i.e. the inverse of :meth:`PagePool.defrag`'s ``{old: new}`` mapping.
    """
    return jax.tree.map(lambda pool: pool[:, perm], pools)


def inverse_permutation(mapping: dict[int, int], num_pages: int):
    """Turn defrag's ``{old: new}`` into the (NP,) gather index
    ``perm[new] = old`` that :func:`permute_pages` wants."""
    perm = list(range(num_pages))
    for old, new in mapping.items():
        perm[new] = old
    return jnp.asarray(perm, jnp.int32)
