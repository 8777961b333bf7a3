"""Family-dispatched model API: init / loss_fn / init_cache / decode_step.

Every family exposes the same four entry points, so the trainer, server,
dry-run, and benchmarks are family-agnostic.  ``get_model`` optionally
pins a :class:`repro.numerics.NumericsConfig` scope around every entry
point, so a model handle can carry its kernel-dispatch recipe with it
(the serving engine snapshots its own config the same way)."""
from __future__ import annotations

import functools
from types import SimpleNamespace

from repro import numerics
from . import encdec_lm, hybrid_lm, lm, ssm_lm, vlm_lm

_FAMILIES = {
    "dense": lm,
    "moe": lm,
    "ssm": ssm_lm,
    "hybrid": hybrid_lm,
    "audio": encdec_lm,
    "vlm": vlm_lm,
}


def _pinned(fn, cfg: numerics.NumericsConfig):
    if fn is None:
        return None

    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with numerics.use(cfg):
            return fn(*a, **kw)

    return wrapped


def get_model(cfg, numerics_config: numerics.NumericsConfig | None = None
              ) -> SimpleNamespace:
    """Build the family-agnostic model handle for ``cfg``.

    ``numerics_config`` (optional) pins every entry point to that numerics
    scope — equivalent to wrapping each call in ``repro.numerics.use(...)``
    — so dispatch decisions stay stable regardless of the caller's ambient
    context.
    """
    mod = _FAMILIES[cfg.family]
    # Paged serving entries exist only for the KV-cache families (lm.py:
    # dense/moe, incl. MLA); the continuous-batching engine checks for
    # None and the serve CLI falls back to the dense loop elsewhere.
    paged = hasattr(mod, "decode_step_paged")
    handle = SimpleNamespace(
        init=lambda key: mod.init(cfg, key),
        loss_fn=lambda params, batch: mod.loss_fn(params, batch, cfg),
        forward_logits=lambda params, batch: mod.forward_logits(
            params, batch, cfg),
        init_cache=lambda batch, max_len, **kw: mod.init_cache(
            cfg, batch, max_len, **kw),
        decode_step=lambda params, cache, tokens, idx: mod.decode_step(
            params, cfg, cache, tokens, idx),
        prefill=(lambda params, tokens, positions=None: mod.prefill(
            params, cfg, tokens, positions)) if paged else None,
        prefill_chunk=(lambda params, cache, tokens, start:
                       mod.prefill_chunk(params, cfg, cache, tokens,
                                         start)) if paged else None,
        init_paged_cache=(lambda num_pages, page_size, **kw:
                          mod.init_paged_cache(cfg, num_pages, page_size,
                                               **kw)) if paged else None,
        decode_step_paged=(lambda params, pools, block_tables, lengths,
                           tokens: mod.decode_step_paged(
                               params, cfg, pools, block_tables, lengths,
                               tokens)) if paged else None,
        decode_in_place=(lambda pools, batch: mod.decode_in_place(
            cfg, pools, batch)) if paged else None,
        module=mod,
    )
    if numerics_config is not None:
        for name in ("init", "loss_fn", "forward_logits", "init_cache",
                     "decode_step", "prefill", "prefill_chunk",
                     "init_paged_cache", "decode_step_paged",
                     "decode_in_place"):
            setattr(handle, name, _pinned(getattr(handle, name),
                                          numerics_config))
    return handle
