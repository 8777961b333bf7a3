"""Unified kernel dispatch: routes framework contractions to the fused
Pallas TCEC kernel.

Every split-policy contraction in the framework funnels through
``repro.core.policy._dot_impl`` (``pdot`` / ``policy_mm`` / ``policy_bmm``
and their ``custom_vjp`` backward GEMMs).  This module decides, per call,
whether that contraction lowers to the fused Pallas kernel
(``kernels/tcec_matmul.py``) or stays on the documented XLA term-expansion
fallback.  Both paths compute the identical corrected-GEMM math — the
kernel just fuses it into one VMEM-tiled pass (the paper's CUTLASS
integration), which is where the throughput headline comes from.

Configuration comes from :mod:`repro.numerics`: every decision function
takes the frozen :class:`~repro.numerics.NumericsConfig` as an explicit
``cfg`` argument (defaulting to ``numerics.active()``, i.e. the innermost
``repro.numerics.use(...)`` context or the env defaults).  The decision
runs at trace time on static shapes, so under ``jit`` it costs nothing at
runtime — and because the active config's epoch is part of the jit cache
key, entering/exiting a ``use(...)`` context deterministically re-lowers
instead of silently reusing a stale decision (the old footgun).

Dispatch rules (see docs/kernels.md):

  1. the policy is a bf16 split policy (``tcec_bf16x3`` / ``tcec_bf16x6``):
     plain policies are a single XLA dot, and the fp16 reproduction
     policies model CUDA Tensor Cores, which the bf16 MXU kernel cannot;
  2. the contraction has at most one (leading) batch dim; its free and
     contracted dims collapse onto the kernel's ``(B?, M, K) @ (B?, K, N)``
     (a projection ``bsd,dhk->bshk`` runs as one ``(b*s, d) @ (d, h*k)``
     GEMM);
  3. M, N, K all reach ``min_dim`` (tiny GEMMs lose more to 128-padding
     than the fusion wins);
  4. the backend is TPU — or ``force`` is set, which runs the kernel in
     interpret mode (tests, CPU verification);
  5. the escape hatch is off: ``REPRO_DISABLE_PALLAS=1`` (or
     ``use(enabled=False)``) restores the XLA path wholesale;
  6. under an installed GSPMD mesh (``repro.parallel.ctx``), the
     ``shard_map`` knob is on (``use(shard_map=True)``, the default /
     ``REPRO_SHARD_MAP``) and ``kernels/shmap.py`` supports a per-shard
     spec for the shapes — the call then runs as per-device kernel shards
     under ``shard_map`` (K-sharded contractions fold locally, then one
     f32 ``psum``).  Unsupported specs (or the knob off) decline to the
     XLA fallback, which GSPMD shards natively.

The pre-``repro.numerics`` entry points (``override`` / ``config`` /
``reload_config`` / ``env_flag`` / ``DispatchConfig``) survive as thin
deprecation shims at the bottom of this module.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import numerics
from repro.core.policy import PrecisionPolicy
from repro.obs.explain import record as _explain
from . import ops, tuning


def _cfg(cfg) -> numerics.NumericsConfig:
    return cfg if cfg is not None else numerics.active()


def _policy_rule(policy: PrecisionPolicy) -> str:
    """Rule-1 decline slug: plain policies vs the fp16 reproduction
    policies (repro.obs.explain vocabulary)."""
    return "plain-policy" if policy.is_plain() else "policy-ineligible"


def _guarded(kernel: str, ident: tuple, cfg, thunk, site: str):
    """Run a fused-kernel thunk behind the circuit breaker.

    With the ``guard`` knob on (default), a kernel failure for this
    ``(backend, kernel, *ident)`` key is caught and converted to an XLA
    fallback (return None), and repeated failures quarantine the key for
    a cooldown (see :mod:`repro.kernels.guard`).  With the knob off the
    error propagates — the debugging posture.  ``site`` is the
    :mod:`repro.faults` injection point exercised by the chaos battery.

    NB this runs at trace time: a jitted caller consults the breaker
    once per (function, shape, config-epoch) trace, not per execution.
    Every outcome — launch, open breaker, kernel failure — lands in the
    explain table (``ident`` is ``(policy, *shape-bucket)`` at all three
    call sites, matching the explain key convention).
    """
    from repro import faults
    pol, bucket = str(ident[0]), tuple(ident[1:])
    if not cfg.guard:
        faults.raise_if(site)
        out = thunk()
        _explain(kernel, pol, bucket, "fused")
        return out
    from . import guard
    key = guard.make_key(kernel, ident)
    if not guard.allow(key):
        _explain(kernel, pol, bucket, "breaker-open")
        return None
    try:
        faults.raise_if(site)
        out = thunk()
    except Exception as exc:       # noqa: BLE001 — fallback exists by design
        guard.failure(key, exc)
        _explain(kernel, pol, bucket, "kernel-failure")
        return None
    guard.success(key)
    _explain(kernel, pol, bucket, "fused")
    return out


# ----------------------------------------------------------- eligibility

def eligible_policy(policy: PrecisionPolicy) -> bool:
    """Rule 1: bf16 split policies only.

    The fused kernels are parametric in the policy's term schedule
    (``keep`` / ``groups`` / ``n_splits``), so any bf16 multi-term policy
    (x3/x6/x10, ...) routes through them.  Three policy classes decline
    cleanly to the XLA expansion instead: plain policies (nothing to
    fuse), ``upcast_products`` policies (the fp16/fp8 reproduction paths
    assume full-precision products the kernel does not model), and
    ``compensated`` policies (error-free TwoSum accumulation has no MXU
    mapping — it is the accuracy extreme, not the throughput one)."""
    return (not policy.is_plain()
            and policy.jdtype == jnp.bfloat16
            and not policy.upcast_products
            and not policy.compensated)


def _canonicalize(a, b, dims):
    """Map a ``dot_general`` spec onto the kernel's ``(B?, M, K) @ (B?, K, N)``
    layout: ``(a, b, out_shape)``, or None when the contraction doesn't fit
    (rule 2).

    Free dims of each operand collapse into M (resp. N) and the contracted
    dims into K, after a transpose when they are not already in that order
    (``custom_vjp``'s backward GEMMs contract leading dims).  ``out_shape``
    is ``dot_general``'s ``(batch, lhs-free..., rhs-free...)``, which the
    kernel's ``(B?, M, N)`` output reshapes to.
    """
    import math
    (ca, cb), (ba, bb) = dims
    nb = len(ba)
    if nb > 1 or tuple(ba) != tuple(range(nb)) or tuple(bb) != tuple(range(nb)):
        return None
    a_free = [d for d in range(nb, a.ndim) if d not in ca]
    b_free = [d for d in range(nb, b.ndim) if d not in cb]
    if not ca or not a_free or not b_free:
        return None
    batch = list(range(nb))
    a = jnp.transpose(a, batch + a_free + list(ca))
    b = jnp.transpose(b, batch + list(cb) + b_free)
    lead = tuple(a.shape[:nb])
    m_shape = a.shape[nb:nb + len(a_free)]
    n_shape = b.shape[nb + len(cb):]
    K = math.prod(a.shape[nb + len(a_free):])
    a = a.reshape(lead + (math.prod(m_shape), K))
    b = b.reshape(lead + (K, math.prod(n_shape)))
    return a, b, lead + tuple(m_shape) + tuple(n_shape)


def _mesh_plan_or_decline(shapes_plan, cfg):
    """Rule 6: returns ``(mesh, plan)`` — ``(None, None)`` when no mesh is
    installed, or the string ``"decline"`` when a mesh is installed but
    the knob is off / the spec is unsupported (the caller falls back to
    XLA, which GSPMD shards natively)."""
    from repro.parallel import ctx
    mesh = ctx.current_mesh()
    if mesh is None:
        return None, None
    if not cfg.shard_map:
        return mesh, "decline"
    if "model" in ctx.dp_axes():
        # dp_over_model: the context declares "model" a *batch* axis
        # (small-model pure DP — parallel/sharding.py replicates params).
        # The plan builders would assign it to N/K/M instead, forcing an
        # all-gather on entry to every shard_map; pure DP is exactly what
        # the XLA fallback shards natively, so decline.
        return mesh, "decline"
    plan = shapes_plan(mesh)
    return mesh, (plan if plan is not None else "decline")


def _decide(a, b, policy: PrecisionPolicy, dims, cfg):
    """The rule walk: ``(canonical operands | None, rule slug)`` — the
    slug names the declining rule (repro.obs.explain vocabulary) or is
    ``"fused"`` on acceptance."""
    if not cfg.enabled:
        return None, "hatch-disabled"
    if not eligible_policy(policy):
        return None, _policy_rule(policy)
    if not (cfg.force or jax.default_backend() == "tpu"):
        return None, "off-backend"
    canon = _canonicalize(a, b, dims)
    if canon is None:
        return None, "shape-unsupported"
    at, bt, _ = canon
    M, K = at.shape[-2], at.shape[-1]
    N = bt.shape[-1]
    if min(M, N, K) < cfg.min_dim:
        return None, "below-min-dim"
    from . import shmap
    _, plan = _mesh_plan_or_decline(
        lambda mesh: shmap.matmul_plan(at.shape, bt.shape, mesh), cfg)
    if plan == "decline":
        return None, "mesh-declined"
    return canon, "fused"


def decide(a, b, policy: PrecisionPolicy, dims, cfg=None):
    """The GEMM dispatch decision, with the config threaded explicitly.

    Returns the canonicalized ``(a, b)`` operands when the contraction
    should lower to the fused kernel, or None for the XLA fallback.
    (Probing only — ``maybe_dispatch`` records the explain decision.)
    """
    canon, _ = _decide(a, b, policy, dims, _cfg(cfg))
    return None if canon is None else canon[:2]


def maybe_dispatch(a, b, policy: PrecisionPolicy, dims, cfg=None):
    """Return the fused-kernel result, or None to fall back to XLA.

    Called from ``repro.core.policy._dot_impl`` for every split-policy
    contraction (forward and backward).  Under an installed mesh the call
    runs per shard through the ``shard_map`` wrapper (rule 6).  Declines
    record their rule in the explain table here; launches record inside
    ``_guarded``.
    """
    cfg = _cfg(cfg)
    canon, rule = _decide(a, b, policy, dims, cfg)
    if canon is None:
        _explain("matmul", policy.name,
                 (tuple(a.shape), tuple(b.shape)), rule)
        return None
    at, bt, out_shape = canon
    from . import shmap
    mesh, plan = _mesh_plan_or_decline(
        lambda m: shmap.matmul_plan(at.shape, bt.shape, m), cfg)
    M, K = at.shape[-2], at.shape[-1]
    N = bt.shape[-1]
    B = at.shape[0] if at.ndim == 3 else 1
    ident = (policy.name,) + tuning.shape_bucket(B, M, N, K)
    if mesh is not None:
        if plan == "decline":         # decide() screens this; stay graceful
            _explain("matmul", policy.name, ident[1:], "mesh-declined")
            return None

        def _run():
            return shmap.sharded_matmul(at, bt, policy=policy.name,
                                        mesh=mesh, cfg=cfg, plan=plan)
    else:
        def _run():
            block = tuned_block(M, N, K, policy.name, batch=B, cfg=cfg)
            return ops.tcec_matmul(at, bt, policy=policy.name, block=block,
                                   interpret=cfg.interpret, cfg=cfg)
    out = _guarded("matmul", ident, cfg, _run, "kernel.matmul")
    return None if out is None else out.reshape(out_shape)


# ------------------------------------------------- attention dispatch

def _attention_reason(q, k, v, pol, cfg) -> str:
    """The attention rule walk: ``"fused"`` when eligible, else the
    declining rule's explain slug."""
    if not cfg.enabled or not cfg.flash_attention:
        return "hatch-disabled"
    if not eligible_policy(pol):
        return _policy_rule(pol)
    if not (cfg.force or jax.default_backend() == "tpu"):
        return "off-backend"
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        return "shape-unsupported"
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    if (k.shape[0] != B or v.shape[:3] != k.shape[:3] or k.shape[3] != hd
            or Hkv == 0 or H % Hkv):
        return "shape-unsupported"
    if min(S, T) < cfg.min_dim:
        return "below-min-dim"
    from . import shmap
    _, plan = _mesh_plan_or_decline(
        lambda mesh: shmap.attention_plan(q.shape, k.shape, mesh), cfg)
    if plan == "decline":
        return "mesh-declined"
    # even the minimum (128, 128) block must fit VMEM — extreme-rep GQA
    # (rep ~ 100+ query heads per KV head) declines to the XLA path
    # instead of tripping the kernel's budget assert inside jit
    from .tcec_attention import attn_vmem_bytes
    from .tcec_matmul import VMEM_BUDGET
    if attn_vmem_bytes((128, 128), H // Hkv, hd, v.shape[3],
                       pol) > VMEM_BUDGET:
        return "vmem-budget"
    return "fused"


def attention_eligible(q, k, v, *, policy, cfg=None) -> bool:
    """Trace-time eligibility of the fused attention kernel for these
    operands.  True iff: split bf16 policy; TPU backend or ``force``;
    model-layout 4-D shapes with ``H % Hkv == 0``; ``min(S, T) >=
    min_dim``; both escape hatches off; and — under an installed GSPMD
    mesh — the ``shard_map`` knob is on and ``kernels/shmap.py`` has a
    per-shard spec for these shapes (head- or q-sequence-sharded), in
    which case the kernel runs per device under ``shard_map``.  An
    unsupported spec declines to the pdot fallbacks, which carry the
    context-parallel sharding constraints.

    Declines record their rule in the explain table here (the sdpa call
    sites pre-check eligibility and skip :func:`attention` entirely when
    False); acceptances record inside ``_guarded`` at launch.
    """
    from repro.core.policy import get_policy
    cfg = _cfg(cfg)
    pol = get_policy(policy)
    reason = _attention_reason(q, k, v, pol, cfg)
    if reason != "fused":
        _explain("attention", pol.name,
                 (tuple(q.shape), tuple(k.shape)), reason)
        return False
    return True


def attention(q, k, v, *, policy, q_pos=None, k_pos=None, causal: bool = True,
              window=0, softcap: float | None = None, cfg=None):
    """Route a model attention call to the fused TCEC flash-attention
    kernel, or return None for the pdot-composition fallback.

    Called from ``models.layers.sdpa`` (and the MLA / cross-attention
    variants) with model-layout operands: q ``(B, S, H, hd)``, k/v
    ``(B, T, Hkv, hd[v])``.  Eligibility mirrors :func:`decide`:
    split bf16 policy, TPU backend (or ``force`` -> interpret mode),
    ``min(S, T) >= min_dim``, and both escape hatches off
    (``REPRO_DISABLE_PALLAS`` disables all kernels,
    ``REPRO_DISABLE_FLASH_ATTN`` just this one).  ``window`` may be a
    traced scalar — it feeds the kernel as a runtime operand, so the
    decision never depends on its value.

    NB the raw kernel has no VJP: differentiated call sites must go
    through ``models.layers.sdpa`` (or ``_fused_sdpa``), whose custom_vjp
    recomputes the backward via the pdot composition.
    """
    from repro.core.policy import get_policy
    cfg = _cfg(cfg)
    pol = get_policy(policy)
    if not attention_eligible(q, k, v, policy=pol, cfg=cfg):
        return None
    from . import shmap
    mesh, plan = _mesh_plan_or_decline(
        lambda m: shmap.attention_plan(q.shape, k.shape, m), cfg)
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    ident = (pol.name, B, Hkv, H // Hkv,
             tuning._round_up(S, 128), tuning._round_up(T, 128))
    if mesh is not None:
        if plan == "decline":         # eligibility screens this; graceful
            _explain("attention", pol.name, ident[1:], "mesh-declined")
            return None
        return _guarded(
            "attention", ident, cfg,
            lambda: shmap.sharded_attention(q, k, v, q_pos, k_pos,
                                            policy=pol.name, causal=causal,
                                            window=window, softcap=softcap,
                                            mesh=mesh, cfg=cfg, plan=plan),
            "kernel.attention")

    def _run():
        from .tcec_attention import tcec_attention
        block = cfg.attn_block
        if block is None:
            block = tuning.get_attention_block(B, Hkv, H // Hkv, S, T, hd,
                                               v.shape[3], pol.name,
                                               causal=causal, cfg=cfg)
        return tcec_attention(q, k, v, q_pos, k_pos, policy=pol.name,
                              causal=causal, window=window, softcap=softcap,
                              block=block, interpret=cfg.interpret)
    return _guarded("attention", ident, cfg, _run, "kernel.attention")


# -------------------------------------------- paged decode-attention
#
# Decode-time attention against the serving engine's paged KV cache
# (serving/kv_cache.py): K/V live in fixed-size pages of a shared pool,
# addressed through per-sequence block tables.  The fused kernel
# (kernels/tcec_paged_attention.py) gathers the pages via scalar-prefetch
# BlockSpecs and runs TCEC-split QK^T / P·V; the fallback (the caller's
# gather + ``attention_decode`` math) is the verification oracle.

def _layer_view(pages):
    """The shape of one layer's pool: a stacked ``(L, NP, Hkv, ps, hd)``
    pool is judged by its ``(NP, Hkv, ps, hd)`` entry (the kernels read
    one entry per call, selected by a layer index)."""
    return jax.ShapeDtypeStruct(pages.shape[1:], pages.dtype)


def attention_decode_eligible(q, k_pages, v_pages, *, policy,
                              cfg=None) -> bool:
    """Trace-time eligibility of the paged decode-attention kernel.

    True iff: split bf16 policy; TPU backend or ``force``; decode-layout
    shapes — q ``(B, H, hd)``, pools ``(NP, Hkv, ps, hd[v])`` with
    ``H % Hkv == 0``; a single page fits VMEM; the hatches are off
    (``REPRO_DISABLE_PALLAS`` wholesale, ``REPRO_DISABLE_PAGED_ATTN``
    granular); and — under an installed GSPMD mesh — the ``shard_map``
    knob is on and ``kernels/shmap.py`` supports the layout (KV heads on
    ``model``, batch on the data axes; block tables stay device-local).
    No ``min_dim`` gate: decode rows are ``rep``-tall by construction —
    the page gather, not the tile size, is the point.
    """
    from repro.core.policy import get_policy
    cfg = _cfg(cfg)
    pol = get_policy(policy)
    reason = _paged_reason(q, k_pages, v_pages, pol, cfg)
    if reason != "fused":
        _explain("paged_attention", pol.name,
                 (tuple(q.shape), tuple(k_pages.shape)), reason)
        return False
    return True


def _paged_reason(q, k_pages, v_pages, pol, cfg) -> str:
    """The paged decode-attention rule walk: ``"fused"`` when eligible,
    else the declining rule's explain slug."""
    if not cfg.enabled or not cfg.paged_attention:
        return "hatch-disabled"
    if not eligible_policy(pol):
        return _policy_rule(pol)
    if not (cfg.force or jax.default_backend() == "tpu"):
        return "off-backend"
    if q.ndim != 3 or k_pages.ndim != 4 or v_pages.ndim != 4:
        return "shape-unsupported"
    B, H, hd = q.shape
    NP, Hkv, ps, hd2 = k_pages.shape
    if (hd2 != hd or v_pages.shape[:3] != k_pages.shape[:3]
            or Hkv == 0 or H % Hkv):
        return "shape-unsupported"
    from . import shmap
    _, plan = _mesh_plan_or_decline(
        lambda mesh: shmap.paged_plan(q.shape, k_pages.shape, mesh), cfg)
    if plan == "decline":
        return "mesh-declined"
    from .tcec_paged_attention import paged_vmem_bytes
    from .tcec_matmul import VMEM_BUDGET
    if paged_vmem_bytes(1, ps, H // Hkv, hd, v_pages.shape[3],
                        pol) > VMEM_BUDGET:
        return "vmem-budget"
    return "fused"


def attention_decode(q, k_pages, v_pages, block_tables, lengths, *, policy,
                     window=0, softcap: float | None = None, layer=None,
                     cfg=None):
    """Route a paged decode-attention call to the fused kernel, or return
    None for the gather-and-attend fallback.

    Called from ``models.layers.attention_decode_paged`` with one query
    token per sequence slot: q ``(B, H, hd)``, pools ``(NP, Hkv, ps,
    hd[v])`` — or, with ``layer`` given, a stack of them ``(L, NP, Hkv,
    ps, hd[v])`` read at that entry (the decode step's in-place path) —
    ``block_tables`` ``(B, maxp)`` i32, ``lengths`` ``(B,)`` i32
    counting valid tokens *including* the current one (whose K/V must
    already be written to its page).  ``window`` may be a traced scalar.

    NB the kernel is **more accurate** than the fallback: it TCEC-splits
    the f32 query and probs where the dense decode path rounds both to
    bf16 (tests/test_serving.py asserts the ordering against an f32
    oracle).  ``REPRO_DISABLE_PAGED_ATTN=1`` restores exact dense parity.
    """
    from repro.core.policy import get_policy
    cfg = _cfg(cfg)
    pol = get_policy(policy)
    kl, vl = ((k_pages, v_pages) if layer is None
              else (_layer_view(k_pages), _layer_view(v_pages)))
    if not attention_decode_eligible(q, kl, vl, policy=pol, cfg=cfg):
        return None
    from . import shmap
    mesh, plan = _mesh_plan_or_decline(
        lambda m: shmap.paged_plan(q.shape, kl.shape, m), cfg)
    B, H, hd = q.shape
    NP, Hkv, ps, _ = kl.shape
    ident = (pol.name, B, Hkv, H // Hkv, block_tables.shape[1], ps)
    if mesh is not None:
        if plan == "decline":         # eligibility screens this; graceful
            _explain("paged_attention", pol.name, ident[1:], "mesh-declined")
            return None
        return _guarded(
            "paged_attention", ident, cfg,
            lambda: shmap.sharded_paged_attention(
                q, k_pages, v_pages, block_tables, lengths, policy=pol.name,
                window=window, softcap=softcap, layer=layer, mesh=mesh,
                cfg=cfg, plan=plan),
            "kernel.paged")

    def _run():
        from .tcec_paged_attention import tcec_paged_attention
        g = cfg.paged_block
        if g is None:
            g = tuning.get_paged_block(B, Hkv, H // Hkv,
                                       block_tables.shape[1], ps, hd,
                                       vl.shape[3], pol.name, cfg=cfg)
        return tcec_paged_attention(q, k_pages, v_pages, block_tables,
                                    lengths, policy=pol.name, window=window,
                                    softcap=softcap, pages_per_step=g,
                                    layer=layer, interpret=cfg.interpret)
    return _guarded("paged_attention", ident, cfg, _run, "kernel.paged")


# ------------------------------------------------- in-place K/V write
#
# The decode step's in-place path (models/lm.py ``stack_decode_paged``)
# carries every layer's pool as one stack through its layer scan: each
# layer writes its token's K/V with the aliased page-write kernel
# (kernels/kv_page_write.py), then reads the stack with the paged kernel
# at its layer index, so no pool bytes move but the new token's.  The
# path runs only where both kernels are eligible; otherwise the scan
# slices each layer's pool and scatters with XLA, as before.

def kv_write_reason(q, pool, *, policy, cfg=None) -> str:
    """The in-place path's rule walk, recording nothing: ``"fused"`` when
    the page-write kernel and the paged kernel are both eligible for
    queries ``(B, H, hd)`` and one stack's pools ``{"k", "v"}``, each
    head-major ``(L, NP, Hkv, ps, hd[v])``, else the declining rule.  The
    rules are the paged kernel's (hatches, policy, backend, mesh plan,
    VMEM) judged on one layer's pool: the write kernel's one-page blocks
    need nothing more.  Other pools (MLA's latent ones) are
    ``shape-unsupported``."""
    from repro.core.policy import get_policy
    cfg = _cfg(cfg)
    pol = get_policy(policy)
    if set(pool) != {"k", "v"}:
        return "shape-unsupported"
    k_pool, v_pool = pool["k"], pool["v"]
    if (k_pool.ndim != 5 or v_pool.ndim != 5
            or k_pool.shape[:4] != v_pool.shape[:4]):
        return "shape-unsupported"
    return _paged_reason(q, _layer_view(k_pool), _layer_view(v_pool), pol,
                         cfg)


def kv_write_eligible(q, pool, *, policy, cfg=None) -> bool:
    """:func:`kv_write_reason` is ``"fused"``; a decline is recorded in
    the explain table under ``kv_write`` (the launch records inside
    ``_guarded``)."""
    from repro.core.policy import get_policy
    pol = get_policy(policy)
    reason = kv_write_reason(q, pool, policy=pol, cfg=cfg)
    if reason != "fused":
        leaf = jax.tree.leaves(pool)[0]
        _explain("kv_write", pol.name,
                 (tuple(q.shape), tuple(leaf.shape)), reason)
        return False
    return True


def kv_write(k_pool, v_pool, k_new, v_new, page, off, layer, *, policy,
             cfg=None):
    """Write one token per slot into stacked pools in place, or return
    None for the caller's XLA scatter.

    k_pool/v_pool ``(L, NP, Hkv, ps, hd[v])``; k_new/v_new ``(B, Hkv,
    hd[v])``; page/off ``(B,)`` i32 (each slot's current page and row);
    ``layer`` a traced scalar.  The caller has judged the path with
    :func:`kv_write_eligible`; this runs the kernel behind the circuit
    breaker (fault site ``kernel.kv_write``), per shard under a mesh.
    ``policy`` only keys the explain entry: the write is exact."""
    from repro.core.policy import get_policy
    cfg = _cfg(cfg)
    pol = get_policy(policy)
    B, Hkv = k_new.shape[:2]
    _, _, _, ps, hd = k_pool.shape
    ident = (pol.name, B, Hkv, ps, hd)
    from . import shmap
    mesh, plan = _mesh_plan_or_decline(
        lambda m: shmap.paged_plan((B, Hkv, hd), k_pool.shape[1:], m), cfg)
    if plan == "decline":                 # eligibility screens this
        _explain("kv_write", pol.name, ident[1:], "mesh-declined")
        return None
    if mesh is not None:
        def _run():
            return shmap.sharded_kv_write(k_pool, v_pool, k_new, v_new,
                                          page, off, layer, mesh=mesh,
                                          cfg=cfg, plan=plan)
    else:
        def _run():
            from .kv_page_write import kv_page_write
            return kv_page_write(k_pool, v_pool, k_new, v_new, page, off,
                                 layer, interpret=cfg.interpret)
    return _guarded("kv_write", ident, cfg, _run, "kernel.kv_write")


# ------------------------------------------------- epilogue-fusion hook

def epilogue_eligible(policy: PrecisionPolicy, cfg=None) -> bool:
    """Whether ``models.layers.fused_linear`` may fold its bias/activation
    into the kernel's scaled epilogue under the given config.

    Declines under an installed GSPMD mesh: the fused path flattens
    ``(B, S, D) -> (B*S, D)``, and that reshape replicates a sharded
    batch dim under GSPMD — the unfused pdot path dispatches through the
    ``shard_map`` wrapper instead (same GEMMs, unfused epilogue).

    Records every decision (shape-independent, so the bucket is empty);
    the GEMM underneath still records its own matmul decision."""
    from repro.parallel import ctx
    cfg = _cfg(cfg)
    if not cfg.enabled or not cfg.fuse_epilogue:
        rule = "hatch-disabled"
    elif not eligible_policy(policy):
        rule = _policy_rule(policy)
    elif ctx.current_mesh() is not None:
        rule = "mesh-declined"
    elif not (cfg.force or jax.default_backend() == "tpu"):
        rule = "off-backend"
    else:
        rule = "fused"
    _explain("epilogue", policy.name, (), rule)
    return rule == "fused"


def tuned_block(M: int, N: int, K: int, policy_name: str,
                batch: int = 1, cfg=None) -> tuple[int, int, int]:
    """Config override if set, else the autotuner (measured or heuristic)."""
    cfg = _cfg(cfg)
    if cfg.block is not None:
        return cfg.block
    return tuning.get_block(M, N, K, policy_name, batch=batch, cfg=cfg)


# ------------------------------------------------------ deprecation shims
#
# The pre-repro.numerics surface.  Each shim emits exactly one
# DeprecationWarning and delegates; tests/test_deprecation.py runs them
# under -W error::DeprecationWarning to pin the warning set.

def override(**kw):
    """Deprecated: use ``repro.numerics.use(...)``."""
    numerics._deprecated("repro.kernels.dispatch.override()",
                         "repro.numerics.use()")
    return numerics.use(**kw)


def config() -> numerics.NumericsConfig:
    """Deprecated: use ``repro.numerics.active()``."""
    numerics._deprecated("repro.kernels.dispatch.config()",
                         "repro.numerics.active()")
    return numerics.active()


def reload_config() -> numerics.NumericsConfig:
    """Deprecated: use ``repro.numerics.reload_env_defaults()``."""
    numerics._deprecated("repro.kernels.dispatch.reload_config()",
                         "repro.numerics.reload_env_defaults()")
    return numerics.reload_env_defaults()


def env_flag(name: str) -> bool:
    """Deprecated: use ``repro.numerics.env_value(name)``."""
    numerics._deprecated("repro.kernels.dispatch.env_flag()",
                         "repro.numerics.env_value()")
    return numerics._legacy_flag(name)


def __getattr__(name):
    if name == "DispatchConfig":
        numerics._deprecated("repro.kernels.dispatch.DispatchConfig",
                             "repro.numerics.NumericsConfig")
        return numerics.NumericsConfig
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
