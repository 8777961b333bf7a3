"""Sharded TCEC: ``shard_map`` dispatch for the Pallas kernels under a mesh.

Before this module, every dispatch site declined the moment a GSPMD mesh
was installed (``parallel/ctx.py``): a bare ``pallas_call`` inside a
GSPMD program is replicated per device, so the exact configuration the
production posture cares about — sharded training and serving — silently
fell back to the XLA term expansion.  This module closes that gap: it
maps the framework's mesh conventions (``parallel/sharding.py``: batch on
the ``pod``/``data`` axes, heads / hidden / sequence on ``model``) onto
per-shard operand ``PartitionSpec``s and wraps each kernel call in
``jax.shard_map``, so every device runs the fused
kernel on *its shard only* and GSPMD inserts at most a reshard on entry.

Three plan builders — :func:`matmul_plan`, :func:`attention_plan`,
:func:`paged_plan` — decide, from static shapes and the installed mesh,
which dims each mesh axis shards.  A plan is ``None`` when some axis of
size > 1 cannot be assigned to a dividing dim (or carries a name outside
the framework's ``pod``/``data``/``model`` convention); dispatch then
declines to the XLA fallback, whose collectives GSPMD already shards well
(the *unsupported-spec decline path* — tested).  Axes of size 1 never
block a plan, so a single-device mesh still routes through the wrapper
(tests exercise the full code path without a multi-device runtime).

Reduction-order guarantee (the part that must be pinned, not just made to
run — Khattak & Mikaitis, "Accurate Models of NVIDIA Tensor Cores", and
Valpey et al.'s SMT formalization both show split-term summation order
changes the error bound):

  * **M/N/batch/head/sequence sharding** splits only *independent* output
    rows/columns across devices.  Every scale-group fold happens locally
    and completely; per-shard results are **bit-identical** to the
    unsharded kernel on the same data.
  * **K sharding** splits the contraction.  Each device folds its local
    partial products smallest-first (the paper's Code-3 epilogue,
    unchanged), and only *then* does one f32 ``psum`` combine the
    per-device partial GEMMs.  The cross-device sum is therefore an f32
    RN reduction of f32 partials — the same associativity class as the
    kernel's own f32 K-grid accumulation (the paper's RZ-avoidance is
    preserved; no split term ever crosses the wire), so the error bound
    gains only the usual log₂(shards) f32 summation ULPs.  The order —
    local fold FIRST, f32 psum AFTER — is asserted by tests
    (``tests/test_shmap.py``) and documented in ``docs/parallel.md``.

Autotuning under a plan measures the **local tile**, not the global
shape: block lookups go to the ``backend/shmap/...`` cache namespace
keyed by the per-shard problem (``kernels/tuning.py``), since the tile
the kernel actually runs is the shard.

The :func:`counters` view increments once per wrapped dispatch at trace
time (the ``kernels/shmap/calls`` registry counter in
:mod:`repro.obs.metrics`) — the acceptance hook tests use to assert
that a mesh-installed program really routed through the kernels.
"""
from __future__ import annotations

from collections.abc import Mapping
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import numerics
from repro.obs import metrics as _metrics

# Cache namespace for per-shard tuning keys: ``backend/shmap/...``.
NAMESPACE = "shmap"

#: the wrapped kernels (label values of ``kernels/shmap/calls``)
KERNELS = ("matmul", "attention", "paged", "kv_write")


def _bump(kernel: str):
    _metrics.counter("kernels/shmap/calls").inc(kernel=kernel)


def counters() -> dict[str, int]:
    """Trace-time sharded-dispatch counts, ``{kernel: calls}`` (zeroes
    included).  Backed by the ``kernels/shmap/calls`` registry counter,
    so ``repro.obs.snapshot()`` carries the same numbers."""
    c = _metrics.counter("kernels/shmap/calls")
    return {k: int(c.value(kernel=k)) for k in KERNELS}


def reset_counters():
    _metrics.counter("kernels/shmap/calls").reset()


class _CallsView(Mapping):
    """Read-only live view backing the deprecated :data:`CALLS` dict."""

    def __getitem__(self, key):
        if key not in KERNELS:
            raise KeyError(key)
        return counters()[key]

    def __iter__(self):
        return iter(KERNELS)

    def __len__(self):
        return len(KERNELS)

    def __repr__(self):
        return repr(counters())


def __getattr__(name):
    if name == "CALLS":
        numerics._deprecated("repro.kernels.shmap.CALLS",
                             "repro.kernels.shmap.counters()")
        return _CallsView()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def reset_calls():
    """Deprecated: use :func:`reset_counters`."""
    numerics._deprecated("repro.kernels.shmap.reset_calls()",
                         "repro.kernels.shmap.reset_counters()")
    reset_counters()


def _cfg(cfg) -> numerics.NumericsConfig:
    return cfg if cfg is not None else numerics.active()


def _interpret(cfg) -> bool:
    if cfg.interpret is not None:
        return cfg.interpret
    return jax.default_backend() != "tpu"


# ----------------------------------------------------------------- plans
#
# The framework's axis convention (parallel/sharding.py): ``pod``/``data``
# are the data-parallel axes, ``model`` is the tensor-parallel axis.  A
# plan assigns every size->1 mesh axis to a dim it divides; unknown axis
# names of size > 1 make the spec unsupported.

def _dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh, name: str) -> int:
    return int(mesh.shape[name]) if name in mesh.axis_names else 1


def _dp_size(mesh) -> int:
    n = 1
    for a in _dp_axes(mesh):
        n *= int(mesh.shape[a])
    return n


def _known_axes_only(mesh) -> bool:
    return all(a in ("pod", "data", "model") or int(mesh.shape[a]) == 1
               for a in mesh.axis_names)


@dataclass(frozen=True)
class MatmulPlan:
    """Per-shard operand specs for one canonical ``(B?, M, K) @ (B?, K, N)``.

    ``psum_axes`` is non-empty iff the contraction (K) is sharded: the body
    then f32-``psum``s the *locally folded* partial GEMM across those axes
    (see the module docstring's reduction-order guarantee).  ``local`` is
    the per-shard ``(B, M, N, K)`` the autotuner keys on.
    """
    a_spec: P
    b_spec: P
    out_spec: P
    psum_axes: tuple[str, ...]
    local: tuple[int, int, int, int]
    sharded_dim: str                 # "batch" | "M" | "N" | "K" | "none"


def matmul_plan(a_shape, b_shape, mesh) -> MatmulPlan | None:
    """Assign mesh axes to the dims of a canonical GEMM, or None.

    Data-parallel axes take the batch dim (3-D operands) or M (2-D).  The
    ``model`` axis prefers N (column parallel — matches the up-projection
    weight sharding), then K (row parallel: local fold + f32 psum — the
    down-projection), then M (row-sharded activations).  Any size->1 axis
    left unassignable makes the spec unsupported (return None).
    """
    if not _known_axes_only(mesh):
        return None
    batched = len(a_shape) == 3
    B = a_shape[0] if batched else 1
    M, K = a_shape[-2], a_shape[-1]
    N = b_shape[-1]
    dp = _dp_axes(mesh)
    dsize = _dp_size(mesh)
    msize = _axis_size(mesh, "model")

    Bl, Ml, Nl, Kl = B, M, N, K
    a_dims = [None] * len(a_shape)
    b_dims = [None] * len(b_shape)
    o_dims = [None] * len(a_shape)

    # data-parallel axes -> batch (batched) or M (2-D)
    m_taken = False
    if dsize > 1:
        if batched and B % dsize == 0:
            a_dims[0] = b_dims[0] = o_dims[0] = dp if len(dp) > 1 else dp[0]
            Bl = B // dsize
        elif M % dsize == 0:
            a_dims[-2] = o_dims[-2] = dp if len(dp) > 1 else dp[0]
            Ml = M // dsize
            m_taken = True
        else:
            return None

    psum: tuple[str, ...] = ()
    sharded = "none"
    if msize > 1:
        if N % msize == 0:
            b_dims[-1] = o_dims[-1] = "model"
            Nl = N // msize
            sharded = "N"
        elif K % msize == 0:
            a_dims[-1] = b_dims[-2] = "model"
            Kl = K // msize
            psum = ("model",)
            sharded = "K"
        elif M % msize == 0 and not m_taken:
            a_dims[-2] = o_dims[-2] = "model"
            Ml = M // msize
            sharded = "M"
        else:
            return None
    elif dsize > 1:
        sharded = "batch" if (batched and Bl != B) else "M"

    return MatmulPlan(P(*a_dims), P(*b_dims), P(*o_dims), psum,
                      (Bl, Ml, Nl, Kl), sharded)


@dataclass(frozen=True)
class AttentionPlan:
    """Per-shard specs for model-layout attention operands.

    ``mode`` is ``"heads"`` (KV-head groups on ``model`` — the TP layout
    matching the wq/wk/wv weight sharding) or ``"qseq"`` (query-sequence
    on ``model`` with K/V replicated — context parallelism; the causal /
    window masks stay correct because the *global* position vectors are
    sharded alongside q, so each shard sees its true offsets).  ``local``
    is the per-shard ``(B, Hkv, S, T)`` the autotuner keys on.
    """
    q_spec: P
    k_spec: P
    v_spec: P
    qp_spec: P
    kp_spec: P
    out_spec: P
    local: tuple[int, int, int, int]
    mode: str


def attention_plan(q_shape, k_shape, mesh) -> AttentionPlan | None:
    """q ``(B, S, H, hd)``, k ``(B, T, Hkv, hd)`` -> plan or None.

    ``model`` prefers head sharding (requires ``Hkv % msize == 0`` so the
    contiguous H chunks align with whole GQA groups — q reshapes to
    ``(B, S, Hkv, rep, hd)`` KV-head-major), else q-sequence sharding
    (``S % msize == 0``).  Data-parallel axes take the batch.
    """
    if not _known_axes_only(mesh):
        return None
    B, S, H, _ = q_shape
    T, Hkv = k_shape[1], k_shape[2]
    dp = _dp_axes(mesh)
    dsize = _dp_size(mesh)
    msize = _axis_size(mesh, "model")

    bdim = None
    Bl = B
    if dsize > 1:
        if B % dsize != 0:
            return None
        bdim = dp if len(dp) > 1 else dp[0]
        Bl = B // dsize

    Hkvl, Sl = Hkv, S
    if msize > 1 and Hkv % msize == 0:
        mode = "heads"
        Hkvl = Hkv // msize
        q_spec = P(bdim, None, "model", None)
        k_spec = v_spec = P(bdim, None, "model", None)
        qp_spec = kp_spec = P(bdim, None)
        out_spec = P(bdim, None, "model", None)
    elif msize > 1 and S % msize == 0:
        mode = "qseq"
        Sl = S // msize
        q_spec = P(bdim, "model", None, None)
        k_spec = v_spec = P(bdim, None, None, None)
        qp_spec = P(bdim, "model")
        kp_spec = P(bdim, None)
        out_spec = P(bdim, "model", None, None)
    elif msize > 1:
        return None
    else:
        mode = "heads"
        q_spec = k_spec = v_spec = P(bdim, None, None, None)
        qp_spec = kp_spec = P(bdim, None)
        out_spec = P(bdim, None, None, None)
    return AttentionPlan(q_spec, k_spec, v_spec, qp_spec, kp_spec, out_spec,
                         (Bl, Hkvl, Sl, T), mode)


@dataclass(frozen=True)
class PagedPlan:
    """Per-shard specs for paged decode attention.

    The page pools shard their KV-head dim on ``model`` (each device owns
    its heads' slices of *every* page); block tables and lengths stay
    device-local — replicated over ``model``, batch-sharded over the
    data-parallel axes with the query.  ``local`` is the per-shard
    ``(B, Hkv)`` the pages-per-step autotuner keys on.
    """
    q_spec: P
    pool_spec: P
    bt_spec: P
    len_spec: P
    out_spec: P
    local: tuple[int, int]


def paged_plan(q_shape, pool_shape, mesh) -> PagedPlan | None:
    """q ``(B, H, hd)``, pools ``(NP, Hkv, ps, hd)`` -> plan or None."""
    if not _known_axes_only(mesh):
        return None
    B, H, _ = q_shape
    Hkv = pool_shape[1]
    dp = _dp_axes(mesh)
    dsize = _dp_size(mesh)
    msize = _axis_size(mesh, "model")

    bdim = None
    Bl = B
    if dsize > 1:
        if B % dsize != 0:
            return None
        bdim = dp if len(dp) > 1 else dp[0]
        Bl = B // dsize

    Hkvl = Hkv
    hdim = None
    if msize > 1:
        if Hkv % msize != 0:
            return None
        hdim = "model"
        Hkvl = Hkv // msize
    return PagedPlan(
        q_spec=P(bdim, hdim, None),
        pool_spec=P(None, hdim, None, None),
        bt_spec=P(bdim, None),
        len_spec=P(bdim),
        out_spec=P(bdim, hdim, None),
        local=(Bl, Hkvl))


# -------------------------------------------------------------- wrappers

def sharded_matmul(a, b, *, policy: str, mesh, cfg=None,
                   plan: MatmulPlan | None = None) -> jax.Array:
    """Run the fused TCEC GEMM per shard under ``mesh``.

    Operands are the canonical ``(B?, M, K) @ (B?, K, N)`` the dispatch
    layer produces.  K-sharded plans fold each shard's scale groups
    locally (the paper's smallest-first epilogue, untouched) and then
    ``psum`` the f32 partial products — see the module docstring for why
    that order preserves the error bound.
    """
    from . import ops, tuning
    cfg = _cfg(cfg)
    if plan is None:
        plan = matmul_plan(a.shape, b.shape, mesh)
    assert plan is not None, (a.shape, b.shape, dict(mesh.shape))
    Bl, Ml, Nl, Kl = plan.local
    block = cfg.block
    if block is None:
        block = tuning.get_block(Ml, Nl, Kl, policy, batch=Bl, cfg=cfg,
                                 namespace=NAMESPACE)
    interpret = _interpret(cfg)

    def body(x, y):
        out = ops.tcec_matmul(x, y, policy=policy, block=block,
                              interpret=interpret, cfg=cfg)
        if plan.psum_axes:
            # f32 RN sum of fully-folded f32 partials — AFTER the local
            # smallest-first group fold, never across split terms
            out = jax.lax.psum(out, plan.psum_axes)
        return out

    _bump("matmul")
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(plan.a_spec, plan.b_spec),
                         out_specs=plan.out_spec, check_vma=False)(a, b)


def _pos_2d(pos, B, n):
    """Global (B, n) i32 positions — materialized BEFORE shard_map so a
    q-sequence shard sees its true global offsets, not a local arange."""
    if pos is None:
        pos = jnp.arange(n, dtype=jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        pos = jnp.broadcast_to(pos[None], (B, n))
    return pos


def sharded_attention(q, k, v, q_pos=None, k_pos=None, *, policy: str,
                      causal: bool = True, window=0,
                      softcap: float | None = None, mesh, cfg=None,
                      plan: AttentionPlan | None = None) -> jax.Array:
    """Run the fused TCEC flash-attention kernel per shard under ``mesh``.

    Model-layout operands (q ``(B, S, H, hd)``, k/v ``(B, T, Hkv,
    hd[v])``).  Head sharding gives each device whole GQA groups (K/V
    never replicated across ``model``); q-sequence sharding replicates
    K/V and shards the query rows, with the causal/window masks offset by
    the shard's global position via the sharded position vectors.  Either
    way the softmax and every scale-group fold complete locally, so each
    shard is bit-identical to the unsharded kernel on the same rows.
    """
    from . import tuning
    cfg = _cfg(cfg)
    if plan is None:
        plan = attention_plan(q.shape, k.shape, mesh)
    assert plan is not None, (q.shape, k.shape, dict(mesh.shape))
    B, S, H, hd = q.shape
    T, Hkv, hdv = k.shape[1], k.shape[2], v.shape[3]
    Bl, Hkvl, Sl, Tl = plan.local
    block = cfg.attn_block
    if block is None:
        block = tuning.get_attention_block(Bl, Hkvl, H // Hkv, Sl, Tl, hd,
                                           hdv, policy, causal=causal,
                                           cfg=cfg, namespace=NAMESPACE)
    interpret = _interpret(cfg)
    qp = _pos_2d(q_pos, B, S)
    kp = _pos_2d(k_pos, B, T)
    win = jnp.asarray(0 if window is None else window, jnp.int32)

    def body(qs, ks, vs, qps, kps, w):
        from .tcec_attention import tcec_attention
        return tcec_attention(qs, ks, vs, qps, kps, policy=policy,
                              causal=causal, window=w, softcap=softcap,
                              block=block, interpret=interpret)

    _bump("attention")
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(plan.q_spec, plan.k_spec, plan.v_spec, plan.qp_spec,
                  plan.kp_spec, P()),
        out_specs=plan.out_spec, check_vma=False)(q, k, v, qp, kp, win)


def _pool_spec(plan: PagedPlan, ndim: int) -> P:
    """The plan's pool spec for a per-layer ``(NP, Hkv, ps, hd)`` pool, or
    for a stack of them with the leading layer axis unsharded."""
    return P(*((None,) * (ndim - 4) + tuple(plan.pool_spec)))


def sharded_paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                            policy: str, window=0,
                            softcap: float | None = None, layer=None,
                            mesh, cfg=None,
                            plan: PagedPlan | None = None) -> jax.Array:
    """Run the fused paged decode-attention kernel per shard under ``mesh``.

    The pools shard on the KV-head dim (``model``); block tables and
    lengths stay device-local (replicated over ``model``), so the page
    gather on each device reads its own pool shard with the *same* table —
    no cross-device page traffic.  Batch shards over the data axes.  With
    ``layer`` given the pools are stacks ``(L, NP, Hkv, ps, hd)``, the
    layer axis unsharded and the index replicated.
    """
    from . import tuning
    cfg = _cfg(cfg)
    stacked = layer is not None
    pool_shape = k_pages.shape[1:] if stacked else k_pages.shape
    if plan is None:
        plan = paged_plan(q.shape, pool_shape, mesh)
    assert plan is not None, (q.shape, k_pages.shape, dict(mesh.shape))
    B, H, hd = q.shape
    NP, Hkv, ps, _ = pool_shape
    hdv = v_pages.shape[-1]
    Bl, Hkvl = plan.local
    maxp = block_tables.shape[1]
    g = cfg.paged_block
    if g is None:
        g = tuning.get_paged_block(Bl, Hkvl, H // Hkv, maxp, ps, hd, hdv,
                                   policy, cfg=cfg, namespace=NAMESPACE)
    interpret = _interpret(cfg)
    bt = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    # the (possibly traced) window and layer ride as explicit replicated
    # operands: shard_map bodies must not close over outer-trace values
    win = jnp.asarray(0 if window is None else window, jnp.int32)
    lyr = jnp.asarray(0 if layer is None else layer, jnp.int32)

    def body(qs, kps, vps, bts, lns, w, ly):
        from .tcec_paged_attention import tcec_paged_attention
        return tcec_paged_attention(qs, kps, vps, bts, lns, policy=policy,
                                    window=w, softcap=softcap,
                                    pages_per_step=g,
                                    layer=ly if stacked else None,
                                    interpret=interpret)

    pool = _pool_spec(plan, k_pages.ndim)
    _bump("paged")
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(plan.q_spec, pool, pool, plan.bt_spec, plan.len_spec, P(),
                  P()),
        out_specs=plan.out_spec, check_vma=False)(
            q, k_pages, v_pages, bt, lens, win, lyr)


def sharded_kv_write(k_pool, v_pool, k_new, v_new, page, off, layer, *,
                     mesh, cfg=None, plan: PagedPlan | None = None):
    """Run the in-place page write per shard under ``mesh``.

    Stacked pools ``(L, NP, Hkv, ps, hd)`` shard their KV heads as the
    paged plan does (layer axis unsharded); each device writes its heads
    of every slot's token into its pool shard.  Pools are replicated over
    the data axes, so every slot's token, page and row are replicated
    there too (gathered from the batch shards on entry): each replica
    writes the same tokens and the replicas stay equal.
    """
    from .kv_page_write import kv_page_write
    cfg = _cfg(cfg)
    B, Hkv, hd = k_new.shape
    if plan is None:
        plan = paged_plan((B, Hkv, hd), k_pool.shape[1:], mesh)
    assert plan is not None, (k_new.shape, k_pool.shape, dict(mesh.shape))
    interpret = _interpret(cfg)
    hdim = plan.pool_spec[1]
    pool = _pool_spec(plan, k_pool.ndim)
    tok = P(None, hdim, None)

    def body(kp, vp, kn, vn, pg, of, ly):
        return kv_page_write(kp, vp, kn, vn, pg, of, ly,
                             interpret=interpret)

    _bump("kv_write")
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(pool, pool, tok, tok, P(None), P(None), P()),
        out_specs=(pool, pool), check_vma=False)(
            k_pool, v_pool, k_new, v_new, jnp.asarray(page, jnp.int32),
            jnp.asarray(off, jnp.int32), jnp.asarray(layer, jnp.int32))
