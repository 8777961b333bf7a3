"""Serving subsystem: paged KV cache, continuous batching, paged kernel.

The load-bearing contract: with the paged kernel hatch closed (the CPU
default), **greedy engine output is token-identical to the dense-cache
``generate_dense`` path** — the page gather feeds bitwise the same attend
as the dense cache, across transformer / GQA / MLA(+MoE) smoke archs,
same-length batches and mixed-length continuous batching alike.  On top:
scheduler policy units (FIFO admission, LIFO preemption, slot recycling),
sampling units, page-pool units, and the paged decode kernel's interpret-
mode parity + accuracy ordering against an f32 oracle.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import numerics
from repro.configs import get_smoke_config
from repro.kernels import dispatch, tuning
from repro.kernels.tcec_matmul import VMEM_BUDGET
from repro.kernels.tcec_paged_attention import (paged_vmem_bytes,
                                                tcec_paged_attention)
from repro.core.policy import get_policy
from repro.models import get_model
from repro.models import layers as L
from repro.serving import (Engine, PagePool, PagePoolError, SamplingParams,
                           Scheduler, sampling)
from repro.serving.kv_cache import inverse_permutation, permute_pages
from repro.serving.scheduler import RequestState


def _rand(shape, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


_PARAMS_CACHE = {}


def _model_and_params(arch):
    if arch not in _PARAMS_CACHE:
        cfg = get_smoke_config(arch)
        model = get_model(cfg)
        _PARAMS_CACHE[arch] = (cfg, model,
                               model.init(jax.random.PRNGKey(0)))
    return _PARAMS_CACHE[arch]


def _prompts(cfg, shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, shape), jnp.int32)


# ================================================================ pool

def test_page_pool_alloc_free_roundtrip():
    pool = PagePool(num_pages=8, page_size=4)
    assert pool.num_free == 7            # page 0 reserved (scrap page)
    a = pool.alloc(3)
    assert len(a) == 3 and 0 not in a and pool.num_live == 3
    assert pool.alloc(5) is None         # all-or-nothing
    assert pool.num_free == 4            # failed alloc changed nothing
    pool.free(a)
    assert pool.num_free == 7 and pool.num_live == 0
    with pytest.raises(PagePoolError):
        pool.free(a[:1])                 # double free


def test_page_pool_pages_for():
    pool = PagePool(num_pages=4, page_size=4)
    assert pool.pages_for(1) == 1
    assert pool.pages_for(4) == 1
    assert pool.pages_for(5) == 2
    assert pool.pages_for(0) == 1


def test_page_pool_defrag_compacts_live_pages():
    pool = PagePool(num_pages=10, page_size=2)
    a = pool.alloc(4)
    b = pool.alloc(3)
    pool.free(a)                         # leave holes below b's pages
    mapping = pool.defrag()
    assert sorted(mapping) == sorted(b)
    assert sorted(mapping.values()) == [1, 2, 3]   # compacted to the floor
    assert pool.num_live == 3 and pool.num_free == 6
    c = pool.alloc(6)                    # the holes are allocatable again
    assert c is not None and set(c).isdisjoint(mapping.values())


def test_permute_pages_moves_page_contents():
    pools = {"k": jnp.arange(2 * 4 * 2, dtype=jnp.float32).reshape(2, 4, 2)}
    perm = inverse_permutation({3: 1, 1: 2}, 4)
    out = permute_pages(pools, perm)
    np.testing.assert_array_equal(np.asarray(out["k"][:, 1]),
                                  np.asarray(pools["k"][:, 3]))
    np.testing.assert_array_equal(np.asarray(out["k"][:, 2]),
                                  np.asarray(pools["k"][:, 1]))


# ============================================================= sampling

def test_sample_greedy_is_argmax():
    logits = _rand((3, 32), 0)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(3, dtype=jnp.uint32))
    toks = sampling.sample(logits, jnp.zeros(3), jnp.zeros(3, jnp.int32),
                           jnp.ones(3), keys)
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_sample_top_k_one_is_argmax_at_any_temperature():
    logits = _rand((4, 64), 1)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(4, dtype=jnp.uint32))
    toks = sampling.sample(logits, jnp.full(4, 5.0),
                           jnp.ones(4, jnp.int32), jnp.ones(4), keys)
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_sample_top_k_never_leaves_the_top_k():
    logits = _rand((2, 128), 2)
    top8 = set(np.asarray(jnp.argsort(-logits, axis=-1)[:, :8])[0].tolist())
    for seed in range(20):
        keys = jnp.stack([jax.random.PRNGKey(seed)] * 2)
        toks = sampling.sample(logits, jnp.ones(2), jnp.full(2, 8, jnp.int32),
                               jnp.ones(2), keys)
        assert int(toks[0]) in top8


def test_sample_top_p_tiny_keeps_only_the_mode():
    logits = _rand((2, 64), 3)
    for seed in range(10):
        keys = jnp.stack([jax.random.PRNGKey(seed)] * 2)
        toks = sampling.sample(logits, jnp.ones(2), jnp.zeros(2, jnp.int32),
                               jnp.full(2, 1e-6), keys)
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.asarray(jnp.argmax(logits, -1)))


def test_sample_per_row_params_are_independent():
    """A greedy row and a hot sampled row coexist in one call, and a
    row's draw depends only on its own key — not batch composition."""
    logits = _rand((2, 256), 4)
    key = jax.random.PRNGKey(7)
    keys = jnp.stack([key, jax.random.PRNGKey(8)])
    toks = sampling.sample(logits, jnp.asarray([0.0, 1.0]),
                           jnp.zeros(2, jnp.int32), jnp.ones(2), keys)
    assert int(toks[0]) == int(jnp.argmax(logits[0]))
    solo = sampling.sample(logits[1:], jnp.ones(1), jnp.zeros(1, jnp.int32),
                           jnp.ones(1), key[None] * 0 + keys[1:])
    assert int(toks[1]) == int(solo[0])


# ============================================================ scheduler

def _mk_sched(num_pages=16, page_size=4, max_slots=2):
    return Scheduler(PagePool(num_pages, page_size), max_slots)


def test_scheduler_admits_fifo_into_free_slots():
    s = _mk_sched(max_slots=2)
    r1 = s.add([1] * 4, SamplingParams())
    r2 = s.add([2] * 4, SamplingParams())
    r3 = s.add([3] * 4, SamplingParams())
    admitted = s.admit()
    assert [r.rid for r in admitted] == [r1.rid, r2.rid]
    assert admitted[0].slot == 0 and admitted[1].slot == 1
    assert [r.rid for r in s.waiting] == [r3.rid]
    # slot recycling: finishing r1 lets r3 in, reusing slot 0
    s.finish(s.running[0])
    assert s.admit()[0].rid == r3.rid
    assert s.running[0].rid == r3.rid


def test_scheduler_admission_is_strict_fifo_no_bypass():
    s = _mk_sched(num_pages=4, page_size=4, max_slots=2)   # 3 free pages
    big = s.add([0] * 13, SamplingParams())    # needs 4 pages: can't fit
    s.add([0] * 2, SamplingParams())           # would fit, but queued behind
    assert s.admit() == []                     # head blocks the line
    assert s.waiting[0] is big


def test_scheduler_preempts_youngest_and_requeues_front():
    s = _mk_sched(num_pages=9, page_size=4, max_slots=2)   # 8 free pages
    a = s.add([0] * 8, SamplingParams())       # 3 pages
    b = s.add([0] * 8, SamplingParams())       # 3 pages
    s.admit()
    assert s.pool.num_free == 2
    assert s.pool.alloc(2) is not None         # drain the pool
    ok = s.grow(a)                             # a needs a page -> evict b
    assert ok and b.slot is None and b.n_preemptions == 1
    assert s.waiting[0] is b and len(a.pages) == 4
    assert list(s.running) == [a.slot]
    # b's generated-so-far tokens ride along into its re-prefill prompt
    b.out.extend([5, 6])
    assert b.full_sequence == [0] * 8 + [5, 6]


def test_scheduler_grow_fails_only_when_alone_and_dry():
    s = _mk_sched(num_pages=3, page_size=4, max_slots=1)
    a = s.add([0] * 4, SamplingParams())
    s.admit()
    assert s.pool.alloc(s.pool.num_free) is not None
    assert not s.grow(a)                       # nobody left to evict


# ===================================================== paged kernel

def _paged_case(B=3, Hkv=2, rep=4, hd=64, hdv=64, ps=8, maxp=5, seed=0):
    rng = np.random.default_rng(seed)
    NP = 1 + B * maxp
    kp = jnp.asarray(rng.standard_normal((NP, Hkv, ps, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, Hkv, ps, hdv)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, Hkv * rep, hd)), jnp.float32)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, NP)).reshape(B, maxp), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, maxp * ps, B), jnp.int32)
    return q, kp, vp, bt, lengths


def _gather(pages, bt):
    B, maxp = bt.shape
    g = jnp.swapaxes(pages[bt], 2, 3)          # (B, maxp, ps, Hkv, d)
    return g.reshape(B, maxp * g.shape[2], g.shape[3], g.shape[4])


def _f32_oracle(q, kp, vp, bt, lengths, window=0):
    """Exact f32 paged decode attention (the accuracy yardstick)."""
    kg = _gather(kp, bt).astype(jnp.float32)
    vg = _gather(vp, bt).astype(jnp.float32)
    B, T, Hkv, hd = kg.shape
    rep = q.shape[1] // Hkv
    qg = q.reshape(B, Hkv, rep, hd)
    s = jnp.einsum("bhrd,bthd->bhrt", qg, kg) / np.sqrt(hd)
    d = (lengths[:, None] - 1) - jnp.arange(T)
    ok = d >= 0
    if window:
        ok &= d < window
    s = jnp.where(ok[:, None, None, :], s, L.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhrt,bthd->bhrd", p, vg)
    return o.reshape(B, q.shape[1], -1)


def _bf16_fallback(q, kp, vp, bt, lengths, window=0):
    """The engine's XLA fallback math: page gather + the dense decode
    attend (bf16 cache dots — models.layers._decode_attend)."""
    class Cfg:
        attn_softcap = None
    o = L._decode_attend(q[:, None], _gather(kp, bt), _gather(vp, bt),
                         Cfg(), lengths - 1, window)
    return o[:, 0]


@pytest.mark.parametrize("g", [1, 2, 4, 5])
def test_paged_kernel_matches_f32_oracle_across_gather_widths(g):
    q, kp, vp, bt, lengths = _paged_case(seed=10)
    ref = _f32_oracle(q, kp, vp, bt, lengths)
    out = tcec_paged_attention(q, kp, vp, bt, lengths, pages_per_step=g,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_paged_kernel_recovers_precision_the_bf16_decode_path_discards():
    """The paper's point, applied at decode time: the kernel TCEC-splits
    the f32 query and probs where the dense path rounds both to bf16 —
    so the kernel must sit strictly closer to the f32 oracle, while
    staying within bf16-level distance of the fallback."""
    q, kp, vp, bt, lengths = _paged_case(B=4, maxp=4, seed=11)
    ref = np.asarray(_f32_oracle(q, kp, vp, bt, lengths))
    fb = np.asarray(_bf16_fallback(q, kp, vp, bt, lengths))
    out = np.asarray(tcec_paged_attention(q, kp, vp, bt, lengths,
                                          pages_per_step=2, interpret=True))
    err_kernel = np.max(np.abs(out - ref))
    err_fallback = np.max(np.abs(fb - ref))
    assert err_kernel < err_fallback / 4, (err_kernel, err_fallback)
    np.testing.assert_allclose(out, fb, rtol=5e-2, atol=5e-2)


def test_paged_kernel_window_and_empty_rows():
    q, kp, vp, bt, lengths = _paged_case(seed=12)
    ref = _f32_oracle(q, kp, vp, bt, lengths, window=5)
    out = tcec_paged_attention(q, kp, vp, bt, lengths, window=5,
                               pages_per_step=2, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # a slot with no valid tokens returns zeros, never NaN
    z = tcec_paged_attention(q, kp, vp, bt, jnp.zeros_like(lengths),
                             pages_per_step=2, interpret=True)
    assert bool(jnp.all(z == 0.0))


def test_paged_kernel_ignores_stale_garbage_in_recycled_pages():
    """Masking is a select, not an additive bias: non-finite stale data in
    pages beyond the sequence length must not poison the softmax."""
    q, kp, vp, bt, lengths = _paged_case(B=2, maxp=3, seed=13)
    kp = kp.at[int(bt[0, 2]), :].set(jnp.inf)     # garbage past length
    vp = vp.at[int(bt[0, 2]), :].set(jnp.nan)
    short = jnp.asarray([3, 5], jnp.int32)        # well inside page 0
    out = tcec_paged_attention(q, kp, vp, bt, short, pages_per_step=1,
                               interpret=True)
    assert bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_kernel_reads_a_stacked_pool_at_its_layer(layer):
    """A stack of pools read at ``layer`` is bitwise the kernel on that
    layer's pool alone (the decode step's in-place path)."""
    q, kp, vp, bt, lengths = _paged_case(seed=17)
    rng = np.random.default_rng(18)
    ks = jnp.stack([kp, -kp, jnp.asarray(rng.standard_normal(kp.shape),
                                         kp.dtype)])
    vs = jnp.stack([vp, vp * 2, -vp])
    out = tcec_paged_attention(q, ks, vs, bt, lengths, layer=jnp.int32(layer),
                               window=7, pages_per_step=2, interpret=True)
    ref = tcec_paged_attention(q, ks[layer], vs[layer], bt, lengths,
                               window=7, pages_per_step=2, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layer", [0, 3])
def test_kv_page_write_equals_the_xla_scatter(dtype, layer):
    """The aliased page write is bitwise ``pool.at[layer, page, :, off]
    .set(new)``: rows 0 and ps-1, the first and last layer, and several
    inactive slots all writing the scrap page 0 (whose contents are then
    unspecified, as they are for a scatter with repeated indices)."""
    from repro.kernels.kv_page_write import kv_page_write
    L_, NP, Hkv, ps, hd, hdv = 4, 9, 2, 8, 64, 32
    rng = np.random.default_rng(19)
    kp = jnp.asarray(rng.standard_normal((L_, NP, Hkv, ps, hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((L_, NP, Hkv, ps, hdv)), dtype)
    page = np.asarray([0, 3, 0, 5, 0, 8], np.int32)
    off = np.asarray([1, 0, 6, ps - 1, 3, 2], np.int32)
    B = len(page)
    kn = jnp.asarray(rng.standard_normal((B, Hkv, hd)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, Hkv, hdv)), jnp.float32)
    k2, v2 = kv_page_write(kp, vp, kn, vn, page, off, jnp.int32(layer),
                           interpret=True)
    act = page != 0
    rk = kp.at[layer, page[act], :, off[act]].set(kn[act].astype(dtype))
    rv = vp.at[layer, page[act], :, off[act]].set(vn[act].astype(dtype))
    keep = np.ones((L_, NP), bool)
    keep[layer, 0] = False                  # the scrap page
    for got, ref in ((k2, rk), (v2, rv)):
        assert got.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got)[keep],
                                      np.asarray(ref)[keep])


# -------------------------------------------- dispatch + tuning wiring

def test_paged_dispatch_eligibility_and_hatches(monkeypatch):
    q, kp, vp, bt, lengths = _paged_case(seed=14)
    pol = "tcec_bf16x6"
    with numerics.use(force=True, interpret=True, paged_block=2):
        assert dispatch.attention_decode_eligible(q, kp, vp, policy=pol)
        out = dispatch.attention_decode(q, kp, vp, bt, lengths, policy=pol)
        assert out is not None and out.shape == (3, 8, 64)
        # granular hatch
        with numerics.use(paged_attention=False):
            assert dispatch.attention_decode(q, kp, vp, bt, lengths,
                                             policy=pol) is None
        # wholesale hatch
        with numerics.use(enabled=False):
            assert dispatch.attention_decode(q, kp, vp, bt, lengths,
                                             policy=pol) is None
        # plain policies stay on XLA
        assert not dispatch.attention_decode_eligible(q, kp, vp,
                                                      policy="bf16")
    # off-TPU without force: decline
    assert not dispatch.attention_decode_eligible(q, kp, vp, policy=pol)
    # env hatch round-trip through the process defaults
    monkeypatch.setenv("REPRO_DISABLE_PAGED_ATTN", "1")
    assert not numerics.reload_env_defaults().paged_attention
    monkeypatch.setenv("REPRO_DISABLE_PAGED_ATTN", "0")
    assert numerics.reload_env_defaults().paged_attention
    monkeypatch.delenv("REPRO_DISABLE_PAGED_ATTN")
    numerics.reload_env_defaults()


def test_paged_dispatch_under_mesh_routes_or_declines():
    """Under a mesh the paged kernel runs per shard through shard_map
    (kernels/shmap.py); the knob / an unsupported spec decline."""
    from jax.sharding import Mesh
    from repro.kernels import shmap
    from repro.parallel import ctx
    q, kp, vp, bt, lengths = _paged_case(seed=15)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("model",))
    with numerics.use(force=True, interpret=True):
        ref = dispatch.attention_decode(q, kp, vp, bt, lengths,
                                        policy="tcec_bf16x6")
        with ctx.use_mesh(mesh):
            assert dispatch.attention_decode_eligible(
                q, kp, vp, policy="tcec_bf16x6")
            n0 = shmap.counters()["paged"]
            out = dispatch.attention_decode(q, kp, vp, bt, lengths,
                                            policy="tcec_bf16x6")
            assert out is not None and shmap.counters()["paged"] == n0 + 1
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
            with numerics.use(shard_map=False):
                assert not dispatch.attention_decode_eligible(
                    q, kp, vp, policy="tcec_bf16x6")

        class _FakeMesh:                   # Hkv not divisible by the axis
            shape = {"model": max(3, kp.shape[2] + 1)}
            axis_names = ("model",)
        with ctx.use_mesh(_FakeMesh()):
            assert not dispatch.attention_decode_eligible(
                q, kp, vp, policy="tcec_bf16x6")


def test_paged_kernel_matches_fused_dispatch_inside_model_layer():
    """attention_decode_paged under forced dispatch (fused kernel) agrees
    with its own gather fallback to kernel tolerance."""
    cfg = get_smoke_config("qwen3-0.6b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    lp = jax.tree.map(lambda a: a[0], params["dense_blocks"])["attn"]
    from repro.models import lm
    pools = lm.init_paged_cache(cfg, 9, 4)["dense_blocks"]
    pool = jax.tree.map(lambda a: a[0], pools)
    rng = np.random.default_rng(3)
    pool = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype), pool)
    x = _rand((2, 1, cfg.d_model), 16)
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    lengths = jnp.asarray([6, 11], jnp.int32)
    ref, _ = L.attention_decode_paged(lp, x, cfg, pool, bt, lengths)
    with numerics.use(force=True, interpret=True, min_dim=0,
                           paged_block=2):
        out, _ = L.attention_decode_paged(lp, x, cfg, pool, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_paged_autotune_namespace_roundtrip(tmp_path):
    calls = []

    def fake_measure(g):
        calls.append(g)
        return 1.0 + abs(g - 4) / 1e3          # prefers 4 pages per step

    cache = tuning.BlockCache(path=str(tmp_path / "tune.json"))
    g, meta = tuning.autotune_paged(4, 2, 4, 16, 16, 64, 64, "tcec_bf16x6",
                                    measure=fake_measure, cache=cache)
    assert meta["source"] == "measured" and g == 4
    n = len(calls)
    g2, meta2 = tuning.autotune_paged(4, 2, 4, 16, 16, 64, 64,
                                      "tcec_bf16x6", measure=fake_measure,
                                      cache=cache)
    assert g2 == g and meta2["source"] == "cache" and len(calls) == n
    key = tuning.paged_cache_key(4, 2, 4, 16, 16, 64, 64, "tcec_bf16x6",
                                 jax.default_backend())
    assert "/paged/" in key
    assert key != tuning.attn_cache_key(4, 2, 4, 16, 16, 64, 64,
                                        "tcec_bf16x6",
                                        jax.default_backend())


def test_paged_candidates_respect_vmem():
    pol = get_policy("tcec_bf16x6")
    cands = tuning.paged_candidate_blocks(64, 16, 8, 64, 64, "tcec_bf16x6")
    assert cands and all(
        paged_vmem_bytes(g, 16, 8, 64, 64, pol) <= VMEM_BUDGET
        for g in cands)
    assert all(g <= 64 for g in cands)
    g = tuning.paged_heuristic_block(64, 16, 8, 64, 64, "tcec_bf16x6")
    assert g * 16 >= 128                       # reaches the 128-lane MXU
    assert tuning.paged_candidate_blocks(2, 4, 1, 64, 64,
                                         "tcec_bf16x6") == [2, 1]


# ================================================= engine <-> dense parity

PARITY_ARCHS = ["qwen3-0.6b", "gemma2-9b", "deepseek-v3-671b"]


@pytest.mark.parametrize(
    "arch,kernels",
    [pytest.param(a, "off", id=a) for a in PARITY_ARCHS]
    + [pytest.param(a, "forced", id=f"{a}-forced") for a in PARITY_ARCHS])
def test_engine_greedy_token_identical_to_dense_generate(arch, kernels):
    """The acceptance contract: transformer / GQA+window+softcap / MLA+MoE
    — greedy engine output == dense-cache reference, token for token.
    ``forced`` runs the kernels in interpret mode: the decode step then
    keeps its pools in place (the page-write and paged kernels on the
    stacked pools) for the K/V families, and MLA takes the per-layer
    scan."""
    import contextlib
    from repro.launch.serve import generate, generate_dense
    cfg, model, params = _model_and_params(arch)
    prompts = _prompts(cfg, (2, 9), seed=5)
    dense = np.asarray(generate_dense(cfg, params, prompts, 6))
    scope = (numerics.use(force=True, interpret=True) if kernels == "forced"
             else contextlib.nullcontext())
    with scope:
        eng = np.asarray(generate(cfg, params, prompts, 6))
    np.testing.assert_array_equal(dense, eng)


# which kernel knobs keep the decode step's pools in place, and which
# explain rule declines the in-place path otherwise
IN_PLACE_CASES = [
    ("qwen3-0.6b", dict(force=True, interpret=True), "fused"),
    ("gemma2-9b", dict(force=True, interpret=True), "fused"),
    ("qwen3-0.6b", dict(force=True, interpret=True, paged_attention=False),
     "hatch-disabled"),
    ("qwen3-0.6b", dict(force=True, interpret=True, enabled=False),
     "hatch-disabled"),
    ("qwen3-0.6b", {}, "off-backend"),
    ("deepseek-v3-671b", dict(force=True, interpret=True),
     "shape-unsupported"),
]


@pytest.mark.parametrize("arch,knobs,rule", IN_PLACE_CASES)
def test_engine_in_place_decode_counts_and_explains(arch, knobs, rule):
    """``decode_steps_in_place`` counts every decode step where the path
    engages and none elsewhere; the ``kv_write`` explain entry reads
    ``fused`` or names the declining rule; greedy tokens match the
    dense reference either way (MLA, the hatches and an unforced CPU keep
    the per-layer scan)."""
    from repro.launch.serve import generate_dense
    from repro.obs.explain import decisions, reset
    reset()
    cfg, model, params = _model_and_params(arch)
    prompts = _prompts(cfg, (2, 9), seed=5)
    dense = np.asarray(generate_dense(cfg, params, prompts, 5))
    eng = Engine(cfg, params, max_slots=2, num_pages=16, page_size=4,
                 numerics_config=numerics.active().replace(**knobs))
    out = eng.run([np.asarray(p) for p in prompts],
                  SamplingParams(max_tokens=5))
    np.testing.assert_array_equal(dense, np.stack(list(out.values())))
    stats = eng.stats()
    assert stats["decode_steps"] > 0
    in_place = stats["decode_steps"] if rule == "fused" else 0
    assert stats["decode_steps_in_place"] == in_place
    rules = {r for k, v in decisions().items() if "/kv_write/" in k
             for r in v}
    assert rules == {rule}, rules


def test_engine_nonfinite_rerun_leaves_the_in_place_path():
    """The guard's one-shot re-run traces under the XLA-fallback scope,
    which declines the in-place path (hatch closed); the dispatched steps
    still all ran in place, and tokens match the dense reference."""
    from repro import faults
    from repro.launch.serve import generate_dense
    from repro.obs.explain import decisions, reset
    reset()
    cfg, model, params = _model_and_params("qwen3-0.6b")
    prompts = _prompts(cfg, (2, 9), seed=5)
    dense = np.asarray(generate_dense(cfg, params, prompts, 5))
    eng = Engine(cfg, params, max_slots=2, num_pages=16, page_size=4,
                 numerics_config=numerics.active().replace(
                     force=True, interpret=True))
    with faults.use(faults.FaultPlan(
            [faults.FaultSpec("decode.nonfinite", at=(1,), arg=0)])):
        out = eng.run([np.asarray(p) for p in prompts],
                      SamplingParams(max_tokens=5))
    np.testing.assert_array_equal(dense, np.stack(list(out.values())))
    stats = eng.stats()
    assert stats["fallback_reruns"] == 1
    assert stats["decode_steps_in_place"] == stats["decode_steps"]
    rules = {r for k, v in decisions().items() if "/kv_write/" in k
             for r in v}
    assert rules == {"fused", "hatch-disabled"}, rules


def test_engine_kv_write_fault_falls_back_to_the_scatter():
    """An injected page-write failure (fault site ``kernel.kv_write``)
    is caught by the guard: the layer scatters into the carried stack
    with XLA instead, and the tokens do not change."""
    from repro import faults
    from repro.kernels import guard
    from repro.launch.serve import generate_dense
    from repro.obs.explain import decisions, reset
    reset()
    cfg, model, params = _model_and_params("qwen3-0.6b")
    prompts = _prompts(cfg, (2, 9), seed=5)
    dense = np.asarray(generate_dense(cfg, params, prompts, 5))
    eng = Engine(cfg, params, max_slots=2, num_pages=16, page_size=4,
                 numerics_config=numerics.active().replace(
                     force=True, interpret=True))
    plan = faults.plan_from_spec("kernel.kv_write@0")
    try:
        with faults.use(plan):
            out = eng.run([np.asarray(p) for p in prompts],
                          SamplingParams(max_tokens=5))
    finally:
        guard.reset()
    assert plan.log == [("kernel.kv_write", 0)]
    np.testing.assert_array_equal(dense, np.stack(list(out.values())))
    rules = {r for k, v in decisions().items() if "/kv_write/" in k
             for r in v}
    assert rules == {"kernel-failure"}, rules


def test_engine_never_shares_an_active_slots_current_page():
    """The in-place write gives each slot's current page to one grid step
    alone: no two running slots write the same page, none writes the
    scrap page 0, and no current page is shared with the prefix cache —
    across slot recycling, shared prefixes and chunked prefill."""
    cfg, model, params = _model_and_params("qwen3-0.6b")
    rng = np.random.default_rng(10)
    shared = list(rng.integers(0, cfg.vocab_size, 12))
    prompts = ([shared + list(rng.integers(0, cfg.vocab_size, n))
                for n in (0, 1, 3, 4, 6)]
               + [list(rng.integers(0, cfg.vocab_size, n)) for n in (5, 9)])
    eng = Engine(cfg, params, max_slots=3, num_pages=48, page_size=4,
                 numerics_config=numerics.active().replace(
                     prefix_cache=True, chunked_prefill=8))
    ps, seen = eng.pool.page_size, []
    dispatch_ = eng._decode_dispatch

    def checked():
        pages = [int(eng.block_tables[r.slot, eng.lengths[r.slot] // ps])
                 for r in eng.sched.running.values()
                 if r.state is RequestState.RUNNING]
        assert 0 not in pages and len(set(pages)) == len(pages), pages
        assert all(eng.pool.refcount(p) == 1 for p in pages), pages
        seen.append(len(pages))
        return dispatch_()
    eng._decode_dispatch = checked
    eng.run(prompts, SamplingParams(max_tokens=6))
    assert max(seen) == 3 and eng.stats()["prefix_hits"] > 0


def test_engine_mixed_lengths_match_per_request_dense():
    """Continuous batching must not change anyone's tokens: requests of
    different lengths decoding side by side each match their own
    single-request dense run."""
    from repro.launch.serve import generate_dense
    cfg, model, params = _model_and_params("qwen3-0.6b")
    rng = np.random.default_rng(6)
    lens = [5, 9, 13]
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in lens]
    engine = Engine(cfg, params, max_slots=3, num_pages=64, page_size=4)
    rids = [engine.add_request(p, SamplingParams(max_tokens=6))
            for p in prompts]
    out = engine.run()
    for p, rid in zip(prompts, rids):
        ref = np.asarray(generate_dense(
            cfg, params, jnp.asarray(p, jnp.int32)[None], 6))[0]
        np.testing.assert_array_equal(ref, np.asarray(out[rid]))


def test_engine_slot_recycling_more_requests_than_slots():
    from repro.launch.serve import generate_dense
    cfg, model, params = _model_and_params("qwen3-0.6b")
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, 4 + i) for i in range(5)]
    engine = Engine(cfg, params, max_slots=2, num_pages=64, page_size=4)
    rids = [engine.add_request(p, SamplingParams(max_tokens=5))
            for p in prompts]
    out = engine.run()
    assert not engine.sched.has_work and engine.pool.num_live == 0
    for p, rid in zip(prompts, rids):
        ref = np.asarray(generate_dense(
            cfg, params, jnp.asarray(p, jnp.int32)[None], 5))[0]
        np.testing.assert_array_equal(ref, np.asarray(out[rid]))


def test_engine_preemption_recovers_and_stays_token_identical():
    """A pool too small for two residents forces a preemption; the victim
    re-prefills (prompt + generated so far) and still produces exactly its
    solo-run tokens."""
    from repro.launch.serve import generate_dense
    cfg, model, params = _model_and_params("qwen3-0.6b")
    rng = np.random.default_rng(8)
    p1 = rng.integers(0, cfg.vocab_size, 4)
    p2 = rng.integers(0, cfg.vocab_size, 4)
    engine = Engine(cfg, params, max_slots=2, num_pages=7, page_size=4,
                    max_pages_per_slot=6)
    r1 = engine.add_request(p1, SamplingParams(max_tokens=12))
    r2 = engine.add_request(p2, SamplingParams(max_tokens=12))
    out = engine.run()
    preempts = [engine._requests[r].n_preemptions for r in (r1, r2)]
    assert sum(preempts) >= 1, preempts
    for p, rid in [(p1, r1), (p2, r2)]:
        ref = np.asarray(generate_dense(
            cfg, params, jnp.asarray(p, jnp.int32)[None], 12))[0]
        np.testing.assert_array_equal(ref, np.asarray(out[rid]))


def test_engine_stop_tokens_and_max_tokens():
    cfg, model, params = _model_and_params("qwen3-0.6b")
    rng = np.random.default_rng(9)
    p = rng.integers(0, cfg.vocab_size, 6)
    engine = Engine(cfg, params, max_slots=1, num_pages=32, page_size=4)
    rid = engine.add_request(p, SamplingParams(max_tokens=8))
    free_run = engine.run()[rid]
    assert len(free_run) == 8
    # stop on the 3rd greedy token: output is the first two, stop excluded
    engine2 = Engine(cfg, params, max_slots=1, num_pages=32, page_size=4)
    rid2 = engine2.add_request(
        p, SamplingParams(max_tokens=8, stop_tokens=(free_run[2],)))
    stopped = engine2.run()[rid2]
    assert stopped == free_run[:2]
    # stop on the very first token: empty output, slot still recycled
    engine3 = Engine(cfg, params, max_slots=1, num_pages=32, page_size=4)
    rid3 = engine3.add_request(
        p, SamplingParams(max_tokens=8, stop_tokens=(free_run[0],)))
    assert engine3.run()[rid3] == []
    assert engine3.pool.num_live == 0


def test_engine_defrag_is_output_invariant():
    from repro.launch.serve import generate_dense
    cfg, model, params = _model_and_params("qwen3-0.6b")
    rng = np.random.default_rng(10)
    p1 = rng.integers(0, cfg.vocab_size, 7)
    p2 = rng.integers(0, cfg.vocab_size, 5)
    engine = Engine(cfg, params, max_slots=2, num_pages=32, page_size=4)
    r1 = engine.add_request(p1, SamplingParams(max_tokens=9))
    r2 = engine.add_request(p2, SamplingParams(max_tokens=4))
    for _ in range(5):
        engine.step()                     # r2 finishes -> holes in the pool
    engine.defragment()
    while engine.sched.has_work:
        engine.step()
    ref = np.asarray(generate_dense(
        cfg, params, jnp.asarray(p1, jnp.int32)[None], 9))[0]
    np.testing.assert_array_equal(ref,
                                  np.asarray(engine._requests[r1].out))


def test_engine_finishes_preempted_request_past_the_length_cap():
    """Regression (review finding): a request preempted after *generating*
    its way to the per-slot cap must be finished from the queue — its
    re-admission would need more pages than a block-table row holds
    (add_request's cap check only guards initial prompts)."""
    cfg, model, params = _model_and_params("qwen3-0.6b")
    rng = np.random.default_rng(14)
    p = rng.integers(0, cfg.vocab_size, 4)
    engine = Engine(cfg, params, max_slots=1, num_pages=32, page_size=4,
                    max_pages_per_slot=2)
    rid = engine.add_request(p, SamplingParams(max_tokens=20))
    req = engine._requests[rid]
    # simulate the preempted state: generated up to the cap, back in queue
    req.out.extend(int(t) for t in
                   rng.integers(0, cfg.vocab_size, 2 * 4 - len(p)))
    out = engine.run()
    assert engine._requests[rid].finished and not engine.sched.has_work
    assert len(out[rid]) == 2 * 4 - len(p)     # nothing generated on top


def test_engine_preemption_keeps_the_sampled_key_stream_aligned():
    """Regression (review finding): the decode step's split order must
    match the prefill draw's (`key, sub = split(key)`), or a preemption's
    re-prefill resumes a sampled request's stream on the wrong side of
    the split."""
    cfg, model, params = _model_and_params("qwen3-0.6b")
    rng = np.random.default_rng(15)
    p = rng.integers(0, cfg.vocab_size, 4)
    sp = SamplingParams(temperature=0.9, top_k=16, max_tokens=10, seed=21)
    solo = Engine(cfg, params, max_slots=1, num_pages=32, page_size=4)
    ref = solo.run([p], sp)
    # tight pool: the sampled request (younger) gets preempted mid-stream
    eng = Engine(cfg, params, max_slots=2, num_pages=7, page_size=4,
                 max_pages_per_slot=6)
    eng.add_request(rng.integers(0, cfg.vocab_size, 4),
                    SamplingParams(max_tokens=12))
    rid = eng.add_request(p, sp)
    out = eng.run()
    assert eng._requests[rid].n_preemptions >= 1
    assert out[rid] == list(ref.values())[0]


def test_engine_sampled_stream_independent_of_batching():
    """A request's sampled tokens depend on its own seed, not on what else
    shares the batch (per-request PRNG streams)."""
    cfg, model, params = _model_and_params("qwen3-0.6b")
    rng = np.random.default_rng(11)
    p = rng.integers(0, cfg.vocab_size, 6)
    sp = SamplingParams(temperature=0.8, top_k=32, max_tokens=6, seed=42)
    solo = Engine(cfg, params, max_slots=1, num_pages=32, page_size=4)
    a = solo.run([p], sp)
    busy = Engine(cfg, params, max_slots=2, num_pages=64, page_size=4)
    rid = busy.add_request(p, sp)
    busy.add_request(rng.integers(0, cfg.vocab_size, 9),
                     SamplingParams(temperature=1.0, max_tokens=6, seed=3))
    b = busy.run()
    assert list(a.values())[0] == b[rid]


def test_engine_rejects_unsupported_family_and_oversized_prompt():
    cfg, model, params = _model_and_params("mamba2-130m")
    with pytest.raises(ValueError):
        Engine(cfg, params)
    cfg2, model2, params2 = _model_and_params("qwen3-0.6b")
    engine = Engine(cfg2, params2, max_slots=1, num_pages=32, page_size=4,
                    max_pages_per_slot=2)
    with pytest.raises(ValueError):
        engine.add_request(list(range(16)), SamplingParams())


def test_generate_wrapper_keeps_legacy_shape_and_determinism():
    """Back-compat: (B, P) -> (B, gen_len), deterministic, for both the
    engine-backed families and the dense fallback."""
    from repro.launch.serve import generate
    for arch in ["qwen3-0.6b", "mamba2-130m"]:
        cfg, model, params = _model_and_params(arch)
        prompts = _prompts(cfg, (2, 4), seed=12)
        a = generate(cfg, params, prompts, gen_len=5)
        b = generate(cfg, params, prompts, gen_len=5)
        assert a.shape == (2, 5)
        assert jnp.array_equal(a, b)


def test_prefill_is_single_shot_not_a_decode_loop():
    """The engine's prompt path is ONE jitted sequence-level forward per
    admitted batch — not O(P) decode steps (the legacy loop's shape)."""
    cfg, model, params = _model_and_params("qwen3-0.6b")
    prompts = _prompts(cfg, (3, 9), seed=13)
    engine = Engine(cfg, params, max_slots=3, num_pages=64, page_size=4)
    for i in range(3):
        engine.add_request(np.asarray(prompts[i]),
                           SamplingParams(max_tokens=4))
    engine.run()
    assert engine.n_prefills == 1          # same padded length -> one batch
    assert engine.n_decode_steps <= 4      # never P + gen steps
