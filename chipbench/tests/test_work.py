"""work.py against counts made by hand."""
from chipbench import work


def test_gemm_by_hand():
    # (512 x 1024) @ (1024 x 3072): 2*512*1024*3072 operations;
    # f32 A, B and C once: 4 * (512*1024 + 1024*3072 + 512*3072) bytes
    flops, nbytes = work.gemm(512, 3072, 1024)
    assert flops == 3_221_225_472
    assert nbytes == 4 * (524_288 + 3_145_728 + 1_572_864) == 20_971_520


def test_flash_by_hand():
    # 4 queries at positions 2..5 over 8 keys, causal: they see 3, 4, 5
    # and 6 keys = 18 key rows; QK and PV are 2 * 2 * keys * heads * hd
    flops, nbytes = work.flash(4, 8, heads=2, kv_heads=1, head_dim=16,
                               q_start=2)
    assert flops == 4 * 18 * 2 * 16 == 2304
    # Q and O: 4 * 2 * 16 each; K and V: 8 * 1 * 16 each; 4 bytes
    assert nbytes == 4 * (2 * 128 + 2 * 128) == 2048


def test_paged_by_hand():
    # two live slots with 10 and 30 keys, 4 query heads over 2 KV heads
    flops, nbytes = work.paged([10, 30], heads=4, kv_heads=2, head_dim=8)
    assert flops == 4 * 40 * 4 * 8 == 5120
    # K and V of 40 positions x 2 heads x 8, and q and o of 2 x 4 x 8
    assert nbytes == 4 * (2 * 40 * 2 * 8 + 2 * 2 * 4 * 8) == 5632


def test_model_flops_by_hand():
    d = work.Dims(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
                  d_ff=16, vocab=32)
    # per layer: q 8*8, k 8*4, v 8*4, o 8*8, gate/up/down 3*8*16
    assert d.layer_matmul_params == 64 + 32 + 32 + 64 + 384 == 576
    # one token attending 3 keys: 2 layers * (2*576 + 4*3*2*4)
    assert work.token_flops(d, 3) == 2 * (1152 + 96)
    # positions 0..2 attend 1+2+3 keys
    assert work.span_flops(d, 0, 3) == 2 * (3 * 1152 + 4 * 6 * 8)
    step = {"chunk_spans": [(3, 0, 4)], "tokens": [(3, 0), (3, 1)]}
    # the chunk (padding past the prompt is no work), two head rows, and
    # the decode of the second token at context 3 + 1
    assert work.step_flops(d, 32, step) == (work.span_flops(d, 0, 3)
                                            + 2 * (2 * 8 * 32)
                                            + work.token_flops(d, 4))


def test_step_kernel_calls_count():
    d = work.Dims(layers=3, d_model=128, heads=2, kv_heads=1, head_dim=64,
                  d_ff=256, vocab=512)
    step = {"chunk_spans": [(256, 0, 256)], "tokens": [(256, 0), (64, 5)]}
    calls = work.step_kernel_calls(d, step, chunk=128)
    # two chunks: 7 projections x 3 layers + the head, each
    assert len(calls["matmul"]) == 2 * (7 * 3 + 1)
    assert len(calls["flash"]) == 2 * 3
    assert len(calls["paged"]) == 3     # one decode step, one call a layer


def _ctx(n_events, chips=1):
    """A traced span holding one decode step over two live slots (one
    paged call per layer, by the dispatch model) and ``n_events`` paged
    kernel events of 1 ms each on each of ``chips`` chips."""
    from chipbench import peaks, trace_reduce
    from chipbench.layer_metrics import Ctx
    dims = work.Dims(layers=2, d_model=64, heads=4, kv_heads=2,
                     head_dim=16, d_ff=128, vocab=256)
    evs = [trace_reduce.Event("tcec_paged_attention_pallas.3",
                              i * 2_000_000, i * 2_000_000 + 1_000_000)
           for i in range(n_events)]
    step = {"chunk_spans": [], "tokens": [(100, 1), (200, 5)]}
    return Ctx(steps=[step], dims=dims, vocab=256, chunk=64,
               peak=peaks.for_kind("TPU v5 lite"), chips=chips,
               trace=trace_reduce.Trace(ops={c: list(evs)
                                             for c in range(chips)},
                                        annotations=[]),
               win=(0, 10 ** 9), stats0={}, stats1={})


def test_roofline_needs_the_trace_to_hold_the_modelled_calls():
    """Two layers make two paged calls: with two events the share is
    the calls' least time over 2 ms; with one or three the trace holds
    other work than the dispatch model counts, and there is no share."""
    flops, nbytes = work.paged([101, 205], heads=4, kv_heads=2,
                               head_dim=16)
    least = max(flops / 197e12, nbytes / 819e9)
    assert abs(_ctx(2).roofline("paged") - 100.0 * 2 * least / 2e-3) \
        < 1e-9
    assert _ctx(1).roofline("paged") is None
    assert _ctx(3).roofline("paged") is None


def test_per_chip_splits_each_call_evenly():
    calls = [(8.0, 40.0), (4.0, 12.0)]
    assert work.per_chip(calls, 1) == calls
    assert work.per_chip(calls, 4) == [(2.0, 10.0)] * 4 + [(1.0, 3.0)] * 4


def test_roofline_over_four_chips_expects_an_event_per_chip():
    """Four chips share each layer: each of the two paged calls is one
    event on every chip, with a quarter of the call's work.  Two events a
    chip give the eight events' least times over their 8 ms; the one-chip
    count of events on one chip gives no share."""
    flops, nbytes = work.paged([101, 205], heads=4, kv_heads=2,
                               head_dim=16)
    least = max(flops / 4 / 197e12, nbytes / 4 / 819e9)
    assert abs(_ctx(2, chips=4).roofline("paged")
               - 100.0 * 8 * least / 8e-3) < 1e-9
    one = _ctx(2, chips=4)
    one.trace.ops = {0: one.trace.ops[0]}
    assert one.roofline("paged") is None
    assert _ctx(1, chips=4).roofline("paged") is None
