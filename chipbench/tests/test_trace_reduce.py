"""The reduction from trace events to busy time, idle gaps and labels."""
import pathlib

from chipbench import trace_reduce as tr

E = tr.Event


def test_union_merges_and_clips():
    evs = [E("a", 0, 10), E("b", 5, 20), E("c", 30, 40), E("d", 38, 60)]
    assert tr.union(evs, 2, 50) == [(2, 20), (30, 50)]
    assert tr.busy(evs, 2, 50) == 38


def test_gaps_are_the_complement():
    evs = [E("a", 10, 20), E("b", 15, 25), E("c", 40, 45)]
    assert tr.gaps(evs, 0, 50) == [(0, 10), (25, 40), (45, 50)]
    assert tr.busy(evs, 0, 50) + sum(t - s for s, t in tr.gaps(evs, 0, 50)) \
        == 50


def test_label_takes_the_innermost_open_annotation():
    notes = [E("chipbench.traced", 0, 100), E("chipbench.step", 10, 50),
             E("chipbench.poll", 50, 60)]
    assert tr.label(notes, 20, 30) == "chipbench.step"
    assert tr.label(notes, 52, 58) == "chipbench.poll"
    assert tr.label(notes, 70, 80) == "chipbench.traced"
    assert tr.label(notes, 200, 300) == "none"
    assert tr.window(notes) == (0, 100)


RECORDED = pathlib.Path(__file__).parent / "data" / "decode.xplane.pb"


def test_recorded_trace():
    """A trace recorded on one TPU v5 lite around two engine steps of
    qwen3-0.6b with 512-token chunks, each step a prefill chunk and a decode
    step: device ops on one chip, busy time inside the window, and as
    many events of each fused kernel as work.step_kernel_calls counts
    calls (per chunk 7 projections x 28 layers and the head; one flash
    and one paged call per layer)."""
    t = tr.load(RECORDED)
    assert list(t.ops) == [0]
    lo, hi = tr.window(t.annotations)
    busy = tr.busy(t.ops[0], lo, hi)
    assert 0 < busy <= hi - lo
    from chipbench.layer_metrics import KERNEL_OPS
    counts = {k: sum(1 for e in t.ops[0] if p.search(e.name))
              for k, p in KERNEL_OPS.items()}
    assert counts == {"matmul": 2 * (7 * 28 + 1), "flash": 2 * 28,
                      "paged": 2 * 28}


def test_op_name_and_leaves():
    assert tr.op_name("%tcec_matmul_pallas.36 = f32[512,152064]{1,0} "
                      "custom-call(f32[512,1024] %copy.1)") == \
        "tcec_matmul_pallas.36"
    evs = [E("while.8", 0, 100), E("a", 10, 20), E("b", 30, 40),
           E("c", 120, 130)]
    assert [e.name for e in tr.leaves(evs)] == ["a", "b", "c"]
