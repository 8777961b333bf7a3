import collections

from chipbench import spec, traffic

MIX = spec.cell("qwen3-0.6b.code")["traffic"]
BIG = 2 ** 33 + 12345          # seeds are not held to 32 bits
SPANS = (5.0, 40.0)


def _window(seed):
    """The requests due in the measured window [5, 45)."""
    s = traffic.Stream(MIX, seed, 151936, SPANS)
    s.take_due(5.0 - 1e-9)
    return s.take_due(45.0 - 1e-9)


def test_same_seed_same_requests():
    a, b = _window(BIG), _window(BIG)
    assert [(r.due_s, r.max_tokens, r.prompt.tolist()) for r in a] == \
        [(r.due_s, r.max_tokens, r.prompt.tolist()) for r in b]


def test_every_seed_offers_the_window_the_same_work():
    """The schedule (sizes and due times, in order) is the mix's; the
    seed draws only the tokens."""
    def schedule(seed):
        return [(r.due_s, len(r.prompt), r.max_tokens)
                for r in _window(seed)]
    n = round(MIX["rate_per_s"] * SPANS[1])
    for seed in (1, 7, BIG):
        assert len(_window(seed)) == n
    assert schedule(1) == schedule(BIG) == schedule(7)
    assert _window(1)[0].prompt.tolist() != _window(7)[0].prompt.tolist()
    other = dict(MIX, schedule_seed=MIX["schedule_seed"] + 1)
    s = traffic.Stream(other, 1, 151936, SPANS)
    s.take_due(5.0 - 1e-9)
    moved = [(r.due_s, len(r.prompt), r.max_tokens)
             for r in s.take_due(45.0 - 1e-9)]
    assert moved != schedule(1)


def test_window_composition_follows_the_weights():
    rs = _window(3)
    counts = collections.Counter(len(r.prompt) for r in rs)
    n = len(rs)
    for L, wgt in zip(MIX["prompt_lengths"], MIX["prompt_weights"]):
        assert abs(counts[L] - wgt * n) < 1
    lo, hi = MIX["output"]["min"], MIX["output"]["max"]
    assert all(lo <= r.max_tokens <= hi for r in rs)
    assert all(5.0 <= r.due_s < 45.0 for r in rs)
