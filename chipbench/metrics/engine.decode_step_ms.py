"""Mean host-clock time of an engine step that ran no prefill chunk:
the total over such steps in the traced span, over their count."""
from chipbench.layer_metrics import decode_steps


def read(ctx):
    steps = decode_steps(ctx.steps)
    if not steps:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in steps) / len(steps)
