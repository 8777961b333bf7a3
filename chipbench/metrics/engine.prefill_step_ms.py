"""Mean host-clock time of an engine step that ran at least one prefill
chunk (with the decode step that follows it in the same engine step)."""
from chipbench.layer_metrics import prefill_steps


def read(ctx):
    steps = prefill_steps(ctx.steps)
    if not steps:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in steps) / len(steps)
