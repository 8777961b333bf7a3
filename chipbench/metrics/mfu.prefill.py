"""The whole engine step's share of the chip's bf16 peak, over the steps
that ran a prefill chunk: their model operations over their host-clock
time, chips and peak, in %."""
from chipbench.layer_metrics import prefill_steps


def read(ctx):
    steps = prefill_steps(ctx.steps)
    if not steps:
        return None
    t = sum(s["t1"] - s["t0"] for s in steps)
    return 100.0 * ctx.step_flops(steps) / (
        t * ctx.chips * ctx.peak["bf16_flops_per_s"])
