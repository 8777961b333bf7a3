"""The whole engine step's share of the chip's bf16 peak, over the steps
that ran no prefill chunk, in %."""
from chipbench.layer_metrics import decode_steps


def read(ctx):
    steps = decode_steps(ctx.steps)
    if not steps:
        return None
    t = sum(s["t1"] - s["t0"] for s in steps)
    return 100.0 * ctx.step_flops(steps) / (
        t * ctx.chips * ctx.peak["bf16_flops_per_s"])
