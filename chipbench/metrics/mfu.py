"""Model FLOP utilization of the traced span: the float32 model's
operations for every token processed (work.step_flops) over the span's
length on the trace clock, the chips and the chip's bf16 peak, in %."""


def read(ctx):
    if not ctx.steps:
        return None
    return 100.0 * ctx.step_flops(ctx.steps) / (
        ctx.window_s * ctx.chips * ctx.peak["bf16_flops_per_s"])
