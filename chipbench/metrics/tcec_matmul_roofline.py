"""Share (%) of its roofline that the fused tcec_matmul kernel reached in the
traced span: the least time the chip could take for the float32 work of
its calls there (work.step_kernel_calls; per call the larger of
operations over the bf16 peak and bytes over HBM bandwidth) over the
summed device time of its events."""


def read(ctx):
    return ctx.roofline("matmul")
