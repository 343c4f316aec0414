"""The whole encode step's share of the chip's bf16 peak: frames per
second of the traced run times the encoder's and projection's operations
per frame (``opcount``, real channel counts).  The kernels contract in
float32, which the bf16 peak does not credit."""
from chipbench import opcount


def read(ctx):
    if not ctx.peaks:
        return None
    return (100.0 * ctx.metrics["frames_per_s"]
            * opcount.flops_per_frame(ctx.config)
            / ctx.peaks["bf16_flops_per_s"])
