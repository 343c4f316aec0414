"""The whole IMPALA-CNN step's share of the chip's bf16 peak: frames per
second of the traced run times the torso's and head's operations per
frame (``impala_count``, real channel counts).  The step contracts in
float32, which the bf16 peak does not credit."""
from chipbench import impala_count


def read(ctx):
    if not ctx.peaks:
        return None
    return (100.0 * ctx.metrics["frames_per_s"]
            * impala_count.flops_per_frame(ctx.config)
            / ctx.peaks["bf16_flops_per_s"])
