"""The fused MiniConv kernel's share of its roofline: the least time its
launches could take on this chip (``opcount.roofline_s``: operations over
peak FLOP/s or bytes over peak bytes/s, whichever is larger) over the
device time of the kernel's events in the trace."""
from chipbench import opcount

# every compiled Pallas kernel is a TPU custom call; on the encode path
# the fused encoder+projection launch is the only one
KERNEL = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    kernel_s, events = ctx.trace.op_time(lambda name: KERNEL in name)
    if events == 0 or kernel_s <= 0:
        return None
    least, _ = opcount.roofline_s(ctx.config, ctx.counters["batch"],
                                  ctx.peaks)
    return 100.0 * least * events / kernel_s
