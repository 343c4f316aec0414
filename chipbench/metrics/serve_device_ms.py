"""Device time of the server half's program per micro-batch, from the
trace: the decode and projection programs' time over the micro-batches
the server ran in the traced window."""

# the jitted server half's program, as the trace names its module
MODULE = "jit_fn"


def read(ctx):
    batches = ctx.counters.get("batches")
    if ctx.trace is None or not batches:
        return None
    module_s, events = ctx.trace.module_time(lambda name: MODULE in name)
    if events == 0:
        return None
    return 1e3 * module_s / len(batches)
