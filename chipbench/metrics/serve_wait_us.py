"""Queue wait before admission, per micro-batch: the mean over the traced
window's ``serve.batch`` spans of their ``wait_us`` argument, the longest
time one of the batch's requests waited between the server's reader
taking it off the socket and the batch's start."""


def read(ctx):
    if ctx.trace is None:
        return None
    waits = [float(args["wait_us"]) for _, _, args
             in ctx.trace.program_spans_named("serve.batch")
             if "wait_us" in args]
    if not waits:
        return None
    return sum(waits) / len(waits)
