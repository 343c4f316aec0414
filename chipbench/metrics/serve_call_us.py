"""The host's blocking call into the server half, per micro-batch: the
mean length of the traced window's ``serve.device`` spans (dispatch of
the jitted decode and projection, and the batch's copy to the device)."""


def read(ctx):
    if ctx.trace is None:
        return None
    spans = ctx.trace.program_spans_named("serve.device")
    if not spans:
        return None
    return 1e6 * sum(e - s for s, e, _ in spans) / len(spans)
