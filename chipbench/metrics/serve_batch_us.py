"""The server's host time per micro-batch: the mean length of the traced
window's ``serve.batch`` spans (stack the payloads, call the server half,
fetch the actions, send the answers)."""


def read(ctx):
    if ctx.trace is None:
        return None
    spans = ctx.trace.program_spans_named("serve.batch")
    if not spans:
        return None
    return 1e6 * sum(e - s for s, e, _ in spans) / len(spans)
