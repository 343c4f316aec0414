"""Requests per micro-batch the server admitted in the window
(``WorkerServer.batch_sizes``)."""


def read(ctx):
    batches = ctx.counters.get("batches")
    if not batches:
        return None
    return sum(batches) / len(batches)
