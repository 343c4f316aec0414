"""How late the load generator sent, at its 95th percentile: the time
from when a client could send (its slot, or its previous answer if that
came later) to when it sent.  A starved generator must not read as a
fast server."""


def read(ctx):
    return ctx.counters.get("gen_lag_p95_ms")
