"""Device time of the encode step per launch: every operation that runs
inside the step's program (``jit(Deployment.encoder.apply)``), scoped or
not, as the layout copies XLA puts in carry no scope, over the number of
those programs in the traced window."""
from chipbench import scopes


def read(ctx):
    if ctx.trace is None:
        return None
    step_s, launches = scopes.module_ops_time(
        ctx.trace, lambda name: name.startswith(scopes.ENCODE_STEP))
    if launches == 0:
        return None
    return 1e6 * step_s / launches
