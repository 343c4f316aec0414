"""Device time per launch of the IMPALA-CNN step's max-pools: the step's
operations under the program's ``impala.pool`` scope (one 3x3 stride-2
SAME pool per stack)."""
from chipbench.kinds import encode_impala


def read(ctx):
    return encode_impala.scope_us(ctx, "impala.pool")
