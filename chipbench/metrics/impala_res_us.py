"""Device time per launch of the IMPALA-CNN step's residual blocks: the
step's operations under the program's ``impala.res`` scope (two convs,
two ReLUs and the skip add per block, two blocks per stack)."""
from chipbench.kinds import encode_impala


def read(ctx):
    return encode_impala.scope_us(ctx, "impala.res")
