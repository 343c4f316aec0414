"""Device time of the encode step per launch outside its kernel: the
step's device time (``encode_step_us``) less that of its Pallas kernel,
the step program's one ``tpu_custom_call`` (the program's
``miniconv.kernel`` scope holds it and nothing else).  What is left is
the input's relayout and padding, the weights' padding, the projection
weight's tiling and the outputs' slicing."""
from chipbench import scopes

KERNEL = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx.trace is None:
        return None

    def in_step(name):
        return name.startswith(scopes.ENCODE_STEP)

    step_s, launches = scopes.module_ops_time(ctx.trace, in_step)
    kernel_s, _ = scopes.module_ops_time(ctx.trace, in_step,
                                         op=lambda name: KERNEL in name)
    if launches == 0 or kernel_s <= 0:
        return None
    return 1e6 * (step_s - kernel_s) / launches
