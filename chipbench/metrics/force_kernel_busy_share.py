"""Device time of the two force kernels over busy time, in %."""

from chipbench.metrics_context import is_force_kernel


def read(ctx):
    return ctx.share_of_busy(is_force_kernel)
