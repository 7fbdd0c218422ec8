"""Device idle share: 1 - the union of the device's operation intervals
over the traced window, in %, mean over the cell's devices."""

from chipbench import tracing


def read(ctx):
    w = ctx.trace.window_ns
    return tracing.mean_over_devices(
        ctx.trace, lambda d: 100.0 * (1.0 - ctx.busy_ns(d) / w))
