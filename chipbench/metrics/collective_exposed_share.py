"""Exposed collective time over the window, in %, mean over the cell's
devices: the time in which a collective operation runs on a device and no
other operation does (``tracing.exposed_collective``).  What the multi-chip
layer's communication costs the device beyond what compute hides.  Nothing
when no collective ran on any device."""

from chipbench import tracing


def read(ctx):
    r, w = ctx.trace, ctx.trace.window_ns
    if not w or not any(tracing.is_collective(o)
                        for ops in r.ops.values() for o in ops):
        return None
    return tracing.mean_over_devices(
        r, lambda d: 100.0 * tracing.exposed_collective(r, d) / w)
