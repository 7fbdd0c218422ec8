"""Device idle time between consecutive ``runner.step`` calls, over the
window, in %: the window outside every step's device envelope (its first
to its last operation), mean over devices.  What the runner's host work
between chunks costs the device."""

from chipbench import tracing


def read(ctx):
    r, w = ctx.trace, ctx.trace.window_ns
    if not r.spans:
        return None
    return tracing.mean_over_devices(
        r, lambda d: 100.0 * (w - tracing.length(
            tracing.union(tracing.step_envelopes(r, d)))) / w)
