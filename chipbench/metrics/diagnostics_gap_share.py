"""Device idle inside the runner's ``sim.readback`` and ``sim.diagnostics``
spans (reads of counters, time or state; energy, counters, the telemetry
snapshot), within the steps' device envelopes, over the window, in %, mean
over devices: what the per-step host work costs the device.  Nothing
without the spans (``scopes.py``)."""

from chipbench import scopes


def read(ctx):
    return scopes.gap_share(ctx.trace, scopes.READBACK, scopes.DIAGNOSTICS)
