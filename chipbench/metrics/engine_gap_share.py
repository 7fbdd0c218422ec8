"""Device idle inside the runner's ``sim.engine`` spans (the engine call
through its ``block_until_ready``), within the steps' device envelopes,
over the window, in %, mean over devices: what dispatching the engine
leaves idle, such as eager operations between their programs.  Nothing
without the spans (``scopes.py``)."""

from chipbench import scopes


def read(ctx):
    return scopes.gap_share(ctx.trace, scopes.ENGINE)
