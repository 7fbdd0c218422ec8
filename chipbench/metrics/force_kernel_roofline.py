"""The two force kernels' share of their roofline, in %.

Work is the algorithm's: useful pairs of the window (``force_evals``) times
the operations per pair of ``work.py``; bytes likewise from shapes.  The
least time is the larger of work over the VPU float32 rate and bytes over
HBM bandwidth (``peaks.json``); the time taken is the kernels' device
durations in the trace.  Nothing is returned when no kernel ran."""

from chipbench import tracing, work
from chipbench.metrics_context import is_force_kernel


def read(ctx):
    kernel_ns = sum(o.dur for d in ctx.trace.ops
                    for o in tracing.matching(ctx.trace, d, is_force_kernel))
    pairs = ctx.pairs_per_pass()
    if not kernel_ns or not pairs:
        return None
    least, _ = work.roofline_seconds(pairs, ctx.events(), ctx.cfg.n,
                                     ctx.peaks, ctx.chips)
    return 100.0 * least / (kernel_ns / 1e9 / ctx.chips)
