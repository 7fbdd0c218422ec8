"""Useful pairs over launched pair slots: the window's ``force_evals`` of
both passes over its launched grid tiles times the tile's BI x BJ pairs.
A count; what capacity buckets and padding waste."""


def read(ctx):
    if not ctx.block or not ctx.window.tiles:
        return None
    bi = ctx.cfg.block_i or ctx.cell.config["block_i"]
    bj = ctx.cfg.block_j or ctx.cell.config["block_j"]
    passes = 2 if ctx.cfg.order >= 6 else 1
    return passes * ctx.window.pairs / (ctx.window.tiles * bi * bj)
