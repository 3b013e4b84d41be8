"""Share of the traced window in which no operation ran on a chip: 1 - the
union of its ``XLA Ops`` intervals over the window, averaged over the cell's
chips, in percent."""


def read(ctx):
    from lib.trace import busy_ns

    busy = busy_ns(ctx.events, ctx.lo, ctx.hi)
    if not busy or ctx.hi <= ctx.lo:
        return None
    mean = sum(busy.values()) / len(busy)
    return 100.0 * (1.0 - mean / (ctx.hi - ctx.lo))
