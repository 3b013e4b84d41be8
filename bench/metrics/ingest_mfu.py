"""The whole ingest's share of the chips' peak, in percent: the least time
of every traced call (the larger of its operations over peak FLOP/s and
one read and write of each distinct flow's row plus the weights over peak
bytes/s, ``lib.flops`` with the counts of the cell's family) summed, over
the traced window times the chips.  At the published widths the bytes
bind: a flow row is 1.59 MB."""


def read(ctx):
    from lib import flops

    if not ctx.traced_calls or ctx.hi <= ctx.lo:
        return None
    least = 0.0
    for c in ctx.traced_calls:
        t = flops.batch_least_s(ctx.family, ctx.model, ctx.classes, ctx.pkt_len,
                                c.hi - c.lo, ctx.distinct_flows(c), ctx.peaks)
        least += max(t.values())
    return 100.0 * least / (ctx.traced_s * ctx.chips)
