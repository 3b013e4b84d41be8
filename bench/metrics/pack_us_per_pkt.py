"""Host microseconds per packet packing launch buffers: the program's
``flow.pack`` spans (``FlowEngine._dispatch_fused`` around
``pack_width_groups`` and each width group's staging fill; the per-round
staging of the per-round and sharded paths), their union in the traced
window over the traced calls' packets."""


def read(ctx):
    from lib.trace import span_ns

    ns = span_ns(ctx.events, "flow.pack", ctx.lo, ctx.hi)
    pkts = ctx.traced_packets()
    return ns / 1e3 / pkts if ns and pkts else None
