"""Device milliseconds of the jitted ingest program per chunk it ran: the
summed ``XLA Modules`` events of the program (the fused whole-batch step or
the per-round step) in the traced window, averaged over chips, over the
chunks (``FlowStats.rounds``) of the traced calls."""

PROGRAMS = ("jit_fused", "jit_step", "jit_shard_step")


def read(ctx):
    from lib.trace import MODULES_LINE, device_time

    def match(name):
        return any(name.startswith(p) for p in PROGRAMS)

    ns, n = device_time(ctx.events, match, ctx.lo, ctx.hi, line=MODULES_LINE)
    chunks = sum(c.rounds for c in ctx.traced_calls)
    return ns / ctx.chips / 1e6 / chunks if n and chunks else None
