"""Host microseconds per packet in the flow directory: the engine's
``_resolve_slots`` (``serve/flow_engine.py``), where one call's LRU touches,
idle sweep and slot assignment through ``FlowTableDirectory`` run.  A
traced run wraps it in a ``bench.directory`` span; the metric is the union
of those spans in the traced window over the traced calls' packets."""

SPAN = "bench.directory"


def instrument(engine) -> None:
    from jax.profiler import TraceAnnotation

    resolve = getattr(engine, "_resolve_slots", None)
    if resolve is None:
        return

    def traced(flow_ids):
        with TraceAnnotation(SPAN):
            return resolve(flow_ids)

    engine._resolve_slots = traced


def read(ctx):
    from lib.trace import span_ns

    ns = span_ns(ctx.events, SPAN, ctx.lo, ctx.hi)
    pkts = ctx.traced_packets()
    return ns / 1e3 / pkts if ns and pkts else None
