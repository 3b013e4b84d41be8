"""Host time in the program's own spans, per traced packet.

The flow-serving path records ``jax.profiler.TraceAnnotation`` spans at its
layer boundaries (``flow.launch`` and, inside it, ``flow.dispatch``;
``flow.finalize`` and, inside it, ``flow.wait``), each with the ingest
call's tick as a stat; the profiler keeps the bare name as the event's name.
A layer's time is the union of its spans inside the traced window, so spans
that overlap (one per width group, or a pipeline's calls) count once, less
the part that the spans it nests, where the host waits on the device, cover.
"""

from __future__ import annotations

from typing import Optional

from .trace import merge, span_ns


def us_per_packet(ctx, name: str, less: str) -> Optional[float]:
    """Microseconds per traced packet inside host spans ``name`` and outside
    host spans ``less``; None where the trace holds no span ``name`` (a
    program without them)."""
    pkts = ctx.traced_packets()
    if not pkts or not span_ns(ctx.events, name, ctx.lo, ctx.hi):
        return None
    both = merge(((e.start, e.end) for e in ctx.events
                  if not e.plane.startswith("/device:") and e.name in (name, less)),
                 ctx.lo, ctx.hi)
    ns = sum(e - s for s, e in both) - span_ns(ctx.events, less, ctx.lo, ctx.hi)
    return ns / 1e3 / pkts
