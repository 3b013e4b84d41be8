"""Roofline share of the ``flow_ingest`` score-stage Pallas kernel, in
percent: the least time of the score work of the traced calls' packets
(the larger of its operations over peak FLOP/s and its bytes over peak
bytes/s, ``lib.flops``) over the kernel's device time.  Bytes bind: the
stage does 2 d (K + 1) operations per packet against ~1.2 KB of traffic.
The fused step holds one Mosaic call, this kernel, so its device events
are found by the kernel's name or, failing that, as the step's Pallas
custom call (``pallas_call`` in the op's source, ``custom-call`` name)."""

KERNEL = ("flow_ingest_scores", "pallas_call", "tpu_custom_call", "custom-call")


def read(ctx):
    from lib import flops
    from lib.trace import device_time

    ns, calls = device_time(ctx.events, lambda n: any(k in n for k in KERNEL), ctx.lo, ctx.hi)
    pkts = ctx.traced_packets()
    if not ns or not pkts:
        return None
    f = pkts * flops.score_flops(ctx.model, ctx.classes) / ctx.peaks["flops_per_s"]
    b = flops.score_stage_bytes(ctx.model, ctx.classes, pkts, calls) / ctx.peaks["bytes_per_s"]
    return 100.0 * max(f, b) / (ns / 1e9 / ctx.chips)
