"""Host microseconds per packet launching, less the dispatch: the union of
the program's ``flow.launch`` spans (each launch's host-to-device puts of
its ``idx``/``tok``/``fresh`` buffers) in the traced window, less the part
that their nested ``flow.dispatch`` spans cover, over the traced calls'
packets.  The dispatch of the jitted step is left out: the TPU runtime
holds at most 32 executions in flight and blocks a dispatch past that
until the device finishes one, so with calls queued its time is the
device's."""


def read(ctx):
    from lib.spans import us_per_packet

    return us_per_packet(ctx, "flow.launch", less="flow.dispatch")
