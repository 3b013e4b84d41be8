"""Host microseconds per packet reading answers back and unpacking them
(``_PendingIngest.finalize``; the per-round output pulls): the union of the
program's ``flow.finalize`` spans in the traced window, less the part that
its nested ``flow.wait`` spans cover (the host blocked on the device, which
is the device's time), over the traced calls' packets."""


def read(ctx):
    from lib.spans import us_per_packet

    return us_per_packet(ctx, "flow.finalize", less="flow.wait")
