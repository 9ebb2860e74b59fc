"""Median, over the joined ``jit_p2t_decode`` executions of the traced
stretch, of the device start less the instant the enqueuing call had
returned (the start of the nested ``p2t:decode.readback``, else the end
of the ``p2t:decode.dispatch``): how long a step lay enqueued before
the device took it, i.e. the room the host has before the device waits
for it."""

import program_split


def read(ctx):
    return program_split.dispatch_lead_ms(ctx)
