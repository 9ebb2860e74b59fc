"""Median device time of one ``jit_p2t_decode`` execution of the traced
stretch, each joined to the ``p2t:decode.dispatch`` span that enqueued
it (``program_split.launches``): the step's cost on the device, which
the ``decode`` span has not been since the step runs ahead of the
host."""

import program_split


def read(ctx):
    return program_split.decode_device_ms(ctx)
