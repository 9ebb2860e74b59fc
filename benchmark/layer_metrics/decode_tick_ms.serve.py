"""Median ``p2t:decode`` span (one whole ``decode_once``) of the ticks
that ran a step, from inside the program."""

import program_trace
from common import median


def read(ctx):
    ticks = program_trace.decode_ticks(program_trace.of(ctx),
                                       ctx["trace"].window)
    if not ticks:
        return None
    return median([t[2] - t[1] for t, _ in ticks]) / 1e6
