"""Host work of one decode tick while the device waits: per tick
that ran a step, ``decode.select`` + ``decode.build_batch`` +
``decode.emit``; the median over the traced stretch."""

import program_trace
from common import median

PARTS = ("decode.select", "decode.build_batch", "decode.emit")


def read(ctx):
    pt = program_trace.of(ctx)
    per_tick = []
    for tick, _ in program_trace.decode_ticks(pt, ctx["trace"].window):
        per_tick.append(sum(
            b - a for part in PARTS
            for _, a, b, _ in program_trace.children(pt, tick, part)))
    return median(per_tick) / 1e6 if per_tick else None
