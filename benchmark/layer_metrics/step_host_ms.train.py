"""Median host time of one call of the fused train step, from the
program's own ``p2t:train.step`` spans of the traced stretch; a call
that built its program (count ``built``) is no steady step and is left
out."""

import program_trace
from common import median


def read(ctx):
    spans = program_trace.steady_steps(program_trace.of(ctx),
                                       ctx["trace"].window)
    return median([b - a for _, a, b, _ in spans]) / 1e6 if spans else None
