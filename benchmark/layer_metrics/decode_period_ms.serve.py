"""Median gap between the device starts of two ``jit_p2t_decode``
executions with consecutive ``launch`` ordinals, inside the traced
stretch: what a row waits for its next token, the prefills and scatters
that ran between the two steps included."""

import program_split


def read(ctx):
    return program_split.decode_period_ms(ctx)
