"""Device self time per step of the ops whose scope path holds the
program's ``head_ce`` scope: head matmul + cross entropy,
forward, backward and recomputed together, over the traced steps."""

import program_trace


def read(ctx):
    return program_trace.scope_ms_per_step(ctx, "head_ce")
