"""Device self time per step of the ops that carry the program's
``blocks`` scope and no layer's: the plumbing of the scan over the
block stack (slicing the stacked weights and saved residuals, stacking
the residuals and the block gradients, the copies around the loop)."""

import program_trace


def read(ctx):
    return program_trace.scope_ms_per_step(ctx, program_trace.STACK_SCOPE)
