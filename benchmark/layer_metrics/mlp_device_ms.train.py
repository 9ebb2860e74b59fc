"""Device self time per step of the ops whose scope path holds the
program's ``mlp`` scope: MLP (its pre-norm, both matmuls, GELU, residual add),
forward, backward and recomputed together, over the traced steps."""

import program_trace


def read(ctx):
    return program_trace.scope_ms_per_step(ctx, "mlp")
