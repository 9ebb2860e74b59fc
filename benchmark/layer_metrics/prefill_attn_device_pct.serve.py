"""Of the joined prefill executions' device time, the share spent in
ops under the program's ``attn`` scope (projections, the flash kernel,
a latent family's expansion, the residual add): the prefill's own
attention, apart from the decode program's."""

import program_split


def read(ctx):
    return program_split.prefill_scope_pct(ctx, "attn")
