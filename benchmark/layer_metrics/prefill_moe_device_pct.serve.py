"""Of the joined prefill executions' device time, the share spent in
ops under the program's ``moe`` scope (router, dispatch, the grouped
matmuls, shared experts, combine): the prefill's own expert layers,
apart from the decode program's."""

import program_split


def read(ctx):
    return program_split.prefill_scope_pct(ctx, "moe")
