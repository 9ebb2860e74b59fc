"""Share of the device's busy time under the program's ``attn`` scope
(pre-norm, projections, rotary, the cache write, the paged or flash
kernel, the output projection), inside the traced stretch."""

import moe_trace


def read(ctx):
    return moe_trace.scope_pct(ctx, "attn")
