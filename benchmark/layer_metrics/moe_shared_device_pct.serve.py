"""Share of the device's busy time under the program's ``moe/shared``
scope (the shared experts' SwiGLU, which every row takes), inside the
traced stretch."""

import moe_trace


def read(ctx):
    return moe_trace.scope_pct(ctx, "moe/shared")
