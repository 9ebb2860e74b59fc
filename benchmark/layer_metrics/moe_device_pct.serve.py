"""Share of the device's busy time under the program's ``moe`` scope
(router, dispatch, the grouped matmuls, combine and the layer's
pre-norm), inside the traced stretch."""

import moe_trace


def read(ctx):
    return moe_trace.scope_pct(ctx, "moe")
