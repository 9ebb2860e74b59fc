"""Share of the device's busy time under the program's ``unmask`` scope
(a pass's confidence over the vocabulary, the choice of the positions
to fix, the write-back of the block), inside the traced stretch."""

import moe_trace


def read(ctx):
    return moe_trace.scope_pct(ctx, "unmask")
