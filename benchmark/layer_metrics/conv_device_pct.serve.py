"""Share of the device's busy time under the program's ``conv`` scope
(the gated short convolution: projections, taps, state read and write,
and the layer's pre-norm), inside the traced stretch."""

import moe_trace


def read(ctx):
    return moe_trace.scope_pct(ctx, "conv")
