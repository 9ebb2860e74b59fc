"""Share of the device's busy time under the program's ``ssm`` scope
(the state-space mixer: input projection, convolution and its state,
the chunked scan at prefill, the state step at decode, the gated norm,
the output projection), both programs, inside the traced stretch."""

import moe_trace


def read(ctx):
    return moe_trace.scope_pct(ctx, "ssm")
