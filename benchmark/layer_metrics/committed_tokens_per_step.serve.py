"""Tokens that became real (their block committed) per decode step of
the traced stretch: ``tokens_committed`` over the ``p2t:decode.dispatch``
spans of a block-diffusion program. With every slot full at block
length B and S denoise passes a block it is slots x B / (S + 1); it
falls with idle slots and with blocks computed twice."""

import program_trace


def read(ctx):
    spans = [c for _, _, _, c in program_trace.spans_named(
        program_trace.of(ctx), "decode.dispatch", ctx["trace"].window)]
    # a span counts the step it enqueues (``block_length``) and the
    # tokens of the step it reads back: the last may enqueue none
    steps = sum(1 for c in spans if c.get("block_length"))
    if not steps:
        return None
    return sum(c.get("tokens_committed", 0) for c in spans) / steps
