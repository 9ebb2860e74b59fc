"""Time of the program's ``p2t:prefill`` spans (dispatch, read-back of
the first token, both scatters) per thousand prompt tokens, by their
``tokens`` counts."""

import program_trace


def read(ctx):
    spans = program_trace.spans_named(program_trace.of(ctx), "prefill",
                                      ctx["trace"].window)
    tokens = sum(c.get("tokens", 0) for _, _, _, c in spans)
    if not tokens:
        return None
    return sum(b - a for _, a, b, _ in spans) / 1e6 / (tokens / 1e3)
