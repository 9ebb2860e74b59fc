"""Mean share of the KV pool's blocks in use at the decode steps of
the traced stretch (``blocks_in_use`` / ``blocks_total`` on
``p2t:decode.dispatch``)."""

import program_trace


def read(ctx):
    counts = [c for _, _, _, c in program_trace.spans_named(
        program_trace.of(ctx), "decode.dispatch", ctx["trace"].window)
        if c.get("blocks_total")]
    if not counts:
        return None
    return 100.0 * sum(c["blocks_in_use"] / c["blocks_total"]
                       for c in counts) / len(counts)
