"""Of the rows an expert layer routed, the share with at least one of
their experts HELD here (``moe_rows_routed_here`` / ``moe_rows`` on
``p2t:decode.dispatch`` and ``p2t:prefill``, summed over the traced
stretch): how uneven the grouped matmul's input is when the chip holds
one routing group — the rest of the rows reach no expert here."""

import program_trace

NAMES = ("moe_rows_routed_here", "moe_rows")


def read(ctx):
    pt = program_trace.of(ctx)
    here = rows = 0
    for name in ("decode.dispatch", "prefill"):
        for _, _, _, c in program_trace.spans_named(pt, name,
                                                    ctx["trace"].window):
            if all(k in c for k in NAMES):
                here += c["moe_rows_routed_here"]
                rows += c["moe_rows"]
    return 100.0 * here / rows if rows else None
