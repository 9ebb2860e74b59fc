"""Of the device's idle time while a request was in flight (the
driver's ``bench:in_flight`` spans), the part during which no span of
the program was open on the host: every instant of a gap goes to the
innermost ``p2t:`` span open at it, or to none."""

import program_trace


def read(ctx):
    pt, trace = program_trace.of(ctx), ctx["trace"]
    within = ctx["reduce"].spans_named(trace, "in_flight")
    if not pt.spans or not within or not trace.devices:
        return None
    by = program_trace.idle_by_span(pt, trace, within=within)
    idle = sum(by.values())
    return 100.0 * by.get(None, 0.0) / idle if idle else None
