"""1 - union of device-op intervals over the time in which the engine
had at least one request in flight (the driver's ``in_flight`` spans),
inside the traced stretch."""


def read(ctx):
    red, trace = ctx["reduce"], ctx["trace"]
    within = red.spans_named(trace, "in_flight")
    if not within or not trace.devices:
        return None
    busy_s, window_s = red.busy_and_window_s(trace, within=within)
    if not window_s:
        return None
    return 100.0 * (1.0 - busy_s / window_s)
