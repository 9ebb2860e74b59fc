"""Time in which a collective runs on a device and no other op does,
over the traced stretch, on the worst device."""


def read(ctx):
    trace = ctx["trace"]
    if len(trace.devices) < 2:
        return None
    exposed = ctx["reduce"].exposed_collective(trace)
    window = trace.window[1] - trace.window[0]
    return 100.0 * max(exposed.values()) / window
