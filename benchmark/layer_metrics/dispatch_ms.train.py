"""Median host time inside one call of the fused train step before it
returns (enqueue, no wait), from the benchmark's own span."""


from common import median


def read(ctx):
    d = ctx["spans"].durations.get("step_dispatch")
    return 1e3 * median(d) if d else None
