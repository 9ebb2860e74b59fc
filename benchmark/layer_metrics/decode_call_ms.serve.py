"""Median wall time of one ``decode_once`` call that ran a step."""


from common import median


def read(ctx):
    d = ctx["spans"].durations.get("decode_once")
    return 1e3 * median(d) if d else None
