"""Mean of running sequences over ``max_batch`` across the decode
steps of the traced stretch (a count from the step info dicts)."""


def read(ctx):
    c = ctx["spans"].counters
    if not c.get("decode_steps"):
        return None
    return 100.0 * c["active_rows"] / (c["decode_steps"] * c["max_batch"])
