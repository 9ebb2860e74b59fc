"""1 - union of device-op intervals over the traced stretch, averaged
over the chips."""


def read(ctx):
    busy_s, window_s = ctx["reduce"].busy_and_window_s(ctx["trace"])
    if not window_s or not ctx["trace"].devices:
        return None
    return 100.0 * (1.0 - busy_s / window_s)
