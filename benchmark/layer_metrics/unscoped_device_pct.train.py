"""Share of the device's busy time, over the traced steps, spent in
ops under none of the program's scopes and in no kernel it names."""

import program_trace


def read(ctx):
    return program_trace.unscoped_pct(ctx)
