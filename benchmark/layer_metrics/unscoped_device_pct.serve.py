"""Share of the device's busy time, inside the traced stretch, spent in
ops under none of the program's scopes and in no kernel it names (the
training metric's twin); nothing when a scope that the cell's other
readers need is in no op's path (the empty-cache rule)."""

import program_split


def read(ctx):
    return program_split.unscoped_pct(ctx)
