"""Device time of the joined ``jit_p2t_prefill`` executions over the
device's busy time, inside the traced stretch: the prefill program's
share of the chip (the scatter's is ``kv_scatter_device_pct.serve``, the
decode program's the rest)."""

import program_split


def read(ctx):
    return program_split.prefill_device_pct(ctx)
