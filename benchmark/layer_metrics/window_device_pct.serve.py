"""Of the joined DECODE executions' device time, the share spent in ops
under the program's ``window`` scope (a sliding-window layer's attention:
pre-norm, projections, q/k norm, rotary, the ring write, the ring walk,
the output projection; the scope lies inside ``attn``, which the accepted
``attn_device_pct.serve`` reads whole). None where no op carries it (an
older program, a stale executable: the empty-cache rule)."""

import program_split
import scope_trace


def read(ctx):
    return scope_trace.program_scope_pct(ctx, program_split.DECODE, "window")
