"""``window_device_pct.serve`` of the joined PREFILL executions: the
share of their device time under the program's ``window`` scope (the
sliding-window layers' attention over a whole prompt: projections, q/k
norm, rotary, the band — chunks of a window against two —, the output
projection). Three layers in four are such layers, and their cost is
linear in the prompt where a global layer's is quadratic."""

import program_split
import scope_trace


def read(ctx):
    return scope_trace.program_scope_pct(ctx, program_split.PREFILL,
                                         "window")
