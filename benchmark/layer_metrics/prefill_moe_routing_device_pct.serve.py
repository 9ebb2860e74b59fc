"""Of the joined prefill executions' device time, the share spent in
ops under the expert layers' ``moe/dispatch`` and ``moe/combine``
scopes: the sort of the assignments, the gather of their rows for the
grouped matmuls and the weighted sum of the results back into rows —
index work and row movement around the experts' products, none of it
arithmetic a model asks for. A layer that holds a share of the experts
can keep it to the assignments it holds (PR 47)."""

import program_split
import scope_trace


def read(ctx):
    parts = [scope_trace.program_scope_pct(ctx, program_split.PREFILL,
                                           "moe/" + scope)
             for scope in ("dispatch", "combine")]
    found = [p for p in parts if p is not None]
    return sum(found) if found else None
