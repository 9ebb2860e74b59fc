"""Of the joined prefill executions' device time, the share spent in
ops under the program's ``ssm`` scope (the mixer's projections, the
convolution, the chunked scan, the gated norm): the prefill's own
state-space work, apart from the decode program's. ``program_split``'s
own split knows the accepted scopes only (``ssm`` is not among them,
and it files ``ssm/norm`` under ``norm``), so the ops are matched here
by the scope's name anywhere in their path, as ``moe_trace.scope_pct``
does. None where no op of them carries it (an older program, a stale
executable: the empty-cache rule)."""

import re

import program_split

_SSM = re.compile(r"(?:^|[/(])ssm(?=[/)]|$)")


def read(ctx):
    pt, j = program_split._sound(ctx, program_split.PREFILL)
    if j is None:
        return None
    under = total = 0.0
    for ex in j.joined:
        by = program_split.inside(pt, ex, lambda op: bool(_SSM.search(op[3])))
        under += by.get(True, 0.0)
        total += sum(by.values())
    if not under or not total:
        return None
    return 100.0 * under / total
