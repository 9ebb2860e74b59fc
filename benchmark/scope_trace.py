"""Device time under a scope that ``program_split.SCOPES`` does not list
(``window``), inside the joined executions of ONE program: what the
EXAONE-MoE cell's two scope readers share. The ops are matched by the
scope's name anywhere in their path, as ``moe_trace.scope_pct`` does
over both programs. A program without the scope (an older commit, a
stale executable: the empty-cache rule) gives None, never a number."""

from __future__ import annotations

import re

import program_split


def program_scope_pct(ctx, program: str, scope: str):
    """Share of ``program``'s joined executions' device time spent in
    ops whose path holds ``scope``."""
    pt, j = program_split._sound(ctx, program)
    if j is None:
        return None
    rx = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?=[/)]|$)")
    under = total = 0.0
    for ex in j.joined:
        by = program_split.inside(pt, ex, lambda op: bool(rx.search(op[3])))
        under += by.get(True, 0.0)
        total += sum(by.values())
    if not under or not total:
        return None
    return 100.0 * under / total
