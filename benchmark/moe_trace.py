"""What the LFM2-MoE cell's per-layer readers share: the program's
routing counts off its spans, and the busy share under a scope that
``program_trace.SCOPES`` does not list. A program without the spans,
counts or scopes (an older commit, a stale executable: the empty-cache
rule) gives None, never a number."""

from __future__ import annotations

import re

import program_trace

COUNTS = ("moe_assignments", "moe_experts_hit", "moe_load_max")


def routing_counts(ctx) -> list:
    """[(span name, counts)] of the ``decode.dispatch`` and ``prefill``
    spans of the traced stretch that carry the routing counts."""
    pt = program_trace.of(ctx)
    out = []
    for name in ("decode.dispatch", "prefill"):
        for _, _, _, c in program_trace.spans_named(pt, name,
                                                    ctx["trace"].window):
            if all(k in c for k in COUNTS):
                out.append((name, c))
    return out


def scope_pct(ctx, scope: str):
    """Device self time of the ops whose path holds ``scope``, as a
    share of the busy time inside the traced stretch."""
    pt, trace = program_trace.of(ctx), ctx["trace"]
    token = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?=[/)]|$)")
    lo, hi = trace.window
    under = total = 0.0
    found = False
    for ops in pt.ops.values():
        by = program_trace.self_time_by(
            ops, lo, hi, lambda op: bool(token.search(op[3])))
        found |= True in by
        under += by.get(True, 0.0)
        total += sum(by.values())
    if not found or not total:
        return None
    return 100.0 * under / total
