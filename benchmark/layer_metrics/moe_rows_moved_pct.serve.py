"""Of the rows an expert layer would move were every assignment
gathered for the experts and gathered back (2 x rows routed x experts a
row), the share it did move (``moe_rows_moved`` over 2 x ``moe_rows`` x
``num_experts_per_tok`` on ``p2t:decode.dispatch`` and ``p2t:prefill``,
summed over the traced stretch). A layer that holds every expert moves
all of them (100, and more by a prompt's padding); one that holds a
share can stop at the assignments it holds. None where the spans carry
no such count (an older program)."""

import moe_trace


def read(ctx):
    k = ctx["cell"]["config"].get("num_experts_per_tok")
    counts = [c for _, c in moe_trace.routing_counts(ctx)
              if "moe_rows_moved" in c and c.get("moe_rows")]
    if not k or not counts:
        return None
    return 100.0 * sum(c["moe_rows_moved"] for c in counts) \
        / (2 * k * sum(c["moe_rows"] for c in counts))
