"""Of the rows the grouped matmul's tiles multiply, the share that are
assignments (``moe_assignments`` / ``moe_tile_rows`` on
``p2t:decode.dispatch`` and ``p2t:prefill``, summed over the traced
stretch): a visit multiplies its whole row tile and keeps its own
group's rows, so a tile taller than the rows a group gets is arithmetic
nobody needs. A program whose spans carry no ``moe_tile_rows`` says
nothing."""

import moe_trace


def read(ctx):
    counts = [c for _, c in moe_trace.routing_counts(ctx)
              if c.get("moe_tile_rows")]
    tiled = sum(c["moe_tile_rows"] for c in counts)
    if not tiled:
        return None
    return 100.0 * sum(c["moe_assignments"] for c in counts) / tiled
