"""How uneven the routing of a decode step is: the largest number of
rows one expert got (the worst expert layer's) over the mean load of an
expert (assignments / expert layers / experts), averaged over the
decode steps of the traced stretch. 1.0 is an even spread."""

import moe_trace


def read(ctx):
    cfg = ctx["cell"]["config"]
    if "num_experts" not in cfg:
        return None
    layers = len(cfg["layer_types"]) - cfg["num_dense_layers"]
    ratios = [c["moe_load_max"] * layers * cfg["num_experts"]
              / c["moe_assignments"]
              for name, c in moe_trace.routing_counts(ctx)
              if name == "decode.dispatch" and c["moe_assignments"]]
    return sum(ratios) / len(ratios) if ratios else None
