"""Roofline time of the grouped matmul's required work for experts of
TWO matrices (``W_down act(W_up a)``: weights of the experts actually
hit at the PUBLISHED width, rows in and out of two products, 4 x hidden
x width operations an assignment: ``roofline/nemotron_h.moe_gmm2``, from
the routing counts on ``p2t:decode.dispatch`` and ``p2t:prefill``, bound
taken per call) over the device time of the ``moe_gmm`` kernel's
events. Lanes the program pads its storage with are not required and
show as cost. A program without the counts or the kernel gives None."""

import moe_trace
from roofline import nemotron_h, roofline_seconds


def read(ctx):
    cell, trace = ctx["cell"], ctx["trace"]
    kernels = cell["workload"].get("kernels", {})
    if not cell.get("peaks") or not trace.devices \
            or "moe_gmm" not in kernels:
        return None
    counts = moe_trace.routing_counts(ctx)
    if not counts:
        return None
    cfg = cell["config"]
    need_s, bounds = 0.0, {}
    for _, c in counts:
        flops, nbytes = nemotron_h.moe_gmm2(
            c["moe_assignments"], c["moe_experts_hit"], cfg["hidden_size"],
            cfg["moe_intermediate_size"])
        t, bound = roofline_seconds(flops, nbytes, cell["peaks"])
        need_s += t
        bounds[bound] = bounds.get(bound, 0) + 1
    per_dev = ctx["reduce"].pattern_time(trace, kernels["moe_gmm"]["pattern"])
    ns = max((v[0] for v in per_dev.values()), default=0)
    print(f"moe_gmm2_roofline: calls by bound {bounds}, required "
          f"{need_s * 1e3:.2f} ms, "
          f"{max((v[1] for v in per_dev.values()), default=0)} events, "
          f"{ns / 1e6:.2f} ms", flush=True)
    if not ns:
        return None
    return 100.0 * need_s / (ns / 1e9)
