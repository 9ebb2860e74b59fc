"""Roofline time of the grouped matmul's required work (weights of the
experts actually hit, rows in and out, 6 x hidden x width operations an
assignment: ``roofline/lfm2_moe.moe_gmm``, from the routing counts on
``p2t:decode.dispatch`` and ``p2t:prefill``, bound taken per call) over
the device time of the ``moe_gmm`` kernel's events."""

import moe_trace
from roofline import lfm2_moe, roofline_seconds


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
        flops, nbytes = lfm2_moe.moe_gmm(
            c["moe_assignments"], c["moe_experts_hit"], cfg["hidden_size"],
            cfg["moe_intermediate_size"])
        t, bound = roofline_seconds(flops, nbytes, cell["peaks"])
        need_s += t
        bounds[bound] = bounds.get(bound, 0) + 1
    per_dev = ctx["reduce"].pattern_time(trace, kernels["moe_gmm"]["pattern"])
    ns = max(v[0] for v in per_dev.values())
    print(f"moe_gmm_roofline: calls by bound {bounds}, required "
          f"{need_s * 1e3:.2f} ms, {max(v[1] for v in per_dev.values())} "
          f"events, {ns / 1e6:.2f} ms", flush=True)
    if not ns:
        return None
    return 100.0 * need_s / (ns / 1e9)
