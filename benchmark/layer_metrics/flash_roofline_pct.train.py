"""Roofline time of the flash forward and backward kernels, from their
shapes, over their summed device time in the trace. The kernels are
found by the name patterns in the cell's file (``kernels``), the calls
the algorithm requires per step are stated there too: a kernel run
again for recomputation adds to the time and not to the required work."""

from roofline import flash, roofline_seconds


def read(ctx):
    cell, trace = ctx["cell"], ctx["trace"]
    kernels = cell["workload"].get("kernels", {})
    if not cell.get("peaks") or not trace.devices \
            or "flash_fwd" not in kernels:
        return None
    cfg, traffic = cell["config"], cell["traffic"]
    shards = cell["workload"].get("attention_shards", 1)
    heads = cfg["n_head"]
    shape = dict(batch=traffic["batch"], heads=heads, seq=traffic["seq"],
                 head_dim=cfg["n_embd"] // heads, causal=True)
    need_s, seen_ns, lines = 0.0, 0.0, []
    for key, fn in (("flash_fwd", flash.flash_fwd),
                    ("flash_bwd", flash.flash_bwd)):
        k = kernels[key]
        flops, nbytes = fn(**shape)
        t, bound = roofline_seconds(flops / shards, nbytes / shards,
                                    cell["peaks"])
        per_dev = ctx["reduce"].pattern_time(trace, k["pattern"])
        ns = max(v[0] for v in per_dev.values())
        calls = max(v[1] for v in per_dev.values())
        need_s += t * k["per_step"] * ctx["steps"]
        seen_ns += ns
        lines.append(f"{key}: bound {bound}, roofline {t * 1e6:.1f} us/call,"
                     f" {calls} events, {ns / 1e6:.2f} ms")
    print("flash_roofline: " + "; ".join(lines), flush=True)
    if not seen_ns:
        return None
    return 100.0 * need_s / (seen_ns / 1e9)
