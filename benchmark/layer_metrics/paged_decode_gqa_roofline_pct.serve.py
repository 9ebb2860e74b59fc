"""KV bytes the real contexts of the traced decode steps require of a
grouped-query model (its key/value heads, in the layers that have
attention: ``roofline/paged_decode.paged_decode``), over the published
HBM bandwidth, over the device time of the paged decode kernel's
events."""

from roofline import paged_decode, roofline_seconds


def read(ctx):
    cell, trace = ctx["cell"], ctx["trace"]
    kernels = cell["workload"].get("kernels", {})
    cfg = cell["config"]
    ctx_tokens = ctx["spans"].counters.get("context_tokens", 0.0)
    if not cell.get("peaks") or not trace.devices or not ctx_tokens \
            or "paged_decode" not in kernels \
            or "num_key_value_heads" not in cfg:
        return None
    layers = sum(1 for kind in cfg["layer_types"]
                 if kind == "full_attention")
    flops, nbytes = paged_decode.paged_decode(
        ctx_tokens, layers, cfg["num_key_value_heads"], cfg["head_dim"])
    need_s, bound = roofline_seconds(flops, nbytes, cell["peaks"])
    per_dev = ctx["reduce"].pattern_time(trace,
                                         kernels["paged_decode"]["pattern"])
    ns = max(v[0] for v in per_dev.values())
    print(f"paged_decode_gqa_roofline: bound {bound}, required "
          f"{nbytes / 1e9:.3f} GB, {max(v[1] for v in per_dev.values())} "
          f"events, {ns / 1e6:.2f} ms", flush=True)
    if not ns:
        return None
    return 100.0 * need_s / (ns / 1e9)
