"""KV bytes the passes of the traced stretch require of a block-diffusion
decoder (a sequence's context ONCE a pass and layer, whatever the block
length: ``roofline/sdar_moe.paged_block``, from ``ctx_tokens`` and
``block_length`` on ``p2t:decode.dispatch``), as roofline time, over the
device time of the paged kernel's events."""

import program_trace
from roofline import roofline_seconds, sdar_moe


def read(ctx):
    cell, trace = ctx["cell"], ctx["trace"]
    kernels = cell["workload"].get("kernels", {})
    cfg = cell["config"]
    if not cell.get("peaks") or not trace.devices \
            or "paged_block" not in kernels:
        return None
    steps = [c for _, _, _, c in program_trace.spans_named(
        program_trace.of(ctx), "decode.dispatch", trace.window)
        if c.get("block_length")]
    if not steps:
        return None
    flops, nbytes = sdar_moe.paged_block(
        sum(c["ctx_tokens"] for c in steps), cfg["num_hidden_layers"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], steps[0]["block_length"])
    need_s, bound = roofline_seconds(flops, nbytes, cell["peaks"])
    per_dev = ctx["reduce"].pattern_time(trace,
                                         kernels["paged_block"]["pattern"])
    ns = max(v[0] for v in per_dev.values())
    print(f"paged_block_roofline: bound {bound}, required "
          f"{nbytes / 1e9:.3f} GB over {len(steps)} passes, "
          f"{max(v[1] for v in per_dev.values())} events, "
          f"{ns / 1e6:.2f} ms", flush=True)
    if not ns:
        return None
    return 100.0 * need_s / (ns / 1e9)
