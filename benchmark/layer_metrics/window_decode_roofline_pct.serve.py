"""The ring bytes the traced decode steps require of the sliding-window
layers (``window_tokens`` — the rows' ``min(context, window)`` — x
``window_layers`` x key/value heads x head_dim, K and V, off
``p2t:decode.dispatch``: ``roofline/exaone_moe.window_decode``), over the
published HBM bandwidth, over the device time of the ``window_decode``
kernel's events (the paged body walking a ring, under its own name).
Padded rows are not counted and show as cost. A program without the
count or the kernel gives None."""

import program_trace
from roofline import exaone_moe, roofline_seconds


def read(ctx):
    cell, trace = ctx["cell"], ctx["trace"]
    kernels = cell["workload"].get("kernels", {})
    cfg = cell["config"]
    if not cell.get("peaks") or not trace.devices \
            or "window_decode" not in kernels \
            or "num_key_value_heads" not in cfg:
        return None
    steps = [c for _, _, _, c in program_trace.spans_named(
        program_trace.of(ctx), "decode.dispatch", trace.window)
        if c.get("window_layers") and "window_tokens" in c]
    if not steps:
        return None
    need_s = 0.0
    for c in steps:
        flops, nbytes = exaone_moe.window_decode(
            c["window_tokens"], c["window_layers"],
            cfg["num_key_value_heads"], cfg["head_dim"])
        need_s += roofline_seconds(flops, nbytes, cell["peaks"])[0]
    per_dev = ctx["reduce"].pattern_time(
        trace, kernels["window_decode"]["pattern"])
    ns = max((v[0] for v in per_dev.values()), default=0)
    print(f"window_decode_roofline: required {need_s * 1e3:.2f} ms over "
          f"{len(steps)} steps, "
          f"{max((v[1] for v in per_dev.values()), default=0)} events, "
          f"{ns / 1e6:.2f} ms", flush=True)
    if not ns:
        return None
    return 100.0 * need_s / (ns / 1e9)
