"""Roofline time of the expanded prefill's flash forward — the causal
triangle at query/key width ``qk_nope + qk_rope`` and value width
``v_head_dim``, all heads and layers
(``roofline/deepseek_v2.flash_mla_prefill``), per prompt of the traced
stretch from ``padded`` on its admission's ``p2t:prefill`` span, bound
taken per prompt — over the device time of the ``flash_fwd`` events (in a
serving cell they are the prefill program's). A prompt dispatched just
before the stretch ends may run after it: one prompt's worth of error at
the edges."""

import program_trace
from roofline import deepseek_v2, roofline_seconds


def read(ctx):
    cell, trace = ctx["cell"], ctx["trace"]
    kernels = cell["workload"].get("kernels", {})
    cfg = cell["config"]
    if not cell.get("peaks") or not trace.devices \
            or "flash_fwd" not in kernels or "kv_lora_rank" not in cfg:
        return None
    prompts = [c["padded"] for _, _, _, c in program_trace.spans_named(
        program_trace.of(ctx), "prefill", trace.window) if c.get("padded")]
    if not prompts:
        return None
    need_s, bounds = 0.0, {}
    for padded in prompts:
        t, bound = roofline_seconds(*deepseek_v2.flash_mla_prefill(
            padded, cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"]), cell["peaks"])
        need_s += t
        bounds[bound] = bounds.get(bound, 0) + 1
    per_dev = ctx["reduce"].pattern_time(trace,
                                         kernels["flash_fwd"]["pattern"])
    ns = max(v[0] for v in per_dev.values())
    print(f"flash_mla_prefill_roofline: prompts by bound {bounds}, padded "
          f"lengths {sorted(set(prompts))}, required {need_s * 1e3:.2f} ms, "
          f"{max(v[1] for v in per_dev.values())} events, "
          f"{ns / 1e6:.2f} ms", flush=True)
    if not ns:
        return None
    return 100.0 * need_s / (ns / 1e9)
