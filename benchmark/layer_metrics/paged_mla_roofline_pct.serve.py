"""Roofline time of the latent paged decode's required work — per decode
step of the traced stretch the LARGER of the latents' bytes over the
published HBM bandwidth and 2 x heads x (2 rank + rope) operations a
context token and layer over the published bf16 peak
(``roofline/deepseek_v2.paged_mla``, from ``ctx_tokens`` + ``rows`` on
``p2t:decode.dispatch``: a row reads its context and the token just
written) — over the device time of the ``paged_mla_decode`` events."""

import program_trace
from roofline import deepseek_v2, roofline_seconds


def read(ctx):
    cell, trace = ctx["cell"], ctx["trace"]
    kernels = cell["workload"].get("kernels", {})
    cfg = cell["config"]
    if not cell.get("peaks") or not trace.devices \
            or "paged_mla_decode" not in kernels \
            or "kv_lora_rank" not in cfg:
        return None
    steps = [c for _, _, _, c in program_trace.spans_named(
        program_trace.of(ctx), "decode.dispatch", trace.window)
        if "ctx_tokens" in c and "rows" in c]
    if not steps:
        return None
    need_s, bounds = 0.0, {}
    for c in steps:
        t, bound = roofline_seconds(*deepseek_v2.paged_mla(
            c["ctx_tokens"] + c["rows"], cfg["num_hidden_layers"],
            cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_rope_head_dim"]), cell["peaks"])
        need_s += t
        bounds[bound] = bounds.get(bound, 0) + 1
    per_dev = ctx["reduce"].pattern_time(
        trace, kernels["paged_mla_decode"]["pattern"])
    ns = max(v[0] for v in per_dev.values())
    print(f"paged_mla_roofline: steps by bound {bounds}, required "
          f"{need_s * 1e3:.2f} ms over {len(steps)} steps, "
          f"{max(v[1] for v in per_dev.values())} events, "
          f"{ns / 1e6:.2f} ms", flush=True)
    if not ns:
        return None
    return 100.0 * need_s / (ns / 1e9)
