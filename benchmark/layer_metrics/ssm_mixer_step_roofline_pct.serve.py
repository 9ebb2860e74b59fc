"""``ssm_step_roofline_pct.serve`` for a model whose state-space layers
are SOME of its layers: per decode step of the traced stretch that
carries ``ssm_layers`` (``p2t:decode.dispatch``: the program says how
many of its layers keep recurrent state), its REAL ``rows``' recurrent
states of those layers read once and written once plus the rows' small
operands, at the mixer's published shape (``mamba_num_heads``,
``mamba_head_dim``, ``n_groups``, ``ssm_state_size``:
``roofline/nemotron_h.ssm_state_step``), over the published HBM
bandwidth, over the device time of the ``ssm_state_step`` events. Padded
rows are not counted and show as cost. A program without the count or
the kernel gives None."""

import program_trace
from roofline import nemotron_h, roofline_seconds


def read(ctx):
    cell, trace = ctx["cell"], ctx["trace"]
    kernels = cell["workload"].get("kernels", {})
    cfg = cell["config"]
    if not cell.get("peaks") or not trace.devices \
            or "ssm_state_step" not in kernels \
            or "ssm_state_size" not in cfg:
        return None
    steps = [c for _, _, _, c in program_trace.spans_named(
        program_trace.of(ctx), "decode.dispatch", trace.window)
        if c.get("ssm_layers") and "rows" in c]
    if not steps:
        return None
    need_s = 0.0
    for c in steps:
        flops, nbytes = nemotron_h.ssm_state_step(c["rows"],
                                                  c["ssm_layers"], cfg)
        need_s += roofline_seconds(flops, nbytes, cell["peaks"])[0]
    per_dev = ctx["reduce"].pattern_time(
        trace, kernels["ssm_state_step"]["pattern"])
    ns = max((v[0] for v in per_dev.values()), default=0)
    print(f"ssm_mixer_step_roofline: required {need_s * 1e3:.2f} ms over "
          f"{len(steps)} steps, "
          f"{max((v[1] for v in per_dev.values()), default=0)} events, "
          f"{ns / 1e6:.2f} ms", flush=True)
    if not ns:
        return None
    return 100.0 * need_s / (ns / 1e9)
