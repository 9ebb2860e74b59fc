"""Roofline time of the recurrent state step's required traffic — per
decode step of the traced stretch that carries ``state_bytes``
(``p2t:decode.dispatch``: the program keeps per-sequence state), its
REAL ``rows``' recurrent states read once and written once plus the
rows' ``x``, ``B``, ``C``, ``dt`` and ``y`` over the published HBM
bandwidth (``roofline/falcon_h1.ssm_state_step``; the kernel is
memory-bound: 2 operations a state byte) — over the device time of the
``ssm_state_step`` events. Padded rows are not counted and show as
cost. A program without the count or the kernel gives None."""

import program_trace
from roofline import falcon_h1, roofline_seconds


def read(ctx):
    cell, trace = ctx["cell"], ctx["trace"]
    kernels = cell["workload"].get("kernels", {})
    cfg = cell["config"]
    if not cell.get("peaks") or not trace.devices \
            or "ssm_state_step" not in kernels \
            or "mamba_d_state" not in cfg:
        return None
    steps = [c for _, _, _, c in program_trace.spans_named(
        program_trace.of(ctx), "decode.dispatch", trace.window)
        if c.get("state_bytes") and "rows" in c]
    if not steps:
        return None
    flops, nbytes = falcon_h1.ssm_state_step(
        sum(c["rows"] for c in steps), cfg["num_hidden_layers"],
        cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
        cfg["mamba_d_state"])
    need_s, bound = roofline_seconds(flops, nbytes, cell["peaks"])
    per_dev = ctx["reduce"].pattern_time(
        trace, kernels["ssm_state_step"]["pattern"])
    ns = max((v[0] for v in per_dev.values()), default=0)
    print(f"ssm_step_roofline: bound {bound}, required "
          f"{nbytes / 1e9:.3f} GB over {len(steps)} steps, "
          f"{max((v[1] for v in per_dev.values()), default=0)} events, "
          f"{ns / 1e6:.2f} ms", flush=True)
    if not ns:
        return None
    return 100.0 * need_s / (ns / 1e9)
