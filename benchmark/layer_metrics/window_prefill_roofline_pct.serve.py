"""Roofline time of the sliding-window layers' band at prefill — per
prompt of the traced stretch, from ``padded`` on its admission's
``p2t:prefill`` span: ``roofline/exaone_moe.band_keys(padded,
sliding_window)`` query-key pairs x 4 x ``head_dim`` x query heads
operations, and q, the output, k and v crossing HBM once in bfloat16, x
the layers whose ``layer_types`` entry is ``sliding_attention``, bound
taken per prompt — over the device time of the ``window_fwd`` events
(the band as one kernel, ``kernels/pallas_band.py``: the events whose
OWN name it is — a trace names a device op by its whole HLO line, so
the output projection, whose line holds ``%window_fwd.1`` as an operand,
would match the bare word; the global layer's ``flash_fwd`` does not
match either). Beside the value it prints
the number of events and prompts x sliding layers: where every sliding
layer of every prompt took the kernel they are equal (a prompt dispatched
just before the stretch ends may run after it: one prompt's worth of
error at the edges). A program whose band is plain XLA has no such event
and gives None."""

import program_trace
from roofline import roofline_seconds
from roofline.exaone_moe import band_keys

PATTERN = r"^%?window_fwd[.\d]* = "


def read(ctx):
    cell, trace = ctx["cell"], ctx["trace"]
    cfg = cell["config"]
    layers = sum(1 for kind in cfg.get("layer_types", ())
                 if kind == "sliding_attention")
    if not cell.get("peaks") or not trace.devices or not layers:
        return None
    prompts = [c["padded"] for _, _, _, c in program_trace.spans_named(
        program_trace.of(ctx), "prefill", trace.window) if c.get("padded")]
    if not prompts:
        return None
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    need_s = 0.0
    for padded in prompts:
        flops = 4.0 * hd * nh * band_keys(padded, cfg["sliding_window"])
        nbytes = 2.0 * padded * hd * (2 * nh + 2 * nkv)
        need_s += layers * roofline_seconds(flops, nbytes, cell["peaks"])[0]
    per_dev = ctx["reduce"].pattern_time(trace, PATTERN)
    ns = max((v[0] for v in per_dev.values()), default=0)
    events = max((v[1] for v in per_dev.values()), default=0)
    print(f"window_prefill_roofline: padded lengths {sorted(set(prompts))}, "
          f"required {need_s * 1e3:.2f} ms, {events} events for "
          f"{len(prompts)} prompts x {layers} sliding layers = "
          f"{len(prompts) * layers}, {ns / 1e6:.2f} ms", flush=True)
    if not ns:
        return None
    return 100.0 * need_s / (ns / 1e9)
