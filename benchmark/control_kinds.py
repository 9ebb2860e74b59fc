#!/usr/bin/env python3
"""``control_staged.py`` for a cell of ``drivers/serve_routed_kinds.py``
(Nemotron-H: ONE mixer a layer, the float32 reference computed stage by
stage, a compiled program a layer kind): the SOUND reading and the
CONTROL readings that the cell's limits are set between, one seed a
process.

    python3 benchmark/control_kinds.py --workload <cell> --seed 11 \\
        [--seconds 20] [--precision bfloat16,int8] \\
        [--control relu_not_squared,scale_dropped,...] [--sample 4]

Prints one JSON line: ``sound`` (the program against the float32
reference, what ``run.py`` compares), per ``--precision`` the reference
itself in that precision, and per ``--control`` the float32 reference
with one piece of the model's mathematics left out or bent
(``CONTROLS``) — each in the program's place on the same prompts, its
tokens and its experts judged as the program's are. Every control must
fail by at least one limit. The benchmark's own runs never run this."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def _zero_shared(leaves: dict) -> dict:
    return {k: v * 0 if k.endswith("_s_down") else v
            for k, v in leaves.items()}


def _norm_over_one_group(y, z, weight, cfg):
    import jax
    import jax.numpy as jnp
    g = y * jax.nn.silu(z)
    return g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                             + cfg["layer_norm_epsilon"]) * weight


def _relu(x):
    import jax
    return jax.nn.relu(x)


def _bf16(H):
    import jax
    return jax.lax.reduce_precision(H, 8, 7)


def _rotated(q, k, cfg):
    from reference import nemotron_h
    return nemotron_h.rotated(q, k, cfg)


# what a sound check must NOT pass, as arguments of
# ``serve_routed_kinds.kinds_token_gaps``: relu^2 -> relu; the routed sum
# not scaled by 2.5; the selection bias ignored; the shared expert's
# output gone; the gated norm's statistic over ONE group (all d_inner
# lanes) instead of 8; ``D x`` gone; the convolution's bias gone; a
# rotary embedding ADDED to the attention layers; the recurrent state
# rounded to bfloat16 at every token
CONTROLS = {
    "relu_not_squared": lambda cfg: {"patched": {"act": _relu}},
    "scale_dropped": lambda cfg: {
        "stand_cfg": dict(cfg, routed_scaling_factor=1.0)},
    "selection_bias_ignored": lambda cfg: {
        "patched": {"selection_bias": lambda b: b * 0}},
    "shared_dropped": lambda cfg: {"damage": _zero_shared},
    "norm_over_one_group": lambda cfg: {
        "patched": {"gated_norm": _norm_over_one_group}},
    "d_skip_dropped": lambda cfg: {
        "patched": {"skip": lambda name: name == "d_skip"}},
    "conv_bias_dropped": lambda cfg: {
        "patched": {"conv_bias": lambda b: b * 0}},
    "rotary_added": lambda cfg: {"patched": {"positions": _rotated}},
    "bf16_state": lambda cfg: {"patched": {"kept_state": _bf16}},
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--precision", default="bfloat16,int8")
    ap.add_argument("--control", default=",".join(CONTROLS))
    ap.add_argument("--max-requests", type=int, default=None)
    ap.add_argument("--sample", type=int, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("PADDLE2_TPU_CACHE_MIN_COMPILE_S", "0")
    import run as harness
    import checks
    import trafficgen
    from common import Spans, device_record
    from drivers import program, serve, serve_routed
    from drivers import serve_routed_kinds as kinds
    cell = harness.load_cell(args.workload, args.rehearse)
    if not args.rehearse:
        program.apply_runtime_env(cell["workload"])
    dev = device_record()
    if dev["platform"] != ("cpu" if args.rehearse else "tpu"):
        print(f"control_kinds.py: wrong platform {dev}", file=sys.stderr)
        return 2
    wl, cfg, traffic = cell["workload"], cell["config"], cell["traffic"]
    vocab = cfg[cfg["program"]["token_vocab_key"]]
    engine, reference = kinds.build_engine(cell, args.seed)
    serve.warm_up(engine, wl, vocab, args.seed)
    reqs = trafficgen.requests(traffic, args.seed, args.seconds,
                               vocab)[:args.max_requests]
    load = serve_routed.Load(engine, reqs, Spans(),
                             wl["engine"]["max_batch"])
    elapsed = load.run(args.seconds)
    s = serve.summarize(load, elapsed)
    sample = checks.sample_finished(
        s["finished"], args.seed,
        args.sample or wl["check"]["sample_requests"])
    out = {"seed": args.seed, "finished": len(s["finished"]),
           "requests": len(sample), "device": dev}
    del engine, load
    gc.collect()
    pads = (wl["engine"]["max_model_len"], traffic["output_len"]["max"])

    def reading(name, **how):
        ref = kinds.kinds_token_gaps(reference, cfg, args.seed, sample,
                                     *pads, **how)
        out["tokens"] = ref["tokens"]
        out[name] = serve_routed.routed_numbers(ref)
        print(json.dumps({name: out[name]}), file=sys.stderr, flush=True)

    reading("sound")
    for prec in filter(None, args.precision.split(",")):
        reading("control_" + prec, precision=prec)
    for name in filter(None, args.control.split(",")):
        reading("control_" + name, **CONTROLS[name](cfg))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
