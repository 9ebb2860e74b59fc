#!/usr/bin/env python3
"""``control_staged.py`` for a cell of ``drivers/serve_staged_dense.py``
(Falcon-H1: a dense model whose float32 reference is computed stage by
stage): the SOUND reading and the CONTROL readings that the cell's
``token_logit_gap`` limit is set between, one seed a process.

    python3 benchmark/control_staged_dense.py --workload <cell> --seed 11 \\
        [--seconds 20] [--precision int8] [--control ssm_dropped,...] \\
        [--program-control padded_state|bf16_state] [--sample 4]

Prints one JSON line: ``sound`` (the program against the float32
reference, what ``run.py`` compares), per ``--precision`` the reference
itself in that precision, and per ``--control`` the float32 reference
with one piece of the model's mathematics replaced (``CONTROLS``) — each
in the program's place on the same prompts, its tokens judged as the
program's are. ``--program-control`` damages the PROGRAM instead
(``PROGRAM_CONTROLS``: what only the serving path has — the state a
padded prefill hands over, the type the recurrent state is kept in) and
its reading is the one printed as ``sound``. Every control must fail the
limit. The benchmark's own runs never run this."""

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


def _norm_over_all_lanes(y, z, weight, cfg):
    import jax
    import jax.numpy as jnp
    g = y * jax.nn.silu(z)
    return g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                             + cfg["rms_norm_eps"]) * weight


def _one_multiplier(cfg: dict, index: int) -> dict:
    m = list(cfg["ssm_multipliers"])
    m[index] = 1.0
    return dict(cfg, ssm_multipliers=m)


# what a sound check must NOT pass, as arguments of
# ``serve_staged_dense.staged_token_gaps``: the mixer's whole branch
# gone; ``D x`` gone; the convolution's bias gone; ``dt_bias`` gone; the
# gated norm's statistic over all d_ssm lanes instead of per group; the
# µP multiplier of C (then of dt) set to 1; the keys not scaled
CONTROLS = {
    "ssm_dropped": lambda cfg: {
        "patched": {"skip": lambda name: name == "ssm"}},
    "d_skip_dropped": lambda cfg: {
        "patched": {"skip": lambda name: name == "d_skip"}},
    "conv_bias_dropped": lambda cfg: {
        "patched": {"conv_bias": lambda b: b * 0}},
    "dt_bias_dropped": lambda cfg: {
        "patched": {"dt_bias": lambda leaf, cfg: leaf * 0}},
    "norm_over_all_lanes": lambda cfg: {
        "patched": {"gated_norm": _norm_over_all_lanes}},
    "c_multiplier_one": lambda cfg: {"stand_cfg": _one_multiplier(cfg, 3)},
    "dt_multiplier_one": lambda cfg: {"stand_cfg": _one_multiplier(cfg, 4)},
    "key_multiplier_dropped": lambda cfg: {
        "stand_cfg": dict(cfg, key_multiplier=1.0)},
}


def _padded_state():
    """The prefill hands over the recurrent state at the PADDED end of
    the prompt's bucket: the scan runs over the padding too."""
    from paddle2_tpu.models import falcon_h1
    full = falcon_h1.FalconH1Mixer.full
    falcon_h1.FalconH1Mixer.full = lambda self, u, valid=None: full(self, u)


def _bf16_state():
    """The recurrent state is kept in bfloat16: every step's (and the
    prefill's) state is rounded to 8 bits of mantissa in its slot."""
    import jax
    from paddle2_tpu.serving import falcon_h1_family as fam
    step = fam.ssm_state_step

    def rounded(pool, *args, **kw):
        pool, y = step(pool, *args, **kw)
        return jax.lax.reduce_precision(pool, 8, 7), y

    fam.ssm_state_step = rounded


PROGRAM_CONTROLS = {"padded_state": _padded_state, "bf16_state": _bf16_state}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--precision", default="int8")
    ap.add_argument("--control", default=",".join(CONTROLS))
    ap.add_argument("--program-control", default=None,
                    choices=sorted(PROGRAM_CONTROLS))
    ap.add_argument("--max-requests", type=int, default=None)
    ap.add_argument("--sample", type=int, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("PADDLE2_TPU_CACHE_MIN_COMPILE_S", "0")
    import run as harness
    import checks
    import trafficgen
    from common import Spans, device_record
    from drivers import program, serve
    from drivers import serve_staged_dense as staged
    cell = harness.load_cell(args.workload, args.rehearse)
    if not args.rehearse:
        program.apply_runtime_env(cell["workload"])
    dev = device_record()
    if dev["platform"] != ("cpu" if args.rehearse else "tpu"):
        print(f"control_staged_dense.py: wrong platform {dev}",
              file=sys.stderr)
        return 2
    if args.program_control:
        PROGRAM_CONTROLS[args.program_control]()
    wl, cfg, traffic = cell["workload"], cell["config"], cell["traffic"]
    vocab = cfg[cfg["program"]["token_vocab_key"]]
    engine, reference = staged.build_engine(cell, args.seed)
    serve.warm_up(engine, wl, vocab, args.seed)
    reqs = trafficgen.requests(traffic, args.seed, args.seconds,
                               vocab)[:args.max_requests]
    load = serve.Load(engine, reqs, Spans(), wl["engine"]["max_batch"])
    elapsed = load.run(args.seconds)
    s = serve.summarize(load, elapsed)
    sample = checks.sample_finished(
        s["finished"], args.seed,
        args.sample or wl["check"]["sample_requests"])
    out = {"seed": args.seed, "finished": len(s["finished"]),
           "requests": len(sample), "device": dev,
           "program_control": args.program_control}
    del engine, load
    gc.collect()
    pads = (wl["engine"]["max_model_len"], traffic["output_len"]["max"])

    def reading(name, **how):
        ref = staged.staged_token_gaps(reference, cfg, args.seed, sample,
                                       *pads, **how)
        out["tokens"] = ref["tokens"]
        out[name] = checks.serving_numbers(ref)
        print(json.dumps({name: out[name]}), file=sys.stderr, flush=True)

    reading("sound")
    for prec in filter(None, args.precision.split(",")):
        reading("control_" + prec, precision=prec)
    for name in filter(None, args.control.split(",")):
        reading("control_" + name, variant=(name,), **CONTROLS[name](cfg))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
