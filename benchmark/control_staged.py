#!/usr/bin/env python3
"""``control_freed.py`` for a cell of ``drivers/serve_routed_staged.py``
(DeepSeek-V2: the float32 reference is computed stage by stage): the
SOUND reading and the CONTROL readings that the cell's limits are set
between, one seed a process.

    python3 benchmark/control_staged.py --workload <cell> --seed 11 \\
        [--seconds 20] [--precision int8] \\
        [--control no_k_rope,latent_norm_skipped,shared_dropped,\\
group_limit_ignored,scale_dropped,mscale_dropped] [--sample 4]

Prints one JSON line: ``sound`` (the program against the float32
reference, what ``run.py`` compares), per ``--precision`` the reference
itself in that precision, and per ``--control`` the float32 reference
with one piece of the model's mathematics left out (``CONTROLS``) —
each in the program's place on the same prompts, its tokens and its
experts judged as the program's are. Every control must fail by at
least one limit. The benchmark's own runs never run this."""

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
    return {k: v * 0 if k.endswith("_sw2") else v for k, v in leaves.items()}


def _without_mscale(cfg: dict) -> dict:
    return dict(cfg, rope_scaling=dict(cfg["rope_scaling"], mscale=0.0,
                                       mscale_all_dim=0.0))


# what a sound check must NOT pass, as arguments of
# ``serve_routed_staged.staged_token_gaps``: the rotary left off the
# shared key; the latent's norm skipped; the shared experts' output
# gone; plain top-k over all groups; the routed sum not scaled; the
# softmax scale without its mscale^2
CONTROLS = {
    "no_k_rope": lambda cfg: {
        "patched": {"rope_key": lambda x, cos, sin: x}},
    "latent_norm_skipped": lambda cfg: {
        "patched": {"latent_norm": lambda x, g, eps: x}},
    "shared_dropped": lambda cfg: {"damage": _zero_shared},
    "group_limit_ignored": lambda cfg: {
        "stand_cfg": dict(cfg, topk_group=cfg["n_group"])},
    "scale_dropped": lambda cfg: {
        "stand_cfg": dict(cfg, routed_scaling_factor=1.0)},
    "mscale_dropped": lambda cfg: {"stand_cfg": _without_mscale(cfg)},
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--precision", default="int8")
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
    from drivers import serve_routed_staged as staged
    cell = harness.load_cell(args.workload, args.rehearse)
    if not args.rehearse:
        program.apply_runtime_env(cell["workload"])
    dev = device_record()
    if dev["platform"] != ("cpu" if args.rehearse else "tpu"):
        print(f"control_staged.py: wrong platform {dev}", file=sys.stderr)
        return 2
    wl, cfg, traffic = cell["workload"], cell["config"], cell["traffic"]
    vocab = cfg[cfg["program"]["token_vocab_key"]]
    engine, reference = serve.build_engine(cell, args.seed)
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
        ref = staged.staged_token_gaps(reference, cfg, args.seed, sample,
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
