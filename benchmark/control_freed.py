#!/usr/bin/env python3
"""``control.py``'s serving readings for a cell of ``drivers/
serve_routed.py`` whose float32 reference does not fit on the chip
BESIDE the engine (LFM2-24B-A2B: 5.9 GB of engine, 10.8 GB of reference
weights): one seed a process, and the engine is freed before the
reference's weights are made — as ``run.py`` itself does.

    python3 benchmark/control_freed.py --workload <cell> --seed 11 \\
        [--seconds 15] [--precision int8,fp8] \\
        [--damage experts_removed,experts_int8,bias_dropped] \\
        [--max-requests 160] [--sample 6]

Prints one JSON line: the SOUND reading (the program against the
float32 reference, what ``run.py`` compares) and the CONTROL readings:
per ``--precision`` the reference itself in that precision, per
``--damage`` the float32 reference with damaged weights (``DAMAGES``),
each in the program's place on the same prompts, its tokens and its
experts judged as the program's are. A limit belongs between the
largest sound and the smallest control reading over the seeds (PERF.md
gives them). The benchmark's own runs never run this."""

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


def _damage(change):
    """weights -> weights with ``change(leaf name, array)`` applied to
    every leaf, in place (the float32 weights fill most of the chip)."""
    def apply(params):
        import jax
        return jax.jit(lambda p: {k: change(k, v) for k, v in p.items()},
                       donate_argnums=0)(params)
    return apply


def _is_expert(name: str) -> bool:
    return name.startswith("moe") and name[-3:] in ("_w1", "_w3", "_w2")


def _int8_experts(name, w):
    from reference.common import _fake_int8
    return _fake_int8(w, -2) if _is_expert(name) else w


# what a sound check must NOT pass: every expert layer's output gone;
# the experts' weights (only they) in int8 per output channel; the
# router blind to its selection bias
DAMAGES = {
    "experts_removed": _damage(
        lambda k, w: w * 0 if k.startswith("moe") and k.endswith("_w2")
        else w),
    "experts_int8": _damage(_int8_experts),
    "bias_dropped": _damage(
        lambda k, w: w * 0 if k.startswith("moe") and k.endswith("_bias")
        else w),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--precision", default="int8")
    ap.add_argument("--damage", default="")
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
    cell = harness.load_cell(args.workload, args.rehearse)
    if not args.rehearse:
        program.apply_runtime_env(cell["workload"])
    dev = device_record()
    if dev["platform"] != ("cpu" if args.rehearse else "tpu"):
        print(f"control_freed.py: wrong platform {dev}", file=sys.stderr)
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

    def reading(**how):
        ref = serve_routed.routed_token_gaps(reference, cfg, args.seed,
                                             sample, *pads, **how)
        out["tokens"] = ref["tokens"]
        return serve_routed.routed_numbers(ref)

    out["sound"] = reading()
    print(json.dumps({"sound": out["sound"]}), file=sys.stderr, flush=True)
    for prec in filter(None, args.precision.split(",")):
        out["control_" + prec] = reading(precision=prec)
    for name in filter(None, args.damage.split(",")):
        out["control_" + name] = reading(damage=DAMAGES[name])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
