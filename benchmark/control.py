#!/usr/bin/env python3
"""Readings for the limits of ``correct``: sound runs and the control.

    python3 benchmark/control.py --workload <name> --seeds 11,12,13 \\
        [--seconds 15] [--precision int8] [--no-program]

For every seed, in ONE process (set-up is paid once per seed, programs
come from the compile cache), at the cell's own sizes:

* the SOUND reading: the program against the float32 reference, the
  numbers ``run.py`` compares;
* the CONTROL reading: the reference itself put in the program's place
  and computed in ``--precision`` (default int8, the nearest precision
  below the bf16 the configurations state), against the float32
  reference. For a served model the control does not decode: at each
  position of the same prompts and served tokens it reads the gap of
  the token the lower precision puts first.

Prints one JSON line per seed and a last line with, per number, the
largest sound and the smallest control reading. A limit belongs between
the two (PERF.md gives the readings each limit was set from). The
benchmark's own runs never run this."""

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


def train_readings(cell, seed, args):
    import checks
    import trafficgen
    from common import Spans
    from drivers import train
    out = {}
    cfg = cell["config"]
    if args.program:
        tr = train.Trainer(cell, seed, Spans())
        prog = train.first_steps(tr)
        reference, hp, vocab = tr.reference, tr.hp, tr.vocab
        del tr
        gc.collect()
    else:
        from common import load_module
        reference = load_module("reference", cfg["reference"])
        hp = cell["workload"]["optimizer"]["kwargs"]
        vocab = cfg[cfg["program"]["token_vocab_key"]]
    batches = [trafficgen.batch(cell["traffic"], seed, i, vocab)
               for i in range(train.CHECK_STEPS)]
    ref = checks.reference_training(reference, cfg, hp, seed, batches)
    if args.program:
        out["sound"] = checks.training_numbers(prog, ref)
    for prec in args.precision.split(","):
        low = checks.reference_training(reference, cfg, hp, seed, batches,
                                        precision=prec)
        out["control_" + prec] = checks.training_numbers(low, ref)
    return out


_ENGINE = {}


def serve_readings(cell, seed, args):
    """One engine for all seeds: after the first, a seed's weights go in
    through ``engine.swap_weights`` (the compiled programs take weights
    as arguments)."""
    import checks
    import paddle2_tpu as paddle
    import trafficgen
    from common import Spans, median, percentile
    from drivers import program, serve
    wl, cfg, traffic = cell["workload"], cell["config"], cell["traffic"]
    vocab = cfg[cfg["program"]["token_vocab_key"]]
    if not _ENGINE:
        engine, reference = serve.build_engine(cell, seed)
        _ENGINE.update(engine=engine, reference=reference)
    else:
        engine, reference = _ENGINE["engine"], _ENGINE["reference"]
        model, _ = program.build_model(cfg, wl["program"]["config_overrides"])
        model = paddle.amp.decorate(model, **wl["program"]["amp"])
        model.eval()
        program.set_weights(model, cfg, wl["program"]["layout"], reference,
                            seed)
        engine.swap_weights(model)
        del model
    # warmed up after every swap too: swapped arrays are placed
    # otherwise than the artifact's, so the first swap compiles every
    # program anew, and would do so inside the window
    serve.warm_up(engine, wl, vocab, seed)
    # the seed's schedule as a run offers it; ``--max-requests`` cuts a
    # backlog short so that the drain before the next seed stays brief
    reqs = trafficgen.requests(traffic, seed, args.seconds,
                               vocab)[:args.max_requests]
    load = serve.Load(engine, reqs, Spans(), wl["engine"]["max_batch"])
    elapsed = load.run(args.seconds)
    s = serve.summarize(load, elapsed)
    while load.live:              # drain before the next seed
        load.iterate()
    sample = checks.sample_finished(
        s["finished"], seed, args.sample or wl["check"]["sample_requests"])
    gc.collect()
    pads = (wl["engine"]["max_model_len"], traffic["output_len"]["max"])
    steps = max(1.0, load.spans.counters.get("decode_steps", 0.0))
    out = {"requests": len(sample), "finished": len(s["finished"]),
           "window": {"tokens_per_s": s["tokens"] / elapsed,
                      "context_tokens_per_step":
                          load.spans.counters.get("context_tokens", 0) / steps,
                      "rows_per_step":
                          load.spans.counters.get("active_rows", 0) / steps,
                      "decode_call_ms_p50": 1e3 * median(
                          load.spans.durations.get("decode_once", [0.0])),
                      "itl_ms_p50_p95": [1e3 * median(s["gaps"]),
                                         1e3 * percentile(s["gaps"], 95)]}}
    if args.rehearse:
        del out["window"]
    ref = checks.reference_token_gaps(reference, cfg, seed, sample, *pads)
    out["tokens"] = ref["tokens"]
    out["sound"] = checks.serving_numbers(ref)
    for prec in args.precision.split(","):
        low = checks.reference_token_gaps(reference, cfg, seed, sample,
                                          *pads, precision=prec)
        out["control_" + prec] = checks.serving_numbers(low)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--precision", default="int8")
    ap.add_argument("--no-program", dest="program", action="store_false")
    ap.add_argument("--max-requests", type=int, default=None)
    ap.add_argument("--sample", type=int, default=None,
                    help="served requests compared per seed (default: "
                         "the cell's own)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("PADDLE2_TPU_CACHE_MIN_COMPILE_S", "0")
    import run as harness
    from drivers import program
    cell = harness.load_cell(args.workload, args.rehearse)
    if not args.rehearse:
        program.apply_runtime_env(cell["workload"])
    from common import device_record
    dev = device_record()
    want = "cpu" if args.rehearse else "tpu"
    if dev["platform"] != want or dev["count"] < cell["chips"]:
        print(f"control.py: needs {cell['chips']} {want} device(s), "
              f"found {dev}", file=sys.stderr)
        return 2
    import paddle2_tpu  # noqa: F401
    read = {"train": train_readings,
            "serve": serve_readings}[cell["workload"]["driver"]]
    rows = []
    for seed in (int(x) for x in args.seeds.split(",")):
        row = read(cell, seed, args)
        rows.append(row)
        print(json.dumps({"seed": seed, **row}), flush=True)
    summary = {}
    for side in (k for k in rows[0] if k == "sound"
                 or k.startswith("control_")):
        pick = max if side == "sound" else min
        summary[side] = {n: pick(r[side][n] for r in rows)
                         for n in rows[0][side]}
    print(json.dumps({"summary": summary, "device": dev,
                      "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
