#!/usr/bin/env python3
"""Find a serving cell's knee: one engine, one set-up, the cell's mix
offered at each of a few fixed rates in turn (drained in between).

    python3 benchmark/sweep.py --workload <name> --rates 2,3,4 --seconds 25 --seed 7

Prints one JSON line per rate. The knee is the highest rate at which
the backlog (requests with no first token yet) at the end of the
arrivals is no deeper than at their middle and at least 98 % of the
requests due by then have their first token. Run once when a cell is
defined; its rate is then written into the traffic file as a number."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("PADDLE2_TPU_CACHE_MIN_COMPILE_S", "0")
    import run as harness
    import trafficgen
    from common import Spans, device_record, median, percentile
    from drivers import serve
    cell = harness.load_cell(args.workload, args.rehearse)
    dev = device_record()
    if dev["platform"] != ("cpu" if args.rehearse else "tpu"):
        print(f"sweep.py: wrong platform {dev}", file=sys.stderr)
        return 2
    wl, cfg = cell["workload"], cell["config"]
    vocab = cfg[cfg["program"]["token_vocab_key"]]
    engine, _ = serve.build_engine(cell, args.seed)
    serve.warm_up(engine, wl, vocab, args.seed)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = harness.merge(cell["traffic"],
                                {"arrival": {"rate_per_s": rate}})
        reqs = trafficgen.requests(traffic, args.seed + k, args.seconds,
                                   vocab)
        horizon = trafficgen.population(traffic, args.seconds)["horizon_s"]
        load = serve.Load(engine, reqs, Spans(), wl["engine"]["max_batch"])
        load.timeline = []
        elapsed = load.run(args.seconds)
        s = serve.summarize(load, elapsed)

        def backlog(at):
            rows = [r for r in load.timeline if r[0] <= at]
            return rows[-1][1:] if rows else (0, 0)
        due_by = [r for r in load.records if r["due_s"] <= horizon - 1.0]
        answered = sum(1 for r in due_by if r["stamps"])
        steps = load.spans.counters.get("decode_steps", 0)
        row = {"rate_per_s": rate, "due": len(load.records),
               "answered_share": answered / max(1, len(due_by)),
               "waiting_mid_end": [backlog(horizon / 2)[0],
                                   backlog(horizon)[0]],
               "in_flight_mid_end": [backlog(horizon / 2)[1],
                                     backlog(horizon)[1]],
               "tokens_per_s": s["tokens"] / elapsed,
               "ttft_ms_p50_p95": [1e3 * median(s["ttft"]),
                                   1e3 * percentile(s["ttft"], 95)],
               "itl_ms_p50_p95": [1e3 * median(s["gaps"]),
                                  1e3 * percentile(s["gaps"], 95)],
               "decode_call_ms_p50": 1e3 * median(
                   load.spans.durations["decode_once"]),
               "occupancy": load.spans.counters.get("active_rows", 0)
               / max(1, steps) / wl["engine"]["max_batch"],
               "late_ms_p95": 1e3 * percentile(s["late"], 95)}
        if args.rehearse:
            row = {k: v for k, v in row.items()
                   if k in ("rate_per_s", "due", "answered_share",
                            "waiting_mid_end", "in_flight_mid_end")}
        print(json.dumps(row), flush=True)
        while load.live:          # drain before the next rate
            load.iterate()
    return 0


if __name__ == "__main__":
    sys.exit(main())
