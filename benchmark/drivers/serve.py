"""The serving driver: seeded weights saved as an artifact, served by
the program's continuous-batching engine under an open-loop load that
one thread offers on the host clock. Everything a cell is comes from
its files: engine settings, arrival process and rate, length laws,
sharing, warm-up ladders."""

from __future__ import annotations

import contextlib
import gc
import os
import shutil
import time

import numpy as np

import checks
import trafficgen
from common import (HERE, Spans, load_module, median, memory_peak_bytes,
                    note, percentile, start_trace, stop_trace)
from drivers import program


def build_engine(cell: dict, seed: int):
    """Model of the configuration with the seed's weights -> artifact
    -> the engine that ``inference.Config`` builds from it."""
    import paddle2_tpu as paddle
    from paddle2_tpu import inference
    wl, cfg = cell["workload"], cell["config"]
    reference = load_module("reference", cfg["reference"])
    model, model_cfg = program.build_model(
        cfg, wl["program"]["config_overrides"])
    amp = wl["program"].get("amp")
    if amp:
        model = paddle.amp.decorate(model, **amp)
    model.eval()
    program.set_weights(model, cfg, wl["program"]["layout"], reference, seed)
    art = os.path.join(HERE, "_out", "artifact", cell["name"])
    shutil.rmtree(art, ignore_errors=True)
    os.makedirs(art)
    path = os.path.join(art, "model")
    paddle.jit.save(model, path)
    del model
    gc.collect()
    conf = inference.Config(path)
    settings = {k: tuple(v) if isinstance(v, list) else v
                for k, v in wl["engine"].items()}
    conf.enable_continuous_batching(
        **{k: v for k, v in settings.items() if not k.startswith("_")})
    engine = conf.create_serving_engine(gpt_config=model_cfg)
    shutil.rmtree(art, ignore_errors=True)
    return engine, reference


def warm_up(engine, wl: dict, vocab: int, seed: int) -> dict:
    """Reach, through the public API, every program the traffic can:
    one prefill per prompt length of the ladder and one decode step per
    (batch bucket, page bucket). Then the engine is idle and empty."""
    rng = np.random.default_rng([seed % (2 ** 32), 0x3A93])
    w = wl["warmup"]
    ladder = list(w["prompt_lengths"])
    short = min(ladder)
    used = set()
    steps = 0
    now = 0.0
    for b in w["batch_sizes"]:
        for lead in w["lead_prompt_lengths"]:
            # the lead sets the page bucket; lengths still unseen and
            # no longer than it ride along, short ones fill the rest
            fill = [n for n in ladder if n not in used and n <= lead]
            lens = ([lead] + fill[:b - 1])
            lens += [short] * (b - len(lens))
            used.update(lens)
            for n in lens:
                engine.submit(rng.integers(1, vocab, n).tolist(),
                              max_new_tokens=2, arrival_t=now)
            while len(engine.scheduler.running()) < b:
                now += 1.0
                if not engine.admit_and_prefill(now):
                    raise RuntimeError(
                        f"warm-up: {len(engine.scheduler.running())} of "
                        f"{b} sequences admitted, none further")
            while not engine.idle():
                now += 1.0
                engine.tick(now)
                steps += 1
    missing = [n for n in ladder if n not in used]
    if missing:
        raise RuntimeError(f"warm-up never prefilled lengths {missing}")
    want = len(w["batch_sizes"]) * len(w["lead_prompt_lengths"])
    if engine.num_decode_programs != want:
        raise RuntimeError(
            f"warm-up reached {engine.num_decode_programs} decode "
            f"programs, the cell's ladders span {want}")
    return {"decode_steps": steps,
            "decode_programs": engine.num_decode_programs}


class Load:
    """The open loop. One thread: submit every request now due (stamped
    with its due time), one admission round, one decode step; stamp
    tokens on the host clock as each call returns (both calls read the
    tokens back, so a token exists when its call has returned)."""

    def __init__(self, engine, reqs: list, spans: Spans, max_batch: int):
        self.engine, self.reqs, self.spans = engine, reqs, spans
        self.max_batch = max_batch
        self.next = 0
        self.live = {}            # rid -> record, submitted, not done
        self.records = []         # every submitted request
        self.failed = 0
        self.t0 = None
        self.timeline = None      # a sweep keeps (t, waiting, live)

    def clock(self) -> float:
        return time.perf_counter() - self.t0

    def _submit_due(self, now: float) -> None:
        while self.next < len(self.reqs) \
                and self.reqs[self.next]["due_s"] <= now:
            r = self.reqs[self.next]
            self.next += 1
            rec = {"due_s": r["due_s"], "prompt": r["prompt"],
                   "max_new": r["max_new"], "late_s": now - r["due_s"],
                   "stamps": [], "done": False}
            self.records.append(rec)
            try:
                with self.spans.span("submit"):
                    rid = self.engine.submit(r["prompt"], r["max_new"],
                                             arrival_t=r["due_s"])
            except Exception as e:    # refused, shed: counts as failed
                rec["error"] = repr(e)
                self.failed += 1
                continue
            rec["rid"] = rid
            self.live[rid] = rec

    def _stamp(self, t: float) -> None:
        for rid, rec in list(self.live.items()):
            seq = self.engine.sequence(rid)
            n = len(seq.generated)
            rec["stamps"] += [t] * (n - len(rec["stamps"]))
            if seq.done:
                rec["done"] = True
                rec["tokens"] = list(seq.generated)
                del self.live[rid]

    def iterate(self) -> None:
        now = self.clock()
        self._submit_due(now)
        if self.timeline is not None:
            self.timeline.append((now, sum(
                1 for r in self.live.values() if not r["stamps"]),
                len(self.live)))
        busy = bool(self.live)
        frame = self.spans.span("in_flight") if busy \
            else contextlib.nullcontext()
        with frame:
            t = self.clock()
            with self.spans.span("admit_and_prefill"):
                infos = self.engine.admit_and_prefill(t)
            if infos:
                t2 = self.clock()
                self.spans.count("prefill_seconds", t2 - t)
                self.spans.count("prefill_tokens",
                                 sum(i["prompt_tokens"] for i in infos))
                self._stamp(t2)
            t = self.clock()
            with self.spans.span("decode_once"):
                info = self.engine.decode_once(t)
            if info:
                self.spans.count("decode_steps")
                self.spans.count("active_rows", info["n_active"])
                self.spans.counters["max_batch"] = self.max_batch
                self.spans.count("context_tokens", sum(
                    len(r["prompt"]) + len(r["stamps"])
                    for r in self.live.values() if r["stamps"]))
                self._stamp(self.clock())
            else:
                self.spans.durations["decode_once"].pop()
        if not busy and not infos and not info:
            # nothing in flight: wait for the next arrival
            nxt = self.reqs[self.next]["due_s"] \
                if self.next < len(self.reqs) else now + 0.01
            with self.spans.span("idle_wait"):
                time.sleep(max(0.0, min(nxt - self.clock(), 0.01)))

    def run(self, seconds: float, trace=None) -> float:
        """Drive the loop for ``seconds``; ``trace`` = (dir, from_s,
        for_s) takes a profiler trace of that stretch and ends the run
        with it (starting and stopping the profiler stalls the loop for
        seconds, so what follows would measure the stall). Returns the
        elapsed seconds."""
        self.t0 = time.perf_counter()
        frame = None
        while True:
            now = self.clock()
            if now >= seconds:
                break
            if trace and frame is None and now >= trace[1]:
                self.spans.reset()
                start_trace(trace[0])
                self.spans.tracing = True
                frame = self.spans.span("traced_window")
                frame.__enter__()
            if frame is not None and now >= trace[1] + trace[2]:
                break       # a traced run measures its traced stretch
            self.iterate()
        if frame is not None:
            frame.__exit__(None, None, None)
            self.spans.tracing = False
            stop_trace()
            self.traced = {"durations": dict(self.spans.durations),
                           "counters": dict(self.spans.counters)}
        return self.clock()


def summarize(load: Load, elapsed: float) -> dict:
    """The window's end-to-end numbers, over ALL requests due in it."""
    ttft, gaps, tokens, censored = [], [], 0, 0
    for rec in load.records:
        if "error" in rec:
            continue
        st = rec["stamps"]
        tokens += len(st)
        if st:
            ttft.append(st[0] - rec["due_s"])
            gaps += [b - a for a, b in zip(st, st[1:])]
        else:
            # due, not yet answered when the window closed: it waited
            # at least this long
            ttft.append(elapsed - rec["due_s"])
            censored += 1
    late = [rec["late_s"] for rec in load.records]
    finished = [r for r in load.records if r["done"]]
    return {"tokens": tokens, "ttft": ttft, "gaps": gaps,
            "censored": censored, "late": late, "finished": finished}


def run(cell: dict, args, t_start: float, tally) -> dict:
    wl, cfg, traffic = cell["workload"], cell["config"], cell["traffic"]
    spans = Spans()
    vocab = cfg[cfg["program"]["token_vocab_key"]]
    t_a = time.perf_counter()
    engine, reference = build_engine(cell, args.seed)
    t_b = time.perf_counter()
    warmed = warm_up(engine, wl, vocab, args.seed)
    t_c = time.perf_counter()
    reqs = trafficgen.requests(traffic, args.seed, args.seconds, vocab)
    setup_tally = tally.take()
    spans.reset()
    setup_s = time.perf_counter() - t_start
    note("setup_parts", imports_s=t_a - t_start, build_engine_s=t_b - t_a,
         warm_up_s=t_c - t_b, traffic_s=time.perf_counter() - t_c)

    load = Load(engine, reqs, spans, wl["engine"]["max_batch"])
    trace = None
    if args.trace:
        tw = wl["trace"]
        trace = (cell["trace_dir"],
                 min(tw["from_s"], args.seconds * 0.3),
                 min(tw["for_s"], args.seconds * 0.5))
    elapsed = load.run(args.seconds, trace)
    window_tally = tally.take()
    s = summarize(load, elapsed)
    result = {"metrics": {}, "context": {}, "setup_s": setup_s,
              "attempted": len(load.records), "failed": load.failed,
              "memory_peak_bytes": memory_peak_bytes()}
    if args.trace:
        traced = Spans()
        traced.durations = load.traced["durations"]
        traced.counters = load.traced["counters"]
        result["context"] = {"spans": traced}
    else:
        result["metrics"] = {"serve_tokens_per_s": s["tokens"] / elapsed}
    note("serve_window", elapsed_s=elapsed, requests_due=len(load.records),
         finished=len(s["finished"]), in_flight_at_end=len(load.live),
         unanswered_at_end=s["censored"], failed=load.failed,
         output_tokens=s["tokens"],
         ttft_ms_median=1e3 * median(s["ttft"]),
         ttft_ms_p95=1e3 * percentile(s["ttft"], 95),
         ttft_samples=len(s["ttft"]),
         itl_ms_median=1e3 * median(s["gaps"]),
         itl_ms_p95=1e3 * percentile(s["gaps"], 95),
         itl_ms_max=1e3 * max(s["gaps"], default=0.0),
         itl_samples=len(s["gaps"]),
         generator_late_ms_p95=1e3 * percentile(s["late"], 95),
         decode_steps=engine.decode_steps,
         decode_programs=engine.num_decode_programs, warm_up=warmed)
    note("compiles", setup=setup_tally, window=window_tally)

    # the engine goes before the reference's weights are made
    sample = checks.sample_finished(s["finished"], args.seed,
                                    wl["check"]["sample_requests"])
    max_len = wl["engine"]["max_model_len"]
    out_pad = traffic["output_len"]["max"]
    del engine, load
    gc.collect()
    t_ref = time.perf_counter()
    ref = checks.reference_token_gaps(reference, cfg, args.seed, sample,
                                      max_len, out_pad)
    numbers = checks.serving_numbers(ref)
    numbers["window_compiles"] = float(window_tally["compiles"])
    result["correct"] = checks.verdict(numbers, wl["check"]["limits"])
    note("reference", seconds=time.perf_counter() - t_ref,
         requests_checked=len(sample), tokens_checked=ref["tokens"],
         **numbers)
    return result
