#!/usr/bin/env python3
"""``control_freed.py``'s readings for a cell of ``drivers/
serve_blockdiff.py``: ONE engine serves a short load sound and then
under each DAMAGE, is freed, and the float32 reference judges every
record as ``run.py`` would; then stand-in precisions take the program's
place on the sound run's states.

    python3 benchmark/control_blockdiff.py --workload <cell> --seed 11 \\
        [--seconds 12] [--max-requests 64] [--sample 3] \\
        [--damage causal_in_block,commit_skipped,left_to_right,\\
router_unnormalised] [--precision int8,fp8]

Prints one JSON line: the SOUND reading and one per control. A limit
belongs between the largest sound and the smallest control reading of
the number that control moves (PERF.md gives them). The benchmark's own
runs never run this."""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


# ---- what a sound check must NOT pass: each takes the engine and damages
# the served path for as long as it is entered -------------------------
@contextlib.contextmanager
def commit_skipped(engine):
    """The commit pass's keys and values never reach the block's page
    (its row's page entry points at the garbage block): the cache keeps
    what the last denoise pass wrote."""
    from paddle2_tpu.serving.block_cache import GARBAGE_BLOCK
    build = engine._build_block_step
    bs = engine.config.block_size

    def no_commit(*a):
        step = build(*a)
        meta, tables = step.arrays
        for i in range(len(step.active)):
            if meta[i, 1] == 0:
                tables[i, meta[i, 2] // bs] = GARBAGE_BLOCK
        return step

    with _patched(engine, "_build_block_step", no_commit):
        yield


@contextlib.contextmanager
def left_to_right(engine):
    """Positions fixed in order, not by confidence."""
    import jax.numpy as jnp
    from paddle2_tpu.serving import blockdiff

    sound = blockdiff.unmask_low_confidence

    def in_order(logits, ids, masked, n_fix):
        # every masked position's argmax token, of which the FIRST
        # n_fix masked positions take theirs
        tok, _ = sound(logits, ids, masked,
                       jnp.full_like(n_fix, ids.shape[1]))
        fix = masked & (jnp.cumsum(masked, -1) - 1 < n_fix[:, None])
        return jnp.where(fix, tok, ids), masked & ~fix

    with _patched(blockdiff, "unmask_low_confidence", in_order):
        yield


@contextlib.contextmanager
def router_unnormalised(engine):
    """The experts' weights are the raw probabilities."""
    from paddle2_tpu.incubate import moe
    sound = moe.softmax_topk_route

    def raw(a, gate_w, k, norm_topk=True, scale=1.0):
        return sound(a, gate_w, k, False, scale)

    with _patched(moe, "softmax_topk_route", raw):
        yield


@contextlib.contextmanager
def causal_in_block(engine):
    """A position blind to the later positions of its block: a plain
    causal mask in the prefill, and in a pass each position's context
    ends at itself."""
    import jax.numpy as jnp
    from paddle2_tpu.serving import sdar_family
    sound = sdar_family.paged_attention_decode

    def one_by_one(q, k_pool, v_pool, tables, ctx, **kw):
        B = q.shape[1]
        return jnp.concatenate([
            sound(q[:, j:j + 1], k_pool, v_pool, tables, ctx - (B - 1 - j),
                  **kw) for j in range(B)], axis=1)

    with _patched(sdar_family, "paged_attention_decode", one_by_one), \
            _patched(engine.model.cfg, "block_length", 1):
        yield


DAMAGES = {f.__name__: f for f in (causal_in_block, commit_skipped,
                                   left_to_right, router_unnormalised)}


def serve_once(engine, cell, seed, seconds, max_requests, sample_n):
    """A short load through ``serve_blockdiff.Load`` and the sample of
    what it finished; the engine is drained before it returns."""
    import checks
    import trafficgen
    from common import Spans
    from drivers import serve, serve_blockdiff
    wl, cfg = cell["workload"], cell["config"]
    vocab = cfg[cfg["program"]["token_vocab_key"]]
    reqs = trafficgen.requests(cell["traffic"], seed, seconds,
                               vocab)[:max_requests]
    load = serve_blockdiff.Load(engine, reqs, Spans(),
                                wl["engine"]["max_batch"])
    elapsed = load.run(seconds)
    while not engine.idle():
        load.iterate()
    finished = serve.summarize(load, elapsed)["finished"]
    return checks.sample_finished(finished, seed, sample_n), len(finished)


def forget_programs(engine):
    """The next call of each program traces the (damaged) code anew."""
    engine.runner._decode_programs.clear()
    engine.runner._prefill_programs.clear()


def readings(cell, seed, seconds, max_requests, sample_n, damages,
             precisions) -> dict:
    from drivers import serve, serve_blockdiff
    wl, cfg, traffic = cell["workload"], cell["config"], cell["traffic"]
    vocab = cfg[cfg["program"]["token_vocab_key"]]
    engine, reference = serve.build_engine(cell, seed)
    serve.warm_up(engine, wl, vocab, seed)
    samples = {}
    samples["sound"], n = serve_once(engine, cell, seed, seconds,
                                     max_requests, sample_n)
    out = {"seed": seed, "finished": n, "requests": len(samples["sound"])}
    for name in damages:
        with DAMAGES[name](engine):
            forget_programs(engine)
            samples["control_" + name], _ = serve_once(
                engine, cell, seed, seconds, max_requests, sample_n)
        forget_programs(engine)
    del engine
    gc.collect()
    pads = (wl["engine"]["max_model_len"], traffic["output_len"]["max"])

    def reading(sample, **how):
        ref = serve_blockdiff.blockdiff_gaps(reference, cfg, seed, sample,
                                             *pads, **how)
        return dict(serve_blockdiff.blockdiff_numbers(ref),
                    tokens=ref["tokens"])

    for name, sample in samples.items():
        out[name] = reading(sample)
        print(json.dumps({name: out[name]}), file=sys.stderr, flush=True)
    for prec in precisions:
        out["control_" + prec] = reading(samples["sound"], precision=prec)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--max-requests", type=int, default=64)
    ap.add_argument("--sample", type=int, default=3)
    ap.add_argument("--damage", default=",".join(DAMAGES))
    ap.add_argument("--precision", default="int8,fp8")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("PADDLE2_TPU_CACHE_MIN_COMPILE_S", "0")
    import run as harness
    from common import device_record
    from drivers import program
    cell = harness.load_cell(args.workload, args.rehearse)
    if not args.rehearse:
        program.apply_runtime_env(cell["workload"])
    dev = device_record()
    if dev["platform"] != ("cpu" if args.rehearse else "tpu"):
        print(f"control_blockdiff.py: wrong platform {dev}", file=sys.stderr)
        return 2
    out = readings(cell, args.seed, args.seconds, args.max_requests,
                   args.sample, list(filter(None, args.damage.split(","))),
                   list(filter(None, args.precision.split(","))))
    print(json.dumps(dict(out, device=dev)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
