#!/usr/bin/env python3
"""First light on the chip: the two normal entry points, end to end.

``python chip_smoke.py`` needs one TPU chip and drives, in ONE process
and through the public API only:

* the trainer — GPT-2-medium widths (hidden 1024, 16 heads x 64, FFN
  4096, 24 layers, seq 1024, batch 8, vocab 32768) in bf16
  (``amp.decorate(level="O2")``,
  ``AdamW(multi_precision=True)``, ``recompute_granularity="dots"``,
  stacked blocks, fused head+CE) and stepped through
  ``paddle.jit.train_step`` on distinct seeded batches;
* the server — a seeded random-weight model of the same widths in the
  serving architecture, saved with ``paddle.jit.save`` and served
  through ``inference.Config(...).enable_continuous_batching`` ->
  ``create_serving_engine``: prompts of different lengths are
  ``submit()``-ed and ``tick()``-ed to idle on the host clock. Every
  served token is then held to the model's own full causal forward
  over prompt + served tokens: its reference logit must lie within
  ``TIE_ULPS`` bf16 steps of that position's maximum (on the chip a
  zeroed decode attention lands 23-53 steps away, a wrong layer's pool
  9-10, a sound engine at most 1), and a control that pairs each stream with ANOTHER request's reference
  must be rejected, so the check is shown able to fail. The streams
  are compared token-for-token with ``model.generate(temperature=0)``
  too; a random bf16 model's top two logits are often one or two bf16
  steps apart, and where the two paths then part, the split must be
  such a tie in the reference or the phase fails.

Each phase prints one JSON line; a phase that fails raises, so the
exit code is non-zero and the last line is never reached. The last
line is the contract's and nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

Options (the driver passes none):
  --four-chips     ONLY the sharded phase: dp2 x mp2 over four chips
                   against the same step on one chip (count is 4).
  --cpu-rehearsal  tiny sizes on the CPU backend, to rehearse the
                   control flow; every device field then says ``cpu``.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

import numpy as np

OUT_DIR = "chip_smoke_out"

# (hidden, layers, heads, seq, batch, vocab)
FULL = dict(hidden=1024, layers=24, heads=16, seq=1024, batch=8,
            vocab=32768)
TINY = dict(hidden=64, layers=2, heads=4, seq=64, batch=2, vocab=512)
STEPS = 8           # train steps, each on its own seeded batch
# A served token passes when its reference logit is within this many
# bf16 steps of the reference maximum. Logits leave the head in bf16
# (one step is 2^-6 at the maxima of 2-4 a random-weight model shows),
# and two numerically different bf16 attention paths move them by a
# step or two. Observed on the chip (PR 21): served tokens at most 1
# step below the maximum, generate's parting token 2; with the decode
# attention zeroed 23-53 steps, with every layer reading layer 0's
# pool 9-10. A 32768-way random-weight model has about one other token
# this close to its maximum.
TIE_ULPS = 4


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CacheTally:
    """Persistent-compilation-cache hits/misses, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {"cache_hits": self.hits, "cache_misses": self.misses}
        self.hits = self.misses = 0
        return out


def device_record() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def skewed_batch(rs, batch: int, seq: int, vocab: int):
    """Token ids from a seeded, heavily skewed unigram distribution:
    learnable within a handful of steps (the loss falls from ~ln(vocab)
    toward the distribution's entropy), unlike uniform noise."""
    return np.minimum((vocab * rs.random_sample((batch, seq)) ** 6),
                      vocab - 1).astype(np.int32)


def build_trainer(size: dict, tensor_parallel: bool = False):
    """The GPT trainer's model, optimizer and compiled step."""
    import paddle2_tpu as paddle
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.models import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(vocab_size=size["vocab"], hidden_size=size["hidden"],
                    num_layers=size["layers"], num_heads=size["heads"],
                    max_position_embeddings=size["seq"],
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_recompute=True, recompute_granularity="dots",
                    stacked_blocks=True, fused_head_loss=True,
                    tensor_parallel=tensor_parallel)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    optimizer = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          multi_precision=True)

    def train_fn(ids, labels):
        _, loss = model(ids, labels=labels)
        return loss

    step = paddle.jit.train_step(train_fn, optimizer)
    return model, optimizer, step


def compiled_step_text(step):
    """The compiled program of the step's one cache entry (a persistent
    cache read after the first call) and its memory analysis."""
    compiled = step.last_entry.lower(*step.last_abstract_args).compile()
    return compiled.as_text(), compiled.memory_analysis()


def run_steps(step, batches, to_tensor):
    """Step once per batch; (losses, seconds per step), each step timed
    around ``block_until_ready``."""
    import jax
    losses, secs = [], []
    for ids in batches:
        t = to_tensor(ids)
        t0 = time.perf_counter()
        loss = step(t, t)
        jax.block_until_ready(loss._data)
        secs.append(time.perf_counter() - t0)
        losses.append(float(np.asarray(loss._data)))
    return losses, secs


def check_losses(losses, vocab: int) -> None:
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    # a randomly initialised LM predicts ~uniform: the first loss is
    # ln(vocab) up to the init noise
    if abs(losses[0] - math.log(vocab)) > 1.0:
        raise AssertionError(
            f"first loss {losses[0]} is not ~ln({vocab})="
            f"{math.log(vocab):.3f}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")


# ------------------------------------------------------------- trainer
def phase_trainer(size: dict, tally: CacheTally,
                  expect_kernel: bool) -> None:
    import jax
    import paddle2_tpu as paddle
    dev = jax.devices()[0]
    model, optimizer, step = build_trainer(size)
    step.collect_cost = True         # keeps the entry + abstract args
    rs = np.random.RandomState(0)
    batches = [skewed_batch(rs, size["batch"], size["seq"], size["vocab"])
               for _ in range(STEPS)]
    losses, secs = run_steps(step, batches, paddle.to_tensor)
    cache = tally.take()
    check_losses(losses, size["vocab"])
    text, mem = compiled_step_text(step)
    n_kernel = text.count("tpu_custom_call")
    if expect_kernel and n_kernel == 0:
        raise AssertionError(
            "no tpu_custom_call in the compiled train step: the flash "
            "kernel was replaced by the XLA attention")
    if step.program_cache_size != 1:
        raise AssertionError(
            f"{step.program_cache_size} step programs, expected 1")
    emit("trainer", device=device_record(), config=size,
         params_m=round(model.num_params() / 1e6, 1),
         compile_plus_first_step_s=round(secs[0], 2),
         steady_step_s=[round(s, 4) for s in secs[2:]],
         losses=[round(x, 4) for x in losses],
         tpu_custom_calls_in_compiled_step=n_kernel,
         compiled_temp_bytes=int(mem.temp_size_in_bytes),
         compiled_argument_bytes=int(mem.argument_size_in_bytes),
         peak_bytes_in_use=peak_bytes(dev) if expect_kernel else None,
         **cache)


# -------------------------------------------------------------- server
def kernel_parity(size: dict) -> dict:
    """Both paged-decode bodies against the dense reference at the
    served head geometry, on this device: at the serving cell's decode
    shape (64 rows x a full table of pages, contexts a quarter to the
    whole of the model's length, on a shuffled pool) and at a ragged
    one (a context of 1, partly filled pages, contexts that end just
    before, on and just after a compute block's edge)."""
    import jax.numpy as jnp
    from paddle2_tpu.serving.paged_attention import (
        paged_attention_decode, paged_attention_reference)
    H, D, bs = size["heads"], size["hidden"] // size["heads"], 16
    seq = size["seq"]
    n_pages = seq // bs
    rng = np.random.default_rng(0)
    edge = min(256, seq // 2)
    cases = {
        "cell": rng.integers(seq // 4, seq + 1, 64),
        "ragged": np.asarray([1, 17, 40, edge - 1, edge, edge + 1,
                              seq - bs - 1, seq]),
    }
    err = {}
    for case, ctx in cases.items():
        rows = len(ctx)
        n_blocks = rows * n_pages + 1
        # every row's pages are its own, scattered over the pool
        tables = (rng.permutation(np.arange(1, n_blocks))
                  .reshape(rows, n_pages).astype(np.int32))
        kp = jnp.asarray(rng.normal(size=(n_blocks, bs, H * D)),
                         jnp.bfloat16)
        vp = jnp.asarray(rng.normal(size=(n_blocks, bs, H * D)),
                         jnp.bfloat16)
        q = jnp.asarray(rng.normal(size=(rows, 1, H, D)), jnp.bfloat16)
        # the reference unrolls rows x heads: eight rows a call
        ref = np.concatenate([np.asarray(paged_attention_reference(
            q[r:r + 8], kp, vp, tables[r:r + 8], ctx[r:r + 8]),
            np.float32) for r in range(0, rows, 8)])
        for name, pps in (("single", None), ("split", 4)):
            out = np.asarray(paged_attention_decode(
                q, kp[None], vp[None], tables, ctx,
                pages_per_split=pps), np.float32)
            if out.shape != ref.shape or not np.isfinite(out).all():
                raise AssertionError(f"paged {name} ({case}): bad output")
            gap = err[f"{case}.{name}"] = float(np.abs(out - ref).max())
            # bf16 probabilities and outputs: 2^-8 relative steps on
            # O(1) values — the tolerance tests/test_serving.py uses
            # for bf16
            if gap > 2e-2:
                raise AssertionError(
                    f"paged {name} kernel ({case}) off the dense "
                    f"reference by {gap}")
    err.update(gqa_parity(size))
    err.update(ring_parity(size))
    err.update(long_table_parity(size))
    err.update(gmm_parity(size))
    err.update(ssm_step_parity(size))
    err.update(flash_parity(size))
    err.update(mla_split(size))
    err.update(paged_split(size))
    err.update(moe_routing_split(size))
    err.update(window_band_split(size))
    return err


def mla_split(size: dict) -> dict:
    """The latent paged body (``paged_mla_decode``) where the size
    allows at the DeepSeek-V2 cell's decode shape — 128 rows, 128 query
    heads over rows of 512 + 64 (+ 64) lanes, 384 pages of 16; contexts
    like the cell's: a prompt of 2,048 / 3,072 / 5,120 whose pages are
    one ascending run, then 0-1,024 decoded tokens on scattered pages —
    against the dense reference, and its time SPLIT (PERF.md section 6,
    PR 36, step 0): the whole body, its copies alone (the block's
    arithmetic left out) and its arithmetic alone (no copy started or
    waited for; what the buffer holds does not change the time), us a
    row, best of 5 chains of twenty distinct calls. Where the two parts
    add up to the whole they do not overlap. Times are taken on the chip
    only."""
    import functools
    from unittest import mock
    import jax
    import jax.numpy as jnp
    from paddle2_tpu.serving import paged_attention as pa
    big = size["hidden"] >= 1024
    B, H, rank, dr, bs, P = ((128, 128, 512, 64, 16, 384) if big
                             else (4, 4, 32, 8, 8, 32))
    rng = np.random.default_rng(4)
    prompts = rng.choice([2048, 3072, 5120] if big else [64, 128], B)
    ctx = (prompts + rng.integers(0, (P * bs - prompts.max()) + 1, B)
           ).astype(np.int32)
    tables = np.zeros((B, P), np.int32)
    scattered = iter(rng.permutation(np.arange(B * P // 2, B * P)) + 1)
    first = 1
    for r in range(B):
        run, live = prompts[r] // bs, -(-int(ctx[r]) // bs)
        tables[r, :run] = np.arange(first, first + run)
        tables[r, run:live] = [next(scattered) for _ in range(live - run)]
        first += run
    W = pa.mla_row_width(rank, dr)
    pool = jax.random.normal(jax.random.PRNGKey(0), (1, B * P + 1, bs, W),
                             jnp.bfloat16).at[..., rank + dr:].set(0)
    q_c, q_r = (jax.random.normal(jax.random.PRNGKey(k), (B, H, n),
                                  jnp.bfloat16)
                for k, n in ((1, rank), (2, dr)))
    got = np.asarray(pa.paged_mla_decode(q_c, q_r, pool, tables, ctx, 0.1147),
                     np.float32)
    ref = np.asarray(pa.paged_mla_reference(q_c[:8], q_r[:8], pool[0],
                                            tables[:8], ctx[:8], 0.1147),
                     np.float32)
    out = {"mla.cell": float(np.abs(got[:8] - ref).max())}
    if not np.isfinite(got).all() or out["mla.cell"] > 2e-2:
        raise AssertionError(f"latent paged kernel off the dense "
                             f"reference by {out['mla.cell']}")
    if not big:
        return out

    class NoCopy:
        start = wait = staticmethod(lambda: None)

    ppb, ppc = pa._mla_plan(P, bs, W, pool.dtype)
    parts = {
        "whole": [],
        "copies_only": [mock.patch.object(pa, "_mla_attend",
                                          lambda *a, **k: None)],
        "arith_only": [mock.patch.object(pa.pltpu, "make_async_copy",
                                         lambda *a, **k: NoCopy)],
    }
    for name, patches in parts.items():
        for p in patches:
            p.start()
        try:
            # a trace of its own: the patched names are not in the key
            # of the jitted call
            call = functools.partial(
                pa._mla_decode.__wrapped__, scale=0.1147, interpret=False,
                ppb=ppb, ppc=ppc)

            @jax.jit
            def chain(q_c, q_r, pool, tables, ctx):
                # distinct operands: identical calls would be merged
                return sum(call(jnp.roll(q_c, k, 0), q_r, pool, tables, ctx,
                                jnp.asarray(0, jnp.int32))[:, 0, 0]
                           .astype(jnp.float32) for k in range(20))

            args = (q_c, q_r, pool, jnp.asarray(tables), jnp.asarray(ctx))
            chain(*args).block_until_ready()
            best = math.inf
            for _ in range(5):
                t0 = time.perf_counter()
                chain(*args).block_until_ready()
                best = min(best, time.perf_counter() - t0)
        finally:
            for p in patches:
                p.stop()
        out[f"mla.us_a_row.{name}"] = best / 20 / B * 1e6
    out["mla.live_pages_a_row"] = float(np.mean(-(-ctx // bs)))
    return out


def paged_split(size: dict) -> dict:
    """The single-softmax paged body (``paged_decode``) where the size
    allows at the decode shapes of the SDAR (64 rows x 4 positions, 32
    query heads over 4 key/value heads of 128, 192 pages), Nemotron
    (256 rows, 32 over 2, 256 pages) and Falcon-H1 (128 rows, 20 over
    4, 160 pages) cells, on two tables each: ``cell`` — a prompt on one
    ascending run of pages, then decoded tokens on scattered pages, as
    the cells' allocator hands them out — and ``scattered`` — no two
    consecutive pages anywhere. Against the dense reference, and its
    time SPLIT as :func:`mla_split` splits the latent body's (PERF.md
    section 6, PR 43, step 0): the whole body, its copies alone (scores,
    softmax and values left out) and its arithmetic alone (no copy
    started or waited for), us a row, best of 5 chains of twenty
    distinct calls. Times are taken on the chip only."""
    from unittest import mock
    import jax
    import jax.numpy as jnp
    from paddle2_tpu.serving import paged_attention as pa
    big = size["hidden"] >= 1024
    bs = 16 if big else 8
    # rows, positions a row, query heads, key/value heads, head dim,
    # pages a row, prompt lengths
    shapes = {
        "sdar": (64, 4, 32, 4, 128, 192, (512, 1024, 2048)),
        "nemotron": (256, 1, 32, 2, 128, 256, (512, 1024, 2048)),
        "falconh1": (128, 1, 20, 4, 128, 160, (256, 512, 1024)),
    } if big else {"tiny": (4, 2, 4, 2, 16, 32, (64, 128))}
    f32 = jnp.float32

    class NoCopy:
        start = wait = staticmethod(lambda: None)

    parts = {
        "whole": [],
        "copies_only": [mock.patch.multiple(
            pa,
            _block_scores=lambda q, k, *_: jnp.zeros(
                (q.shape[0], k.shape[0]), f32),
            _block_softmax=lambda s: s,
            _block_values=lambda p, v: jnp.zeros(
                (p.shape[0], v.shape[1]), f32))],
        "arith_only": [mock.patch.object(pa.pltpu, "make_async_copy",
                                         lambda *a, **k: NoCopy)],
    }
    out = {}
    for shape, (B, Q, H, Hkv, D, P, prompt_lens) in shapes.items():
        rng = np.random.default_rng(5)
        prompts = rng.choice(prompt_lens, B)
        ctx = (prompts + rng.integers(0, P * bs - max(prompt_lens) + 1, B)
               ).astype(np.int32)
        live = -(-ctx // bs)
        n_blocks = B * P + 1
        cell = np.zeros((B, P), np.int32)
        behind = iter(rng.permutation(np.arange(B * P // 2, B * P)) + 1)
        first = 1
        for r in range(B):
            run = prompts[r] // bs
            cell[r, :run] = np.arange(first, first + run)
            cell[r, run:live[r]] = [next(behind)
                                    for _ in range(live[r] - run)]
            first += run
        # a row holds odd ids only or even ids only: no page follows
        # its neighbour
        ids = np.arange(1, n_blocks)
        shuffled = np.concatenate([rng.permutation(ids[::2]),
                                   rng.permutation(ids[1::2])])
        tables = {"cell": cell,
                  "scattered": shuffled.reshape(B, P).astype(np.int32)}
        kp, vp = (jax.random.normal(jax.random.PRNGKey(k),
                                    (1, n_blocks, bs, Hkv * D), jnp.bfloat16)
                  for k in (0, 1))
        q = jax.random.normal(jax.random.PRNGKey(2), (B, Q, H, D),
                              jnp.bfloat16)
        for kind, table in tables.items():
            got = np.asarray(pa.paged_attention_decode(
                q, kp, vp, table, ctx), np.float32)
            # every position of a row sees the same context
            ref = np.stack([np.asarray(pa.paged_attention_reference(
                q[:4, p:p + 1], kp[0], vp[0], table[:4], ctx[:4]),
                np.float32)[:, 0] for p in range(Q)], 1)
            gap = out[f"paged.{shape}.{kind}"] = float(
                np.abs(got[:4] - ref).max())
            if not np.isfinite(got).all() or gap > 2e-2:
                raise AssertionError(f"paged kernel ({shape}, {kind} table) "
                                     f"off the dense reference by {gap}")
        if not big:
            continue
        for name, patches in parts.items():
            for p in patches:
                p.start()
            # the patched names are not in the key of the jitted call
            pa._decode_single.clear_cache()
            try:
                @jax.jit
                def chain(q, kp, vp, table, ctx):
                    # distinct operands: identical calls would be merged
                    return sum(pa.paged_attention_decode(
                        jnp.roll(q, k, 0), kp, vp, table, ctx,
                        interpret=False)[:, 0, 0, 0].astype(f32)
                        for k in range(20))

                for kind, table in tables.items():
                    args = (q, kp, vp, jnp.asarray(table), jnp.asarray(ctx))
                    chain(*args).block_until_ready()
                    best = math.inf
                    for _ in range(5):
                        t0 = time.perf_counter()
                        chain(*args).block_until_ready()
                        best = min(best, time.perf_counter() - t0)
                    out[f"paged.{shape}.{kind}.us_a_row.{name}"] = \
                        best / 20 / B * 1e6
            finally:
                for p in patches:
                    p.stop()
                pa._decode_single.clear_cache()
        out[f"paged.{shape}.live_pages_a_row"] = float(live.mean())
        out[f"paged.{shape}.prompt_pages_a_row"] = float(
            (prompts // bs).mean())
    return out


def flash_parity(size: dict) -> dict:
    """The flash kernel's walk — forward and the three gradients —
    against the XLA attention path on this device, where the size
    allows at the training cell's shape (B 8, S 1024, 16 heads of 64,
    causal: four q tiles, one fused backward call) and at the
    block-diffusion prefill's (S 2048, 32 heads of 128, causal by
    blocks of 4); then the head-major entry against the other one."""
    import jax
    import jax.numpy as jnp
    from paddle2_tpu.kernels.attention import _sdpa_xla
    from paddle2_tpu.kernels.pallas_flash import (flash_attention_bhsd,
                                                  flash_attention_bshd)
    big = size["hidden"] >= 1024
    cases = {"train": ((8, 1024, 16, 64) if big else (1, 256, 2, 64), 1),
             "blockdiff": ((1, 2048, 32, 128) if big else (1, 256, 2, 128),
                           4)}
    rng = np.random.default_rng(3)
    err = {}
    for case, (shape, block) in cases.items():
        q, k, v, w = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                      for _ in range(4))

        def both(attend):
            def run(q, k, v):
                o = attend(q, k, v, causal=True, causal_block=block)
                return (o.astype(jnp.float32)
                        * w.astype(jnp.float32)).sum(), o
            (_, o), grads = jax.jit(jax.value_and_grad(
                run, (0, 1, 2), has_aux=True))(q, k, v)
            return [np.asarray(x, np.float32) for x in (o,) + grads]

        for name, out, ref in zip(("o", "dq", "dk", "dv"),
                                  both(flash_attention_bshd),
                                  both(_sdpa_xla)):
            gap = err[f"flash.{case}.{name}"] = float(
                np.abs(out - ref).max())
            # both round f32 sums to bf16 (the XLA path its
            # probabilities too): the other kernels' 2e-2, in units of
            # the largest value where that passes 1
            if not np.isfinite(out).all() \
                    or gap > 2e-2 * max(1.0, float(np.abs(ref).max())):
                raise AssertionError(
                    f"flash kernel ({case}, {name}) off the XLA path "
                    f"by {gap}")
    # the latent-attention prefill's HEAD-MAJOR entry at the serving
    # cell's three prompt buckets (128 heads, 192 query / key lanes
    # against 128 value lanes, forward only): the same kernel on the
    # same operands as the (batch, seq, heads, dim) entry, so the same
    # values to the bit
    heads, seqs = (128, (2048, 3072, 5120)) if big else (2, (256,))
    for seq in seqs:
        q, k = (jnp.asarray(rng.normal(size=(1, heads, seq, 192)),
                            jnp.bfloat16) for _ in range(2))
        v = jnp.asarray(rng.normal(size=(1, heads, seq, 128)), jnp.bfloat16)
        out = flash_attention_bhsd(q, k, v, causal=True, scale=0.1147)
        ref = jnp.swapaxes(flash_attention_bshd(
            *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), causal=True,
            scale=0.1147), 1, 2)
        gap = err[f"flash.mla_head_major.{seq}"] = float(jnp.abs(
            out.astype(jnp.float32) - ref.astype(jnp.float32)).max())
        if out.shape != v.shape or not gap == 0.0:
            raise AssertionError(
                f"head-major flash entry (S {seq}) off the (batch, seq, "
                f"heads, dim) entry by {gap}")
    return err


def gqa_parity(size: dict) -> dict:
    """The paged body with fewer key/value heads than query heads, at
    the LFM2-MoE cell's decode shape where the size allows: 64 rows x
    256 pages, 32 query heads over 8 key/value heads of 64, ragged
    contexts (1, partly filled pages, a compute block's edge, the whole
    table), on a shuffled pool."""
    import jax.numpy as jnp
    from paddle2_tpu.serving.paged_attention import (
        paged_attention_decode, paged_attention_reference)
    H, Hkv, D, bs = 32, 8, 64, 16
    n_pages = min(256, size["seq"] // bs * 4)
    seq = n_pages * bs
    rng = np.random.default_rng(1)
    rows = 64 if size["seq"] >= 256 else 8
    ctx = rng.integers(1, seq + 1, rows)
    ctx[:6] = [1, 17, bs * 64 - 1, bs * 64, min(bs * 64 + 1, seq), seq]
    ctx = np.minimum(ctx, seq)
    n_blocks = rows * n_pages + 1
    tables = (rng.permutation(np.arange(1, n_blocks))
              .reshape(rows, n_pages).astype(np.int32))
    kp, vp = (jnp.asarray(rng.normal(size=(n_blocks, bs, Hkv * D)),
                          jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(rows, 1, H, D)), jnp.bfloat16)
    ref = np.concatenate([np.asarray(paged_attention_reference(
        q[r:r + 8], kp, vp, tables[r:r + 8], ctx[r:r + 8]), np.float32)
        for r in range(0, rows, 8)])
    out = np.asarray(paged_attention_decode(q, kp[None], vp[None], tables,
                                            ctx), np.float32)
    gap = float(np.abs(out - ref).max())
    if not np.isfinite(out).all() or gap > 2e-2:
        raise AssertionError(f"paged GQA kernel off the dense reference "
                             f"by {gap}")
    return {"gqa.single": gap}


def ring_parity(size: dict) -> dict:
    """The sliding-window layers' ring walk (``window_decode``: the paged
    single-softmax body over a slot's ring seen as pages) at the
    K-EXAONE cell's decode shape where the size allows: 128 rows, 64
    query over 8 key/value heads of 128, a window of 128, rings of 129
    slots x 2 layers; contexts of 1, the window and 9,216 (every row of
    the ring live) among ragged ones, slots shuffled."""
    import jax.numpy as jnp
    from paddle2_tpu.serving.exaone_moe_family import ring_walk
    from paddle2_tpu.serving.paged_attention import paged_attention_reference
    big = size["hidden"] >= 1024
    rows, H, Hkv, D, W = (128, 64, 8, 128, 128) if big else (8, 4, 2, 16, 16)
    rng = np.random.default_rng(3)
    ctx = rng.integers(1, 9217, rows)
    ctx[:5] = [1, 17, W - 1, W, 9216]
    live = np.minimum(ctx, W).astype(np.int32)
    slots = rng.permutation(np.arange(1, rows + 1)).astype(np.int32)
    ring_k, ring_v = (jnp.asarray(
        rng.normal(size=(2, rows + 1, W, Hkv * D)), jnp.bfloat16)
        for _ in range(2))
    q = jnp.asarray(rng.normal(size=(rows, 1, H, D)), jnp.bfloat16)
    out = np.asarray(ring_walk(q, ring_k, ring_v, 1, jnp.asarray(slots),
                               jnp.asarray(live)), np.float32)
    # the dense reference over the same rings, a slot one page of W rows
    ref = np.concatenate([np.asarray(paged_attention_reference(
        q[r:r + 8], ring_k[1], ring_v[1], slots[r:r + 8, None],
        live[r:r + 8]), np.float32) for r in range(0, rows, 8)])
    gap = float(np.abs(out - ref).max())
    if out.shape != ref.shape or not np.isfinite(out).all() or gap > 2e-2:
        raise AssertionError(f"ring walk off the dense reference by {gap}")
    return {"ring.window_decode": gap}


def long_table_parity(size: dict) -> dict:
    """The K-EXAONE cell's global layer where the size allows: a table of
    576 pages (9,216 positions) of 1,024-lane rows, past the
    single-softmax body's fit budget, so the dispatcher hands it that
    body under the raised scoped-VMEM limit; 16 rows, contexts of 1, a
    compute block's edge, past 4,096 and the whole table among ragged
    ones, on a shuffled pool."""
    import jax.numpy as jnp
    from paddle2_tpu.serving import paged_attention as pa
    big = size["hidden"] >= 1024
    H, Hkv, D, bs, n_pages, rows = (64, 8, 128, 16, 576, 16) if big \
        else (4, 2, 16, 8, 12, 8)
    if big:
        assert not pa.fits_single_softmax(n_pages, bs, D, jnp.bfloat16, None,
                                          H, Hkv)
        assert pa.kernel_pages_per_block(n_pages, bs, H, D, jnp.bfloat16,
                                         num_kv_heads=Hkv) > 1
    seq = n_pages * bs
    rng = np.random.default_rng(4)
    ctx = rng.integers(1, seq + 1, rows)
    ctx[:5] = [1, bs * 32, bs * 32 + 1, seq // 2 + 3, seq]
    ctx = np.minimum(ctx, seq)
    n_blocks = rows * n_pages + 1
    tables = (rng.permutation(np.arange(1, n_blocks))
              .reshape(rows, n_pages).astype(np.int32))
    kp, vp = (jnp.asarray(rng.normal(size=(n_blocks, bs, Hkv * D)),
                          jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(rows, 1, H, D)), jnp.bfloat16)
    ref = np.concatenate([np.asarray(pa.paged_attention_reference(
        q[r:r + 8], kp, vp, tables[r:r + 8], ctx[r:r + 8]), np.float32)
        for r in range(0, rows, 8)])
    out = np.asarray(pa.paged_attention_decode(q, kp[None], vp[None], tables,
                                               ctx), np.float32)
    gap = float(np.abs(out - ref).max())
    if not np.isfinite(out).all() or gap > 2e-2:
        raise AssertionError(f"paged kernel over a 576-page table off the "
                             f"dense reference by {gap}")
    return {"gqa.long_table": gap}


def gmm_parity(size: dict) -> dict:
    """The grouped matmul of the dropless expert layer against a plain
    loop over the experts: even loads, one expert taking everything,
    and a skewed routing that leaves experts empty; a decode step's
    rows and a prefill's thousands, at the widths and expert counts of
    the three MoE cells where the size allows (LFM2-24B-A2B: 64 experts
    of 2048 x 1536; SDAR-30B-A3B: 128 of 2048 x 768, both products;
    DeepSeek-V2: 20 held of 160 routed over, 5120 x 1536, the rest of
    the rows parked behind them) — each at the tiles the kernel picks
    there."""
    import jax.numpy as jnp
    from paddle2_tpu.kernels.moe_gmm import gmm_reference, moe_gmm
    big = size["hidden"] >= 1024
    # (name, groups routed over, held, K, N, decode rows, prefill rows)
    cells = ([("lfm2", 64, 64, 2048, 1536, 256, 4096),
              ("sdar.up", 128, 128, 2048, 768, 2048, 4096),
              ("sdar.down", 128, 128, 768, 2048, 2048, 4096),
              ("dsv2", 160, 20, 5120, 1536, 768, 12288)] if big
             else [("tiny", 8, 8, 128, 256, 256, 512),
                   ("tiny.held", 16, 4, 128, 256, 96, 512)])
    rng = np.random.default_rng(2)
    err = {}
    for cell, E, held, K, N, few, many in cells:
        rhs = jnp.asarray(rng.normal(size=(held, K, N)) * 0.05,
                          jnp.bfloat16)
        for rows in (few, many):
            # of the rows, the share routed to a held expert (the rest
            # are parked in a last group that nobody holds)
            here = rows * held // E
            skew = np.bincount(
                np.minimum(rng.geometric(0.2, here) - 1, held - 1),
                minlength=held)
            even = np.full(held, here // held)
            even[0] += here - even.sum()
            loads = {"even": even, "skewed": skew,
                     "one_expert": np.eye(held, dtype=np.int64)[held // 2]
                     * here}
            lhs = jnp.asarray(rng.normal(size=(rows, K)), jnp.bfloat16)
            for name, sizes in loads.items():
                sizes = jnp.asarray(np.append(sizes, rows - here),
                                    jnp.int32)
                out = np.asarray(moe_gmm(lhs, rhs, sizes), np.float32)
                ref = np.asarray(gmm_reference(lhs, rhs, sizes), np.float32)
                gap = err[f"gmm.{cell}.{name}.{rows}"] = float(
                    np.abs(out - ref).max())
                # both round one f32 accumulation to bf16: equal but for
                # the order of the sum (a bf16 step of the largest output)
                if not np.isfinite(out).all() or out[here:].any() \
                        or gap > 2.0 ** -7 * max(1.0,
                                                 float(np.abs(ref).max())):
                    raise AssertionError(
                        f"moe_gmm ({cell}, {name}, {rows} rows) off the "
                        f"plain loop by {gap}")
    return err


def ssm_step_parity(size: dict) -> dict:
    """The recurrent state step against its ``jnp`` formula at the two
    state-space cells' mixer shapes and row buckets where the size
    allows (Nemotron-3-Nano: 256 rows x 64 heads of ``[64, 128]`` in 8
    groups; Falcon-H1: 128 rows x 32 heads of ``[128, 256]`` in 2), over
    a shuffled pool with padded rows in slot 0. Only the chip can show
    it: the kernel puts ``dt x`` on the state's lanes as a product of
    three bfloat16 parts with ones, exact only while the compiler keeps
    the parts as they were cut (PR 42: parts rounded through ``astype``
    came out as ONE bfloat16 on the chip and equal on the CPU, ``y`` off
    by 0.06, inside what the cells' ``correct`` allows)."""
    import jax
    import jax.numpy as jnp
    from paddle2_tpu.kernels.ssd import ssm_state_step, ssm_state_step_xla
    big = size["hidden"] >= 1024
    # (name, rows, heads, groups, P, N)
    cells = ([("nemotron3n", 256, 64, 8, 64, 128),
              ("falconh1", 128, 32, 2, 128, 256)] if big
             else [("tiny", 6, 8, 4, 16, 128)])
    rng = np.random.default_rng(3)
    err = {}
    for cell, R, nh, G, P, N in cells:
        pool = jnp.asarray(rng.normal(size=(2, R + 1, nh, P, N)),
                           jnp.float32)
        slots = rng.permutation(np.arange(1, R + 1)).astype(np.int32)
        slots[-2:] = 0                                  # padded rows
        live = slots > 0
        args = (jnp.asarray(slots),
                jnp.asarray(rng.normal(size=(R, nh, P)), jnp.bfloat16),
                jnp.asarray(rng.normal(size=(R, G, N)), jnp.float32),
                jnp.asarray(rng.normal(size=(R, G, N)), jnp.float32),
                jnp.asarray(rng.uniform(0.01, 0.3, (R, nh)), jnp.float32),
                -jnp.asarray(rng.uniform(0.5, 2.0, nh), jnp.float32),
                jnp.asarray(rng.normal(size=nh), jnp.float32))
        want_pool, want_y = jax.jit(ssm_state_step_xla)(pool, 1, *args)
        got_pool, got_y = jax.jit(ssm_state_step)(pool, 1, *args)
        gap_h = float(jnp.abs(got_pool - want_pool)[:, 1:].max())
        gap_y = float(jnp.abs(got_y - want_y)[live].max())
        err[f"ssm_step.{cell}.state"] = gap_h
        err[f"ssm_step.{cell}.y"] = gap_y
        # float32 throughout: the state differs by a rounding of its
        # two products at most, y by the order of a sum over N lanes
        if not gap_h <= 1e-5 or not gap_y <= 1e-4:
            raise AssertionError(
                f"ssm_state_step ({cell}) off the jnp formula: state "
                f"{gap_h}, y {gap_y}")
    return err


def device_ms(calls: dict, repeats: int = 5, ops: dict = None) -> dict:
    """{name: median device ms of one execution} of the jitted
    ``calls`` ({name: (fn, args)}, each compiled under its name), off
    the ``XLA Modules`` line of ONE profiler trace: a piece of a few
    tens of microseconds is far under what the host's clock resolves
    around a call (~0.2 ms here). ``ops``, if given, takes {name: the
    call's eight largest ops as (ms an execution, op)} off the ``XLA
    Ops`` line."""
    import glob
    import shutil
    import tempfile
    import jax
    from jax.profiler import ProfileData
    jitted = {}
    for name, (fn, args) in calls.items():
        # a function of its own: one jitted before keeps its old name
        def named(*a, _fn=fn):
            return _fn(*a)
        named.__name__ = name
        jitted[name] = (jax.jit(named), args)
        jax.block_until_ready(jitted[name][0](*args))
    where = tempfile.mkdtemp(prefix="p2t_split_")
    try:
        jax.profiler.start_trace(where)
        for fn, args in jitted.values():
            for _ in range(repeats):
                jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(where, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        taken, spans, op_events = {}, [], []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/device:TPU:0":
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    op_events = [(e.start_ns, e.duration_ns, e.name)
                                 for e in line.events]
                if line.name != "XLA Modules":
                    continue
                for event in line.events:
                    name = event.name.split("(")[0].removeprefix("jit_")
                    taken.setdefault(name, []).append(event.duration_ns)
                    spans.append((event.start_ns, event.start_ns
                                  + event.duration_ns, name))
    finally:
        shutil.rmtree(where, ignore_errors=True)
    if ops is not None:
        by_call = {name: {} for name in calls}
        for lo, hi, name in spans:
            for start, ns, op in op_events:
                if name in by_call and lo <= start < hi:
                    by_call[name][op] = by_call[name].get(op, 0.0) \
                        + ns / 1e6 / repeats
        for name, by_op in by_call.items():
            ops[name] = sorted(((round(ms, 4), op[:80])
                                for op, ms in by_op.items()),
                               reverse=True)[:8]
    return {name: float(np.median(taken[name])) / 1e6 for name in calls}


def moe_routing_split(size: dict) -> dict:
    """What an expert layer builds around its grouped matmuls, each
    piece ALONE at the prefill and decode shapes of the three cells
    whose layers hold a share of the experts (K-EXAONE: 16 of 128 held,
    8 a row, hidden 6,144; DeepSeek-V2: 20 of 160, 6 a row, 5,120;
    Nemotron: 64 of 128, 6 a row, 2,688), the form every layer had
    before PR 47 beside the held-prefix form (PERF.md section 6, PR 47,
    step 0): the sort (``argsort`` + ``bincount``, the same in both),
    the row gather over all ``T x k`` assignments against
    ``moe._gather_held`` over the held prefix, and the combine as a
    second ``argsort`` + a gather of all result rows + a float32 sum
    over ``[T, k, H]`` against ``moe._combine_held``; the new forms are
    held to the old ones' results. Device ms of one execution; on the
    chip only."""
    import jax
    import jax.numpy as jnp
    from paddle2_tpu.incubate import moe
    big = size["hidden"] >= 1024
    # rows, experts a row, hidden, experts routed over, held
    shapes = {
        "kexaone.512": (512, 8, 6144, 128, 16),
        "kexaone.2048": (2048, 8, 6144, 128, 16),
        "kexaone.8192": (8192, 8, 6144, 128, 16),
        "dsv2.5120": (5120, 6, 5120, 160, 20),
        "nemotron.2048": (2048, 6, 2688, 128, 64),
        "kexaone.step128": (128, 8, 6144, 128, 16),
        "dsv2.step128": (128, 6, 5120, 160, 20),
        "nemotron.step256": (256, 6, 2688, 128, 64),
    } if big else {"tiny": (24, 2, 32, 8, 2)}
    f32 = jnp.float32
    out = {}
    for shape, (T, k, H, E, held_n) in shapes.items():
        chunk = math.gcd(T, moe.HELD_CHUNK)
        _, ids = jax.lax.top_k(
            jax.random.uniform(jax.random.PRNGKey(1), (T, E)), k)
        w = jax.random.uniform(jax.random.PRNGKey(4), (T, k), f32)
        a = jax.random.normal(jax.random.PRNGKey(2), (T, H), jnp.bfloat16)

        def sort(ids):
            flat = ids.reshape(-1)
            held = flat < held_n
            flat = jnp.where(held, flat, E)
            return (jnp.argsort(flat, stable=True).astype(jnp.int32),
                    jnp.bincount(flat, length=E + 1).astype(jnp.int32),
                    held.reshape(T, k))

        order, sizes, held = jax.jit(sort)(ids)
        n_held = jnp.sum(sizes[:E])
        n = int(n_held)
        # the experts' output: anything on the prefix, unwritten behind
        y = jax.random.normal(jax.random.PRNGKey(3), (T * k, H),
                              jnp.bfloat16)
        y = jnp.where((jnp.arange(T * k) < n)[:, None], y, jnp.nan)

        def gather_all(a, order):
            return a[order // k]

        def gather_held(a, order, n_held):
            return moe._gather_held(a, order, k, n_held, chunk)[0]

        def combine_all(y, order, w):
            z = y[jnp.argsort(order)].reshape(T, k, H).astype(f32)
            return jnp.sum(z * w[..., None], axis=1).astype(y.dtype)

        def combine_held(y, order, w, held):
            return moe._combine_held(y, order, w, held, chunk, y.dtype)

        rows = gather_held(a, order, n_held)
        if not (rows[:n] == gather_all(a, order)[:n]).all():
            raise AssertionError(f"held gather ({shape}) off the whole one")
        got, trips = combine_held(y, order, w, held)
        want = combine_all(jnp.where(jnp.isnan(y), 0, y), order, w)
        gap = out[f"moe_split.{shape}.combine_gap"] = float(
            jnp.abs(got.astype(f32) - want.astype(f32)).max())
        # one float32 sum in another order, rounded to bfloat16 once
        if not gap <= 2.0 ** -7 * max(1.0, float(jnp.abs(want).max())):
            raise AssertionError(
                f"held combine ({shape}) off the whole one by {gap}")
        out[f"moe_split.{shape}.held"] = n
        out[f"moe_split.{shape}.rows_moved_pct"] = 100.0 * (
            (-(-n // chunk) + int(trips)) * chunk + T) / (2 * T * k)
        if not big:
            continue
        tag = shape.replace(".", "_")
        times = device_ms({
            f"p47_sort_{tag}": (sort, (ids,)),
            f"p47_gather_all_{tag}": (gather_all, (a, order)),
            f"p47_gather_held_{tag}": (gather_held, (a, order, n_held)),
            f"p47_combine_all_{tag}": (combine_all, (y, order, w)),
            f"p47_combine_held_{tag}": (combine_held, (y, order, w, held)),
        })
        for name, ms in times.items():
            piece = name.removeprefix("p47_").removesuffix("_" + tag)
            out[f"moe_split.{shape}.ms.{piece}"] = ms
    return out


def window_band_split(size: dict) -> dict:
    """A sliding-window layer's attention at prefill, at the K-EXAONE
    cell's three buckets (64 query over 8 key/value heads of 128, a
    window of 128, hidden 6,144; PERF.md section 6, PR 48, step 0): the
    band ALONE as the einsums (``local_window_attention``, with its
    largest ops) beside the kernel (``window_fwd``) on q, k, v given;
    then ONE sublayer (``ExaoneMoeAttention.full``: projections, q/k
    norm, rotation, the band, the output projection; parameters as
    arguments) in the parent's form (q normed and rotated as plain XLA,
    the einsums) beside the form a TPU takes (q normed and rotated in the
    kernel), which is held to the parent's output. Device ms of one
    execution; on the chip only."""
    import jax
    import jax.numpy as jnp
    from paddle2_tpu.kernels import pallas_band
    from paddle2_tpu.models import exaone_moe as em
    big = size["hidden"] >= 1024
    bf16, f32 = jnp.bfloat16, jnp.float32
    window = 128 if big else 8
    cfg = em.ExaoneMoeConfig(dtype="bfloat16", num_hidden_layers=4) \
        if big else em.exaone_moe_tiny(dtype="bfloat16")
    layer = em.ExaoneMoeAttention(cfg, window)
    params = list(layer.parameters())
    weights = [0.02 * jax.random.normal(jax.random.PRNGKey(i),
                                        p._data.shape, f32).astype(bf16)
               if p._data.ndim > 1 else jnp.ones(p._data.shape, bf16)
               for i, p in enumerate(params)]

    def sublayer(attend):
        def run(weights, u):
            kept = [p._data for p in params]
            for p, w in zip(params, weights):
                p._data = w
            chosen = em.sliding_attention_kernel
            em.sliding_attention_kernel = attend
            try:
                return layer.full(u)[0]
            finally:
                em.sliding_attention_kernel = chosen
                for p, w in zip(params, kept):
                    p._data = w
        return run

    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    out = {}
    for S in (512, 2048, 8192) if big else (24,):
        keys = jax.random.split(jax.random.PRNGKey(S), 4)
        q = jax.random.normal(keys[0], (1, S, nh, hd), bf16)
        k, v = (jax.random.normal(key, (1, S, nkv, hd), bf16)
                for key in keys[1:3])
        u = jax.random.normal(keys[3], (1, S, cfg.hidden_size), bf16)

        def einsums(q, k, v):
            return em.local_window_attention(q, k, v, window)

        def kernel(q, k, v):
            return pallas_band.band_attention(q, k, v, window, hd)

        # the kernel's layout: the heads side by side in the lanes
        flat = tuple(x.reshape(1, S, -1) for x in (q, k, v))
        gap = out[f"band.{S}.kernel_gap"] = float(jnp.abs(
            kernel(*flat).reshape(q.shape).astype(f32)
            - einsums(q, k, v).astype(f32)).max())
        parent, fused = (sublayer(em.sliding_attention),
                         sublayer(em.sliding_attention_kernel))
        want = jax.jit(parent)(weights, u).astype(f32)
        layer_gap = out[f"band.{S}.sublayer_gap"] = float(jnp.abs(
            jax.jit(fused)(weights, u).astype(f32) - want).max())
        # bfloat16 probabilities and outputs on O(1) values; the
        # sublayer's output is a sum over 8,192 of them times 0.02
        if gap > 2e-2 or layer_gap > 2e-2 * float(jnp.abs(want).max()):
            raise AssertionError(
                f"window_fwd off the einsums at {S}: {gap}, {layer_gap}")
        if not big:
            continue
        pieces = {"band_einsums": (einsums, (q, k, v)),
                  "band_kernel": (kernel, flat),
                  "sublayer_parent": (parent, (weights, u)),
                  "sublayer_kernel": (fused, (weights, u))}
        ops = {}
        times = device_ms({f"p48_{piece}_{S}": call
                           for piece, call in pieces.items()}, ops=ops)
        for piece in pieces:
            out[f"band.{S}.ms.{piece}"] = times[f"p48_{piece}_{S}"]
        out[f"band.{S}.einsums_largest_ops"] = ops[f"p48_band_einsums_{S}"]
    return out


def bf16_ulp(x: float) -> float:
    """Spacing of bf16 values (8 significand bits) at magnitude |x|."""
    return 2.0 ** (math.floor(math.log2(abs(x))) - 7)


def reference_logits(model, seq: int, prompts, streams):
    """Per request, the f32 logits ``[len(stream), V]`` that predict
    each served token, from ONE full causal forward of the model over
    prompt + stream — every request right-padded into one batch
    (causal masking hides the padding from every real position)."""
    import paddle2_tpu as paddle
    ids = np.zeros((len(prompts), seq), np.int32)
    for row, (p, g) in enumerate(zip(prompts, streams)):
        ids[row, :len(p) + len(g)] = p + g
    with paddle.no_grad():
        logits = model(paddle.to_tensor(ids))._data
    return [np.asarray(logits[row, len(p) - 1:len(p) - 1 + len(g)],
                       np.float32)
            for row, (p, g) in enumerate(zip(prompts, streams))]


def margins_ulps(ref, stream):
    """How far below the reference maximum each token's reference
    logit lies, in bf16 steps of that maximum."""
    return [float((row.max() - row[tok]) / bf16_ulp(row.max()))
            for row, tok in zip(ref, stream)]


def check_streams(refs, streams, generated_by_model) -> dict:
    """The served streams against the reference logits, the shuffled
    control, and ``model.generate``. Raises on any failure."""
    worst, equal, ties = 0.0, 0, []
    for i, (ref, got, gen) in enumerate(
            zip(refs, streams, generated_by_model)):
        m = margins_ulps(ref, got)
        if max(m) > TIE_ULPS:
            raise AssertionError(
                f"request {i}: served token {got[int(np.argmax(m))]} at "
                f"step {int(np.argmax(m))} lies {max(m):.1f} bf16 steps "
                f"below the reference maximum (tolerance {TIE_ULPS}): "
                f"stream {got}, margins {m}")
        worst = max(worst, max(m))
        # vs model.generate: equal up to the first split, and the split
        # itself a tie in the reference (both tokens within tolerance)
        split = next((k for k, (a, b) in enumerate(zip(got, gen))
                      if a != b), len(got))
        equal += split
        if split < len(got):
            gap = margins_ulps(ref[split:split + 1], [gen[split]])[0]
            if gap > TIE_ULPS:
                raise AssertionError(
                    f"request {i} step {split}: engine {got[split]} vs "
                    f"generate {gen[split]}, which lies {gap:.1f} bf16 "
                    f"steps below the reference maximum — not a tie")
            ties.append({"request": i, "step": split,
                         "engine": got[split], "generate": gen[split],
                         "engine_below_max_ulps": round(m[split], 2),
                         "generate_below_max_ulps": round(gap, 2)})
    if len({tuple(g) for g in streams}) != len(streams):
        raise AssertionError(
            f"served streams do not depend on the prompt: {streams}")
    # control: each stream against the NEXT request's reference must be
    # rejected — the same check, shown able to fail on this model
    for i, got in enumerate(streams):
        other = refs[(i + 1) % len(refs)]
        if max(margins_ulps(other, got)) <= TIE_ULPS:
            raise AssertionError(
                f"control: stream {i} passes against request "
                f"{(i + 1) % len(refs)}'s reference — the check cannot "
                f"tell prompts apart")
    return {"tokens_checked": sum(map(len, streams)),
            "tolerance_bf16_ulps": TIE_ULPS,
            "worst_below_reference_max_ulps": round(worst, 2),
            "control_rejected": True,
            "tokens_equal_generate": equal,
            "ties_where_generate_parts": ties}


def phase_server(size: dict, prompt_lens, new_tokens: int,
                 num_blocks: int, tally: CacheTally,
                 on_chip: bool) -> None:
    import jax
    import paddle2_tpu as paddle
    from paddle2_tpu import inference
    from paddle2_tpu.models import GPTConfig, GPTForCausalLM
    dev = jax.devices()[0]
    parity = kernel_parity(size)
    cfg = GPTConfig(vocab_size=size["vocab"], hidden_size=size["hidden"],
                    num_layers=size["layers"], num_heads=size["heads"],
                    max_position_embeddings=size["seq"],
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_scan=False)
    paddle.seed(1)
    model = GPTForCausalLM(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    model.eval()
    path = os.path.join(OUT_DIR, "artifact", "gpt")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    paddle.jit.save(model, path)

    conf = inference.Config(path)
    conf.enable_continuous_batching(
        block_size=16, num_blocks=num_blocks, max_batch=8,
        max_model_len=size["seq"], kv_dtype="bfloat16",
        prefill_budget_tokens=size["seq"])
    engine = conf.create_serving_engine(gpt_config=cfg)
    if engine.config.interpret is not None:
        raise AssertionError("EngineConfig.interpret must stay None")

    rs = np.random.RandomState(2)
    prompts = [rs.randint(1, size["vocab"], n).tolist()
               for n in prompt_lens]
    t0 = time.perf_counter()
    rids = [engine.submit(p, max_new_tokens=new_tokens) for p in prompts]
    ticks = 0
    while not engine.idle():
        engine.tick(now=time.perf_counter() - t0)
        ticks += 1
        if ticks > 100 * new_tokens:
            raise AssertionError("engine did not drain")
    serve_s = time.perf_counter() - t0
    cache = tally.take()

    streams = [list(engine.sequence(rid).generated) for rid in rids]
    if any(len(g) != new_tokens for g in streams):
        raise AssertionError(f"short streams: {streams}")
    by_generate = [
        np.asarray(model.generate(
            np.asarray([p], np.int32), max_new_tokens=new_tokens,
            temperature=0.0)._data)[0, len(p):].tolist()
        for p in prompts]
    verdict = check_streams(
        reference_logits(model, size["seq"], prompts, streams),
        streams, by_generate)
    pool_gib = 2 * engine.cache.k.size * engine.cache.k.dtype.itemsize \
        / 2 ** 30
    emit("server", device=device_record(), config=size,
         paged_kernel_compiled=on_chip,
         paged_kernel_max_abs_err_vs_reference=parity,
         kv_pool_blocks=num_blocks, kv_pool_gib=round(pool_gib, 2),
         kv_pool_shape=list(engine.cache.k.shape),
         prompt_lens=list(prompt_lens), new_tokens_each=new_tokens,
         served=streams, **verdict,
         ticks=ticks, decode_steps=engine.decode_steps,
         decode_programs=engine.runner.num_decode_programs,
         serve_wall_s_with_compiles=round(serve_s, 2),
         peak_bytes_in_use=peak_bytes(dev) if on_chip else None,
         **cache)


# ----------------------------------------------------------- four chips
def phase_four_chips(size: dict, tally: CacheTally,
                     on_chip: bool) -> None:
    """dp2 x mp2 over the four chips vs the same step on one chip."""
    import jax
    import paddle2_tpu as paddle
    from paddle2_tpu.distributed.spec_layout import hybrid_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    devs = jax.devices()
    if len(devs) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, found "
                             f"{len(devs)}")
    rs = np.random.RandomState(0)
    batches = [skewed_batch(rs, size["batch"], size["seq"], size["vocab"])
               for _ in range(STEPS)]

    # the comparison: one chip, no mesh
    model, optimizer, step = build_trainer(size)
    ref_losses, ref_secs = run_steps(step, batches, paddle.to_tensor)
    check_losses(ref_losses, size["vocab"])
    del model, optimizer, step
    gc.collect()
    tally.take()

    # the mesh a user of hybrid parallelism builds; its runtime flags
    # went into LIBTPU_INIT_ARGS before the backend started (main)
    mesh, _ = hybrid_mesh(dp=2, tp=2)
    model, optimizer, step = build_trainer(size, tensor_parallel=True)
    step.collect_cost = True
    batch_sharding = NamedSharding(mesh, P("dp", None))

    def to_sharded(ids):
        return paddle.Tensor(jax.device_put(ids, batch_sharding))

    losses, secs = run_steps(step, batches, to_sharded)
    cache = tally.take()
    check_losses(losses, size["vocab"])
    # __graft_entry__.dryrun_multichip's tolerances for sharded parity:
    # rtol 2e-3 on the loss of a step from IDENTICAL parameters (step
    # 1 here), rtol 2e-2 once an update has been applied (bf16 rounding
    # differences between the two reduction orders pass through AdamW,
    # so the trajectories separate at that level)
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]
    if rel[0] > 2e-3 or max(rel) > 2e-2:
        raise AssertionError(
            f"sharded losses {losses} vs one chip {ref_losses}")
    text, mem = compiled_step_text(step)
    if on_chip and text.count("tpu_custom_call") == 0:
        raise AssertionError("no tpu_custom_call in the sharded step")
    collectives = {k: text.count(k) for k in
                   ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")}
    # a column-parallel weight (fused qkv) and the batch, per device
    qkv = next(p for n, p in model.named_parameters()
               if n.endswith("qkv__weight"))
    ids = to_sharded(batches[0])._data
    per_dev = []
    for d in devs:
        w = next(s for s in qkv._data.addressable_shards
                 if s.device == d)
        b = next(s for s in ids.addressable_shards if s.device == d)
        per_dev.append({
            "device": d.id, "qkv_shard": list(w.data.shape),
            "batch_shard": list(b.data.shape),
            "bytes_in_use": (int(d.memory_stats()["bytes_in_use"])
                             if on_chip else None),
            "peak_bytes_in_use": peak_bytes(d) if on_chip else None})
    if int(np.prod(per_dev[0]["qkv_shard"])) * 2 != qkv._data.size:
        raise AssertionError(f"qkv not split over mp: {per_dev}")
    if per_dev[0]["batch_shard"][0] * 2 != size["batch"]:
        raise AssertionError(f"batch not split over dp: {per_dev}")
    if on_chip:
        floor = min(r["peak_bytes_in_use"] for r in per_dev)
        if floor < 0.25 * per_dev[0]["peak_bytes_in_use"]:
            raise AssertionError(
                f"work not spread over chips: {per_dev}")
    emit("four_chips", device=device_record(), config=size,
         mesh=dict(mesh.shape), losses_sharded=losses,
         losses_one_chip=ref_losses,
         rel_loss_diff=[round(r, 6) for r in rel],
         compile_plus_first_step_s=round(secs[0], 2),
         steady_step_s=[round(s, 4) for s in secs[2:]],
         one_chip_steady_step_s=[round(s, 4) for s in ref_secs[2:]],
         qkv_global_shape=list(qkv._data.shape),
         tpu_custom_calls_in_compiled_step=text.count("tpu_custom_call"),
         collectives_in_compiled_step=collectives,
         compiled_temp_bytes_per_device=int(mem.temp_size_in_bytes),
         per_device=per_dev, **cache)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    if args.four_chips and not args.cpu_rehearsal:
        # launcher-style: the multichip runtime flags must be in the
        # environment BEFORE the backend starts (hybrid_mesh applies
        # them too, but the one-chip comparison runs first)
        from paddle2_tpu.flags import apply_multichip_xla_env
        apply_multichip_xla_env(platform="tpu")
    import jax
    dev = device_record()
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if dev["platform"] != want:
        print(f"chip_smoke: needs a {want} device, JAX reports "
              f"{dev['platform']!r}", file=sys.stderr)
        return 2
    import paddle2_tpu  # noqa: F401  (resolves the compile cache)
    from paddle2_tpu.flags import compile_cache_dir
    from paddle2_tpu.io.native import build as ring_build
    tally = CacheTally()
    os.makedirs(OUT_DIR, exist_ok=True)
    ring_was_there = os.path.exists(ring_build._LIB)
    ring_build.load_shm_ring()
    emit("start", device=dev, jax=jax.__version__,
         compile_cache_dir=compile_cache_dir(),
         compile_cache_from_env=bool(
             os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         libtpu_init_args=os.environ.get("LIBTPU_INIT_ARGS", ""),
         libshmring_built_now=not ring_was_there)

    on_chip = not args.cpu_rehearsal
    size = FULL if on_chip else TINY
    if args.four_chips:
        phase_four_chips(size, tally, on_chip)
    else:
        phase_trainer(size, tally, expect_kernel=on_chip)
        gc.collect()        # the trainer's 5.4 GB of state goes
        if on_chip:
            # 4096 blocks x 1.5 MiB: a 6 GiB pool (65k tokens of KV at
            # 24 layers) next to 0.7 GB of weights
            phase_server(size, (13, 40, 200, 520), 8, 4096, tally,
                         on_chip=True)
        else:
            phase_server(size, (5, 13, 20, 40), 6, 64, tally,
                         on_chip=False)
    print(json.dumps({"ok": True, "device": device_record()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
