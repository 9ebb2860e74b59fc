"""SDAR-MoE served: the logits of every denoise pass and commit through the
paged cache against the reference's forward over [clean ; noisy] (moved
from ``test_sdar.py``, which states the tolerances; harness: ``served.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle2_tpu.distributed.fault_tolerance import chaos
from paddle2_tpu.serving import blockdiff
from paddle2_tpu.serving.block_cache import GARBAGE_BLOCK, audit_kv_ledger
from served import (LOGIT_TOL, NEVER, build_as_read,  # noqa: F401
                    shared_programs, tiny_engine)
from served import sdar_bench as bench

VOCAB = 503
pytestmark = pytest.mark.usefixtures("shared_programs")


# ------------------------------------------- passes through the paged cache
TAPPED = []                      # the passes' logits of the test in hand
unmask = blockdiff.unmask_low_confidence


def tapped_unmask(logits, ids, masked, n_fix):
    jax.debug.callback(lambda lg: TAPPED.append(np.asarray(lg)), logits,
                       ordered=True)
    return unmask(logits, ids, masked, n_fix)


@pytest.fixture
def logit_tap(monkeypatch):
    """Every pass's logits ``[rows, B, V]`` as the decode program's
    ``unmask`` is handed them, in call order. The engine is held to
    reading every step back in the call that enqueued it — by its own
    rule: an armed drop hook (which never fires here) — so that a call
    of ``decode_once`` pairs with the logits of the step it ran. One
    wrapper and one list for every test (``served.shared_programs``)."""
    monkeypatch.setattr(chaos, "_ACTIVE", chaos.ChaosInjector(NEVER))
    monkeypatch.setattr(blockdiff, "unmask_low_confidence", tapped_unmask)
    del TAPPED[:]
    yield TAPPED
    del TAPPED[:]


def serve(engine, prompts, max_new, store):
    """Drive the engine to idle; {request id: {index of a pass in the
    request's record: its logits [B, V]}} and the request ids. (A block
    that an eviction throws away leaves the record, and the passes that
    recompute it take its indices.)"""
    rids = [engine.submit(p, max_new) for p in prompts]
    rows = {r: {} for r in rids}
    now = 0.0
    while not engine.idle():
        now += 1.0
        engine.admit_and_prefill(now)
        active = [s for s in engine.scheduler.running()
                  if getattr(s, "ready_at", 0.0) <= now]
        before = engine.scheduler.total_evictions
        if engine.decode_once(now):
            jax.effects_barrier()
            lg = store.pop(0)
            # an eviction inside the step drops rows from the END of
            # the running list (LIFO victims)
            gone = engine.scheduler.total_evictions - before
            for i, s in enumerate(active[:len(active) - gone]):
                rows[s.req_id][len(s.passes) - 1] = lg[i]
    assert not store
    return rids, rows


def passes_against_reference(bench, cfg, params, engine, rid, rows):
    """Every pass in the record of request ``rid`` against the
    reference's forward over [clean ; noisy], once per pass index;
    returns the widest logit difference."""
    ref, B = bench["ref"], cfg["block_length"]
    seq = engine.sequence(rid)
    first = len(seq.request.prompt) // B * B
    blocks = {}         # block start -> [(ids after the pass, its logits)]
    for i, (start, row, _, _) in enumerate(engine.block_passes(rid)):
        blocks.setdefault(start, []).append((row, rows[i]))
    starts = sorted(blocks)
    clean = list(seq.request.prompt[:first])
    for start in starts:
        clean += blocks[start][-1][0].tolist()      # the commit's ids
    pos, sees = ref.clean_noisy(len(clean), first, B)
    worst = 0.0
    for j in range(max(len(v) for v in blocks.values())):
        # the state every block is in BEFORE its pass j (a block with
        # fewer passes is fed its final ids and not compared)
        noisy = []
        for start in starts:
            passes = blocks[start]
            if j == 0:
                state = np.full(B, -1)
                left = seq.request.prompt[start:start + B]
                state[:len(left)] = left
            else:
                state = passes[min(j, len(passes)) - 1][0]
            noisy += np.where(state < 0, cfg["mask_token_id"],
                              state).tolist()
        want = np.asarray(ref.forward(
            params, jnp.asarray([clean + noisy], jnp.int32), cfg, mask=sees,
            positions=jnp.asarray(pos), head_from=len(clean))[0][0])
        for b, start in enumerate(starts):
            if j < len(blocks[start]):
                worst = max(worst, float(np.abs(
                    blocks[start][j][1] - want[b * B:(b + 1) * B]).max()))
    return worst


def check_passes(bench, cfg, params, engine, rids, rows):
    for rid in rids:
        assert sorted(rows[rid]) == list(range(len(
            engine.block_passes(rid))))
        worst = passes_against_reference(bench, cfg, params, engine, rid,
                                         rows[rid])
        assert worst <= LOGIT_TOL, (rid, worst)


def test_prefill_passes_and_commit_match_reference(bench, logit_tap):
    """Prompts with every remainder mod B, three sequences in a batch,
    S = 2: the logits of every denoise pass and of the commit, every
    block, against the reference's forward over [clean ; noisy]."""
    model, cfg, params = build_as_read(bench, 5)
    engine = tiny_engine(model, denoising_steps=2)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (5, 22, 36, 7)]
    rids, rows = serve(engine, prompts, 9, logit_tap)
    check_passes(bench, cfg, params, engine, rids, rows)
    assert engine.allocator.used_count == 0
    audit_kv_ledger(engine.allocator, [])


def test_prefix_hit_passes_match_reference(bench, logit_tap):
    """A prefix hit shares whole pages (block_size is a multiple of B, so
    a page holds whole blocks and depends on nothing behind it)."""
    model, cfg, params = build_as_read(bench, 10)
    engine = tiny_engine(model, enable_prefix_cache=True,
                         denoising_steps=4)
    rng = np.random.default_rng(10)
    shared = rng.integers(1, VOCAB, 24).tolist()
    prompts = [shared + rng.integers(1, VOCAB, n).tolist() for n in (3, 6)]
    rids, rows = serve(engine, prompts[:1], 6, logit_tap)
    rids2, rows2 = serve(engine, prompts[1:], 6, logit_tap)
    assert engine.sequence(rids2[0]).prefix_cached_tokens >= 16
    check_passes(bench, cfg, params, engine, rids + rids2,
                 {**rows, **rows2})


def test_eviction_passes_match_reference(bench, logit_tap):
    """A pool too small for the batch: a sequence is evicted inside a
    block and re-prefilled from its committed log; every pass kept,
    before and after, still matches."""
    model, cfg, params = build_as_read(bench, 7)
    engine = tiny_engine(model, num_blocks=12, max_batch=3,
                         denoising_steps=2)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (19, 23, 26)]
    rids, rows = serve(engine, prompts, 13, logit_tap)
    assert engine.scheduler.total_evictions > 0
    check_passes(bench, cfg, params, engine, rids, rows)


def test_skipped_commit_is_seen(bench, logit_tap, monkeypatch):
    """The damaged engine: a commit pass whose keys and values never
    reach the block's page (the row's page entry points at the garbage
    block), so the cache keeps what the LAST DENOISE pass wrote — the
    comparison of the passes' logits fails it."""
    model, cfg, params = build_as_read(bench, 5)
    engine = tiny_engine(model, denoising_steps=2)
    build_step = engine._build_block_step

    def no_commit(*a):
        step = build_step(*a)
        meta, tables = step.arrays
        for i in range(len(step.active)):
            if meta[i, 1] == 0:
                tables[i, meta[i, 2] // 8] = GARBAGE_BLOCK
        return step

    monkeypatch.setattr(engine, "_build_block_step", no_commit)
    prompt = np.random.default_rng(5).integers(1, VOCAB, 12).tolist()
    rids, rows = serve(engine, [prompt], 12, logit_tap)
    worst = passes_against_reference(bench, cfg, params, engine, rids[0],
                                     rows[rids[0]])
    assert worst > 100 * LOGIT_TOL
