"""Serving subsystem: paged KV cache + paged-attention kernel,
continuous-batching scheduler, ServingEngine, deterministic sim, and
the PR's inference/metrics satellites (ISSUE 9)."""

import functools
import os
import threading

import numpy as np
import pytest

import paddle2_tpu as paddle
import jax
import jax.numpy as jnp

from paddle2_tpu.serving import (
    BlockAllocator, BlockTable, EngineConfig, GARBAGE_BLOCK,
    OutOfBlocksError, PagedKVCache, Request, SchedulerConfig, Sequence,
    SeqState, ServingEngine, ContinuousBatchingScheduler,
    blocks_for_tokens, paged_attention_decode, paged_attention_reference,
    poisson_trace, simulate_predictor_baseline, simulate_serving)
from paddle2_tpu.serving import paged_attention as pa
from paddle2_tpu.serving.simulate import cost_seconds


# --------------------------------------------------------- paged attention
def _fragmented_setup(rng, bs, ctx_lens, H, D, num_blocks=32):
    """Pools + deliberately NON-CONTIGUOUS (shuffled) block tables, with
    finite stale garbage in every unused slot to prove masking."""
    B = len(ctx_lens)
    n_pages = max(blocks_for_tokens(c, bs) for c in ctx_lens)
    perm = rng.permutation(np.arange(1, num_blocks))
    tables = np.zeros((B, n_pages), np.int32)
    kp = (rng.normal(size=(num_blocks, bs, H, D)) * 7).astype(np.float32)
    vp = (rng.normal(size=(num_blocks, bs, H, D)) * 7).astype(np.float32)
    dense_k, dense_v = [], []
    used = 0
    for b, c in enumerate(ctx_lens):
        nb = blocks_for_tokens(c, bs)
        blks = perm[used:used + nb]
        used += nb
        tables[b, :nb] = blks
        ks = rng.normal(size=(c, H, D)).astype(np.float32)
        vs = rng.normal(size=(c, H, D)).astype(np.float32)
        dense_k.append(ks)
        dense_v.append(vs)
        for i, blk in enumerate(blks):
            lo, hi = i * bs, min(c, (i + 1) * bs)
            kp[blk, :hi - lo] = ks[lo:hi]
            vp[blk, :hi - lo] = vs[lo:hi]
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    # the pools merge a token's heads into one row: [N, bs, H*D]
    return (q, kp.reshape(num_blocks, bs, H * D),
            vp.reshape(num_blocks, bs, H * D), tables, dense_k, dense_v)


# kernel vs dense reference, fp32: the kernel reduces per page and then
# across pages ([P, 8, bs] scratch, lane-group dots — the layout the
# chip's compiler accepts) where the reference reduces one [1, S] row —
# the same op sequence under a different summation order, so agreement
# is a few ulp, not bitwise (ROADMAP D8(c))
KERNEL_TOL = dict(rtol=2e-6, atol=2e-6)
BF16_TOL = dict(rtol=2e-2, atol=2e-2)


def _pool_blocks(ctx_lens, bs):
    """A pool that holds these contexts, the garbage block and a few
    blocks nobody names."""
    return sum(blocks_for_tokens(c, bs) for c in ctx_lens) + 9


def _decode_vs_reference(q, kp, vp, tables, ctx, dtype="float32"):
    q, kp, vp = (jnp.asarray(x, dtype) for x in (q, kp, vp))
    out = paged_attention_decode(q, kp[None], vp[None], tables,
                                 np.asarray(ctx))
    ref = paged_attention_reference(q, kp, vp, tables, np.asarray(ctx))
    assert out.dtype == q.dtype
    assert np.isfinite(np.asarray(out, np.float32)).all()
    tol = KERNEL_TOL if dtype == "float32" else BF16_TOL
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)
    return out


@pytest.mark.parametrize("bs", [16, 64])
def test_paged_decode_matches_reference_fragmented(bs):
    """ACCEPTANCE: kernel output == dense reference to KERNEL_TOL
    (fp32) across block sizes {16, 64}, ragged context lengths, and
    fragmented (non-contiguous, shuffled) block tables."""
    rng = np.random.default_rng(0)
    ctx = [24, 8, 72]                       # ragged, 8-row-aligned
    q, kp, vp, tables, _, _ = _fragmented_setup(rng, bs, ctx, H=2, D=16)
    _decode_vs_reference(q, kp, vp, tables, ctx)


# the three ways a token's merged H*D row meets the 128-lane tile: two
# heads a tile (the serving cell's widths), one head a tile, and a
# whole row narrower than a tile
HEAD_SHAPES = {"h16xd64": (16, 64), "h2xd128": (2, 128), "h2xd16": (2, 16)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bs", [8, 16, 64])
@pytest.mark.parametrize("shape", sorted(HEAD_SHAPES))
def test_paged_decode_head_shapes_block_sizes_dtypes(shape, bs, dtype):
    """Every shape the one body takes: a context of 600 (which at the
    cell's widths spans several compute blocks, the last one partly
    dead), a context of 1 and a ragged one, on a shuffled pool."""
    H, D = HEAD_SHAPES[shape]
    ctx = [600, 1, 37]
    rng = np.random.default_rng(5)
    q, kp, vp, tables, _, _ = _fragmented_setup(
        rng, bs, ctx, H=H, D=D, num_blocks=_pool_blocks(ctx, bs))
    _decode_vs_reference(q, kp, vp, tables, ctx, dtype)


@pytest.mark.parametrize("block_bytes", [None, 1])
def test_paged_decode_spans_compute_blocks_ragged_tail(block_bytes,
                                                       monkeypatch):
    """The gather walks compute blocks of ``_pages_per_block`` pages:
    contexts that end inside a block's last page (700), exactly on a
    block's edge (512), one key (1), and one block and a bit (300) —
    at the cell's widths with the block size the code picks (16 pages
    of float32), and with the smallest lane-dense block (8 pages), so
    that a row walks up to six of them."""
    if block_bytes is not None:
        monkeypatch.setattr(pa, "_BLOCK_TARGET_BYTES", block_bytes)
    bs, H, D = 16, 16, 64
    ctx = [700, 1, 512, 300]
    n_pages = blocks_for_tokens(max(ctx), bs)
    ppb = pa._pages_per_block(n_pages, bs, H * D, "float32")
    assert ppb == (16 if block_bytes is None else 8)
    assert n_pages > 2 * ppb and (700 // bs) % ppb  # ragged last block
    rng = np.random.default_rng(6)
    q, kp, vp, tables, _, _ = _fragmented_setup(
        rng, bs, ctx, H=H, D=D, num_blocks=_pool_blocks(ctx, bs))
    _decode_vs_reference(q, kp, vp, tables, ctx)


@pytest.mark.parametrize("pps", [None, 3])
def test_paged_decode_never_reads_dead_pages(pps):
    """Poison every block that no live page names — the garbage block
    and every table entry past a row's context included — with NaN:
    the output is finite and bitwise what the clean pool gives, for
    the single-softmax body and for split-K."""
    bs, H, D = 16, 16, 64
    ctx = [300, 1, 37, 600]
    rng = np.random.default_rng(7)
    q, kp, vp, tables, _, _ = _fragmented_setup(
        rng, bs, ctx, H=H, D=D, num_blocks=_pool_blocks(ctx, bs))
    live = {int(tables[b, j]) for b, c in enumerate(ctx)
            for j in range(blocks_for_tokens(c, bs))}
    assert GARBAGE_BLOCK not in live
    dead = [n for n in range(kp.shape[0]) if n not in live]
    kbad, vbad = kp.copy(), vp.copy()
    kbad[dead] = np.nan
    vbad[dead] = np.nan

    def run(k, v):
        return np.asarray(paged_attention_decode(
            jnp.asarray(q), jnp.asarray(k)[None], jnp.asarray(v)[None],
            tables, np.asarray(ctx), pages_per_split=pps))
    clean, poisoned = run(kp, vp), run(kbad, vbad)
    assert np.isfinite(poisoned).all()
    assert np.array_equal(clean, poisoned)


@pytest.mark.parametrize("bs", [16, 64])
def test_paged_reference_bitwise_vs_flash_attention(bs):
    """The dense reference == a JITTED nn.functional.flash_attention
    on the contiguously gathered K/V, bitwise in fp32 at block-aligned
    contexts (equal reduction widths), per (seq, head) slice — an
    H-batched gemm may legally reassociate (1-ulp), so the proof
    slices to H=1 where both sides collapse to the same 2-D dot."""
    from paddle2_tpu.framework.tensor import Tensor
    from paddle2_tpu.nn.functional.flash_attention import flash_attention

    @functools.lru_cache(maxsize=None)
    def flash_jit(c, D):
        def f(q, k, v):
            out, _ = flash_attention(Tensor(q), Tensor(k), Tensor(v),
                                     causal=True)
            return out._data
        return jax.jit(f)

    rng = np.random.default_rng(1)
    H, D = 2, 16
    for c in (bs, 2 * bs):                  # block-aligned contexts
        q, kp, vp, tables, dense_k, dense_v = _fragmented_setup(
            rng, bs, [c], H=H, D=D)
        ref = np.asarray(paged_attention_reference(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), tables,
            np.asarray([c])))
        for h in range(H):
            fa = np.asarray(flash_jit(c, D)(
                jnp.asarray(q[:, :, h:h + 1]),
                jnp.asarray(dense_k[0][None, :, h:h + 1]),
                jnp.asarray(dense_v[0][None, :, h:h + 1])))
            assert np.array_equal(fa, ref[:, :, h:h + 1])


def test_paged_reference_allclose_vs_flash_ragged():
    """Ragged (non-block-aligned) contexts: padded-width reductions may
    regroup vs the exact-width dense path — 1-ulp class, so allclose
    at tight tolerance."""
    from paddle2_tpu.framework.tensor import Tensor
    from paddle2_tpu.nn.functional.flash_attention import flash_attention
    rng = np.random.default_rng(2)
    bs, H, D = 16, 2, 16
    ctx = [24, 40]
    q, kp, vp, tables, dense_k, dense_v = _fragmented_setup(
        rng, bs, ctx, H=H, D=D)
    ref = np.asarray(paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), tables,
        np.asarray(ctx)))
    fn = jax.jit(lambda q, k, v: flash_attention(
        Tensor(q), Tensor(k), Tensor(v), causal=True)[0]._data)
    for b, c in enumerate(ctx):
        fa = np.asarray(fn(jnp.asarray(q[b:b + 1]),
                           jnp.asarray(dense_k[b][None]),
                           jnp.asarray(dense_v[b][None])))
        np.testing.assert_allclose(fa, ref[b:b + 1], rtol=2e-6, atol=2e-6)


# ---- copies by run of consecutive pages: a compute block of 16 pages
# read in aligned groups of 8 table entries (the plan's byte targets
# set to so many of the case's pages), a table of 40 pages
RUN_PPB, RUN_PPC, RUN_PAGES = 16, 8, 40
# name -> (query positions, query heads, key/value heads)
RUN_HEADS = {"multi_head": (1, 2, 2), "group_of_4": (1, 8, 2),
             "group_of_5": (1, 5, 1), "group_of_16": (1, 16, 1),
             "block_of_4": (4, 2, 1)}


def _run_tables():
    """name -> (rows' tables, contexts, pages fetched as runs by hand).
    Ids start at 1 (0 is the garbage block); ``scattered`` ids step by
    2, so no page follows its neighbour."""
    P, bs = RUN_PAGES, 16
    up = np.arange(P)
    scattered = 200 + 2 * np.random.default_rng(8).permutation(P)

    def broken(at):
        return np.where(up < at, 100 + up, 150 + up)

    def rows(*tables):
        return np.stack(tables).astype(np.int32)
    return {
        "one_ascending_run": (rows(1 + up), [P * bs], 40),
        "fully_scattered": (rows(scattered), [P * bs - 5], 0),
        "run_broken_inside_a_group": (rows(broken(3)), [P * bs], 32),
        "run_broken_at_a_groups_edge": (rows(broken(RUN_PPC)), [P * bs], 40),
        "run_broken_at_a_blocks_edge": (rows(broken(RUN_PPB)), [P * bs - 1],
                                        40),
        "descending_run": (rows(60 - up), [P * bs], 0),
        # 19 live pages: two whole groups, then three pages of a run
        "context_ends_inside_a_run": (rows(1 + up), [18 * bs + 12], 16),
        "empty_row_between_live_rows": (
            rows(1 + up, np.zeros(P), scattered), [25 * bs, 0, 37], 24),
    }


@functools.lru_cache(maxsize=None)
def _run_case_outputs(heads: str, small_pool: bool):
    """Every table of :func:`_run_tables` as rows of ONE call (a small
    pool: its own call over the tables' first 8 pages), float32 ->
    (kernel output, reference, tables, contexts, pages a copy, row span
    of each case)."""
    from unittest import mock
    Q, H, Hkv = RUN_HEADS[heads]
    bs, D = 16, 16
    page = bs * Hkv * D * 4
    cases = _run_tables()
    tables = np.concatenate([t for t, _, _ in cases.values()])
    ctx = np.asarray(sum((c for _, c, _ in cases.values()), []), np.int32)
    span, at = {}, 0
    for name, (t, _, _) in cases.items():
        span[name] = slice(at, at + len(t))
        at += len(t)
    num_blocks = 300
    if small_pool:
        # fewer blocks than two copies: ids folded into 1..11
        num_blocks = 12
        tables = (tables[:, :RUN_PPC] % 11 + 1).astype(np.int32)
        ctx = np.minimum(ctx, RUN_PPC * bs)
    rng = np.random.default_rng(9)
    kp, vp = (jnp.asarray(rng.normal(size=(num_blocks, bs, Hkv * D)),
                          jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(len(ctx), Q, H, D)), jnp.float32)
    with mock.patch.object(pa, "_BLOCK_TARGET_BYTES", RUN_PPB * page), \
            mock.patch.object(pa, "_COPY_BYTES", RUN_PPC * page):
        ppb, ppc = pa._decode_plan(tables.shape[1], bs, Hkv * D, "float32",
                                   num_blocks)
        assert (ppb, ppc) == ((RUN_PPC, 1) if small_pool
                              else (RUN_PPB, RUN_PPC))
        assert pa.kernel_pages_per_copy(
            tables.shape[1], bs, Q * H, D, "float32", None, Hkv,
            num_blocks) == ppc
        out = np.asarray(paged_attention_decode(q, kp[None], vp[None],
                                                tables, ctx))
    # every position of a row sees the same context
    ref = np.stack([np.asarray(paged_attention_reference(
        q[:, p:p + 1], kp, vp, tables, ctx))[:, 0] for p in range(Q)], 1)
    return out, ref, tables, ctx, (ppb, ppc), span


def _pages_started(tables, ctx, ppb, ppc):
    """What the kernel's own start routine fetches for these rows, run
    eagerly on the host: ``(row, block page, pool page, pages)`` a
    copy."""
    class Copy:
        def __init__(self, log, *what):
            self.start = lambda: log.append(what)
    bs = 16
    pad = -tables.shape[1] % ppb
    bt = np.pad(tables, ((0, 0), (0, pad)))
    runs = np.asarray(pa._page_runs(bt, ppc)).astype(np.int32)
    log = []
    with jax.disable_jit():
        for row, c in enumerate(ctx):
            live = min(blocks_for_tokens(int(c), bs), tables.shape[1])
            for i in range(-(-live // ppb)):
                pa._start_block(
                    bt, runs, row, i, min(live - i * ppb, ppb), ppb, ppc,
                    lambda blk, at, pages, row=row, i=i: [Copy(
                        log, row, i * ppb + int(at), int(blk), pages)])
    return log


@pytest.mark.parametrize("heads", sorted(RUN_HEADS))
@pytest.mark.parametrize("case", sorted(_run_tables()) + ["small_pool"])
def test_paged_decode_copies_runs_of_consecutive_pages(case, heads):
    """ISSUE 43: a compute block's live pages arrive a RUN of
    consecutive page ids a copy where the table holds one in an aligned
    group, a page a copy elsewhere — on every kind of table the output
    is the dense reference's to KERNEL_TOL (float32), a row without a
    key reads 0, the pages the start routine fetches are the table's
    live pages, each once, and those it fetches as runs are what
    ``coalesced_pages`` counts on the host."""
    small = case == "small_pool"
    out, ref, tables, ctx, (ppb, ppc), span = _run_case_outputs(heads,
                                                                small)
    rows = slice(None) if small else span[case]
    assert np.isfinite(out[rows]).all()
    keyed = ctx[rows] > 0
    np.testing.assert_allclose(out[rows][keyed], ref[rows][keyed],
                               **KERNEL_TOL)
    assert not out[rows][~keyed].any()
    started = _pages_started(tables[rows], ctx[rows], ppb, ppc)
    live = [blocks_for_tokens(int(c), 16) for c in ctx[rows]]
    fetched = sorted((row, at + j, blk + j)
                     for row, at, blk, pages in started
                     for j in range(pages))
    assert fetched == [(row, j, int(tables[rows][row, j]))
                       for row, n in enumerate(live) for j in range(n)]
    as_runs = sum(pages for _, _, _, pages in started if pages > 1)
    assert as_runs == pa.coalesced_pages(tables[rows], live, ppc)
    assert as_runs == (0 if small else _run_tables()[case][2])


def test_paged_decode_bf16_allclose():
    rng = np.random.default_rng(3)
    bs, B, H, D = 16, 2, 2, 16
    ctx = [24, 40]
    tables = np.asarray([[2, 5, 0], [7, 3, 9]], np.int32)
    kp = jnp.asarray(rng.normal(size=(16, bs, H * D)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(16, bs, H * D)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.bfloat16)
    out = paged_attention_decode(q, kp[None], vp[None], tables,
                                 np.asarray(ctx))
    ref = paged_attention_reference(q, kp, vp, tables, np.asarray(ctx))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_paged_decode_ignores_physical_placement():
    """Same K/V values, two different physical layouts -> bitwise
    identical output (the definition of a correct gather)."""
    rng = np.random.default_rng(4)
    bs, H, D, c = 16, 2, 8, 48
    ks = rng.normal(size=(c, H, D)).astype(np.float32)
    vs = rng.normal(size=(c, H, D)).astype(np.float32)
    q = rng.normal(size=(1, 1, H, D)).astype(np.float32)
    outs = []
    for blocks in ([1, 2, 3], [9, 4, 7]):
        kp = np.zeros((12, bs, H * D), np.float32)
        vp = np.zeros((12, bs, H * D), np.float32)
        for i, blk in enumerate(blocks):
            kp[blk] = ks[i * bs:(i + 1) * bs].reshape(bs, H * D)
            vp[blk] = vs[i * bs:(i + 1) * bs].reshape(bs, H * D)
        outs.append(np.asarray(paged_attention_decode(
            jnp.asarray(q), jnp.asarray(kp)[None], jnp.asarray(vp)[None],
            np.asarray([blocks], np.int32), np.asarray([c]))))
    assert np.array_equal(outs[0], outs[1])


# ------------------------------------------------------------ block cache
def test_allocator_free_list_and_high_water():
    a = BlockAllocator(num_blocks=8, block_size=16)
    assert a.free_count == 7                # block 0 reserved
    b1 = a.allocate(3)
    assert GARBAGE_BLOCK not in b1
    b2 = a.allocate(2)
    assert a.high_water == 5
    a.free(b1)
    assert a.free_count == 5
    assert a.high_water == 5                # sticky peak
    with pytest.raises(OutOfBlocksError):
        a.allocate(6)
    with pytest.raises(ValueError):
        a.free(b1)                          # double free
    with pytest.raises(ValueError):
        a.free([0])                         # reserved block


def test_block_table_append_and_padding():
    a = BlockAllocator(num_blocks=16, block_size=4)
    t = BlockTable(a)
    slots = [t.append_slot() for _ in range(6)]
    assert t.num_tokens == 6 and len(t.blocks) == 2
    assert slots[0] == (t.blocks[0], 0)
    assert slots[4] == (t.blocks[1], 0)
    row = t.padded(5)
    assert list(row[:2]) == t.blocks
    assert all(row[2:] == GARBAGE_BLOCK)
    t.release()
    assert t.num_tokens == 0 and a.used_count == 0


def test_paged_cache_scatter_gather_roundtrip():
    cache = PagedKVCache(num_layers=2, num_blocks=8, block_size=4,
                         num_kv_heads=2, head_dim=4)
    rng = np.random.default_rng(0)
    kv = jnp.asarray(rng.normal(size=(2, 7, 2, 4)), jnp.float32)
    row = np.asarray([3, 5], np.int64)
    pool = PagedKVCache.scatter_prefill(cache.k, kv, row, 7, 4)
    dense = PagedKVCache.gather_dense(pool[0], row, 2)
    assert np.array_equal(np.asarray(dense[:7]),
                          np.asarray(kv[0]).reshape(7, 8))


# -------------------------------------------------------------- scheduler
def _mk_seq(alloc, rid, prompt_len, max_new=4, arrival=0.0):
    return Sequence(Request(rid, list(range(1, prompt_len + 1)),
                            max_new, arrival), alloc)


def test_scheduler_admit_fifo_and_budget():
    alloc = BlockAllocator(num_blocks=64, block_size=4)
    sched = ContinuousBatchingScheduler(SchedulerConfig(
        max_batch=4, batch_buckets=(1, 2, 4), page_buckets=(2, 4, 8),
        prefill_budget_tokens=10), alloc)
    for i, n in enumerate([4, 4, 6]):
        sched.submit(_mk_seq(alloc, i, n))
    first = sched.admit()
    # 4 + 4 = 8 fits the 10-token budget; adding the 6-token prompt
    # would exceed it, so request 2 waits for the next round
    assert [s.req_id for s in first] == [0, 1]
    assert [s.req_id for s in sched.admit()] == [2]


def test_scheduler_admit_respects_batch_and_blocks():
    alloc = BlockAllocator(num_blocks=5, block_size=4)   # 4 usable
    sched = ContinuousBatchingScheduler(SchedulerConfig(
        max_batch=2, batch_buckets=(1, 2), page_buckets=(2, 4),
        prefill_budget_tokens=0), alloc)
    sched.submit(_mk_seq(alloc, 0, 6))      # needs 2 blocks (7 tokens)
    sched.submit(_mk_seq(alloc, 1, 6))
    sched.submit(_mk_seq(alloc, 2, 6))
    admitted = sched.admit()
    # 2 fit the batch but the allocator only covers both (2+2 blocks);
    # the third is held by max_batch, then by blocks
    assert [s.req_id for s in admitted] == [0, 1]
    for s in admitted:
        sched.mark_running(s)
    assert sched.admit() == []              # batch full
    sched.finish(admitted[0])
    # finishing released a batch slot AND 2 blocks -> req 2 admits
    assert [s.req_id for s in sched.admit()] == [2]


def test_scheduler_evicts_lifo_and_requeues_front():
    alloc = BlockAllocator(num_blocks=5, block_size=4)   # 4 usable
    sched = ContinuousBatchingScheduler(SchedulerConfig(
        max_batch=4, batch_buckets=(1, 2, 4), page_buckets=(1, 2, 4),
        prefill_budget_tokens=0), alloc)
    a, b = _mk_seq(alloc, 0, 7, max_new=8), _mk_seq(alloc, 1, 7, max_new=8)
    for s in (a, b):
        sched.submit(s)
    for s in sched.admit():
        s.table.num_tokens = 7
        sched.mark_running(s)
    assert alloc.free_count == 0
    # next decode token for seq a crosses a block boundary -> needs a
    # 3rd block -> exhaustion -> the NEWEST running seq (b) is evicted
    a.table.num_tokens = 8
    b.table.num_tokens = 8
    victims = sched.reserve_decode_slots()
    assert victims == [b]
    assert b.state is SeqState.WAITING and b.evictions == 1
    assert b.num_cached == 0 and not b.table.blocks
    assert sched.waiting[0] is b            # requeued at the FRONT
    assert a.state is SeqState.RUNNING
    assert len(a.table.blocks) == 3


def test_scheduler_bucket_shapes():
    cfg = SchedulerConfig(max_batch=8, batch_buckets=(1, 2, 4, 8),
                          page_buckets=(2, 4, 8))
    assert cfg.batch_bucket(3) == 4
    assert cfg.page_bucket(5) == 8
    assert cfg.program_budget == 12
    with pytest.raises(ValueError):
        cfg.page_bucket(9)
    with pytest.raises(ValueError):
        SchedulerConfig(max_batch=8, batch_buckets=(1, 2))


# ------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def tiny_model():
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(0)
    return GPTForCausalLM(gpt_tiny(use_scan=False))


def _engine(model, **over):
    kw = dict(block_size=8, num_blocks=32, max_batch=4,
              prefill_budget_tokens=64, max_model_len=64)
    kw.update(over)
    return ServingEngine(model, config=EngineConfig(**kw))


def _drain(eng, max_steps=300):
    steps = 0
    while not eng.idle() and steps < max_steps:
        eng.tick(now=float(steps))
        steps += 1
    assert eng.idle(), "engine did not drain"


def test_engine_matches_generate_greedy(tiny_model):
    eng = _engine(tiny_model)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, tiny_model.cfg.vocab_size, size=12).tolist()
    rid = eng.submit(prompt, max_new_tokens=4)
    _drain(eng)
    ref = tiny_model.generate(np.asarray(prompt, np.int32)[None],
                              max_new_tokens=4, temperature=0.0)
    ref = np.asarray(ref.numpy())[0][len(prompt):].tolist()
    assert eng.sequence(rid).generated == ref


def test_engine_eviction_exactness(tiny_model):
    """ACCEPTANCE: block exhaustion -> eviction -> requeue ->
    re-prefill, with final tokens identical to an uncontended run."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, tiny_model.cfg.vocab_size,
                            size=14).tolist() for _ in range(4)]

    def run(num_blocks):
        eng = _engine(tiny_model, num_blocks=num_blocks)
        rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
        _drain(eng)
        return eng, rids

    eng_big, rids_big = run(64)
    eng_tight, rids_tight = run(10)         # 9 usable blocks
    assert eng_tight.scheduler.total_evictions >= 1
    for a, b in zip(rids_big, rids_tight):
        assert (eng_big.sequence(a).generated
                == eng_tight.sequence(b).generated)


def test_engine_program_count_bounded(tiny_model):
    """ACCEPTANCE: compiled decode programs <= the fixed bucket count
    across shifting batch compositions (no per-composition recompile).
    """
    eng = _engine(tiny_model)
    rng = np.random.default_rng(5)
    for wave in ([6, 10], [8], [5, 7, 9]):  # varying compositions
        for n in wave:
            eng.submit(rng.integers(0, tiny_model.cfg.vocab_size,
                                    size=n).tolist(), max_new_tokens=4)
        _drain(eng)
    assert eng.num_decode_programs <= eng.program_budget
    # same bucket, different composition: the dict can't grow past the
    # grid even in principle
    assert set(eng.runner._decode_programs) <= {
        (b, p) for b in eng.scheduler.config.batch_buckets
        for p in eng.scheduler.config.page_buckets}


def test_engine_weight_only_int8(tiny_model):
    """Opt-in int8 weight-only quantization: projections swapped, the
    engine still serves, embeddings/head untouched."""
    import copy
    from paddle2_tpu.quantization import WeightOnlyLinear
    model = copy.deepcopy(tiny_model)
    eng = _engine(model, weight_only_int8=True)
    blk = model.gpt.h[0]
    assert isinstance(blk.attn.qkv, WeightOnlyLinear)
    assert isinstance(blk.mlp.up, WeightOnlyLinear)
    assert not isinstance(model.gpt.wte, WeightOnlyLinear)
    rid = eng.submit([3, 1, 4, 1, 5, 9, 2, 6], max_new_tokens=4)
    _drain(eng)
    gen = eng.sequence(rid).generated
    assert len(gen) == 4
    assert all(0 <= t < model.cfg.vocab_size for t in gen)


def test_engine_from_jit_save_artifact(tiny_model, tmp_path):
    """ServingEngine wraps a jit.save'd GPT artifact: weights round-
    trip into the rebuilt architecture and serving output matches the
    live-model engine."""
    from paddle2_tpu.jit.api import save
    from paddle2_tpu.models.gpt import gpt_tiny
    path = str(tmp_path / "gpt_artifact")
    save(tiny_model, path)                  # weights-only artifact
    eng = ServingEngine(
        artifact_path=path, gpt_config=gpt_tiny(use_scan=False),
        config=EngineConfig(block_size=8, num_blocks=32, max_batch=4,
                            max_model_len=64))
    live = _engine(tiny_model)
    prompt = [7, 8, 9, 10, 11, 12]
    r1 = eng.submit(prompt, max_new_tokens=4)
    r2 = live.submit(prompt, max_new_tokens=4)
    _drain(eng)
    _drain(live)
    assert eng.sequence(r1).generated == live.sequence(r2).generated
    # the Config route honors an explicit params file exactly like
    # create_predictor does (weights moved away from the prefix)
    from paddle2_tpu import inference
    moved = str(tmp_path / "weights_moved.bin")
    os.rename(path + ".pdiparams", moved)
    cfg = inference.Config()
    cfg.set_model(path + ".pdmodel", moved)
    cfg.enable_continuous_batching(block_size=8, num_blocks=32,
                                   max_batch=4, max_model_len=64)
    eng2 = cfg.create_serving_engine(gpt_config=gpt_tiny(use_scan=False))
    r3 = eng2.submit(prompt, max_new_tokens=4)
    _drain(eng2)
    assert eng2.sequence(r3).generated == live.sequence(r2).generated


def test_engine_serves_artifact_in_its_saved_dtypes(tmp_path):
    """A bf16 (amp O2) artifact is served in bf16 — not widened back to
    the rebuilt architecture's f32 defaults — with a bf16 KV pool, and
    still matches generate token-for-token."""
    from paddle2_tpu import inference
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(3)
    cfg = gpt_tiny(use_scan=False)
    model = paddle.amp.decorate(GPTForCausalLM(cfg), level="O2",
                                dtype="bfloat16")
    model.eval()
    path = str(tmp_path / "bf16_artifact")
    paddle.jit.save(model, path)
    conf = inference.Config(path)
    conf.enable_continuous_batching(block_size=8, num_blocks=32,
                                    max_batch=4, max_model_len=64,
                                    kv_dtype="bfloat16")
    eng = conf.create_serving_engine(gpt_config=cfg)
    want = {n: str(p._data.dtype) for n, p in model.named_parameters()}
    got = {n: str(p._data.dtype) for n, p in eng.model.named_parameters()}
    assert got == want and "bfloat16" in set(got.values())
    assert eng.cache.k.dtype == jnp.bfloat16
    prompt = list(range(3, 16))              # decode crosses a page
    rid = eng.submit(prompt, max_new_tokens=6)
    _drain(eng)
    ref = np.asarray(model.generate(
        np.asarray([prompt], np.int32), max_new_tokens=6,
        temperature=0.0)._data)[0, len(prompt):].tolist()
    assert eng.sequence(rid).generated == ref


def test_engine_rejects_stacked_blocks():
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    model = GPTForCausalLM(gpt_tiny(stacked_blocks=True))
    with pytest.raises(ValueError, match="stacked_blocks"):
        ServingEngine(model, config=EngineConfig())


# ------------------------------------------------- simulation + the gates
def test_sim_deterministic_and_disaggregated(tiny_model):
    trace = poisson_trace(6, rate_per_s=500.0, prompt_lens=[10, 14],
                          gen_tokens=[4, 6],
                          vocab=tiny_model.cfg.vocab_size, seed=11)
    reps = [simulate_serving(_engine(tiny_model), trace)
            for _ in range(2)]
    assert reps[0].tokens_per_s == reps[1].tokens_per_s
    assert reps[0].p99_ttft_s == reps[1].p99_ttft_s
    assert reps[0].total_tokens == 6 * 5    # mean gen = 5
    assert reps[0].kv_ratio <= 0.55


def test_sim_prefill_lane_does_not_starve_decode(tiny_model):
    """ACCEPTANCE (disaggregation): a huge prefill landing mid-stream
    must not stall the decode batch — running sequences keep producing
    a token per decode step while the prefill lane chews."""
    eng = _engine(tiny_model, prefill_budget_tokens=64)
    # request 0: long generation, admitted first
    r0 = eng.submit([1, 2, 3, 4, 5, 6, 7, 8], max_new_tokens=12)
    eng.admit_and_prefill(now=0.0)
    # request 1: a LONG prompt arrives; its prefill occupies the lane
    # far into the future
    r1 = eng.submit(list(range(1, 49)), max_new_tokens=2)
    eng.admit_and_prefill(now=0.0,
                          ready_at_fn=lambda info: 1e6)  # lane busy
    # decode steps keep running for r0 even though r1's prefill is
    # "in flight" on the lane
    produced = 0
    now = 0.0
    for _ in range(12):
        step = eng.decode_once(now=now)
        if step is None:
            break
        assert step["n_active"] == 1        # r1 never joins (held)
        produced += step["tokens"]
        now += 1e-3
    assert produced == 11                   # 12 total - 1 from prefill
    assert eng.sequence(r0).done
    assert not eng.sequence(r1).done        # still held by the lane


@pytest.mark.slow
def test_sim_beats_predictor_baseline(tiny_model):
    """Smoke-scale version of the bench's 3x gate: under saturating
    load, continuous batching beats one-at-a-time on the same trace
    and the same cost primitives. Marked slow — CI's serving-smoke
    job enforces the full gate via bench.py --serving."""
    probe = _engine(tiny_model)
    tr0 = poisson_trace(2, 100.0, [10], [4],
                        tiny_model.cfg.vocab_size, seed=1)
    simulate_serving(probe, tr0)
    b1 = min(probe.runner._decode_costs)
    decode_s = cost_seconds(probe.runner.decode_cost(b1))
    rate_req = 5.0 / decode_s / 6.0         # ~5x b1 token capacity
    trace = poisson_trace(16, rate_req, [10, 14], [4, 8],
                          tiny_model.cfg.vocab_size, seed=13)
    eng = _engine(tiny_model)
    rep = simulate_serving(eng, trace)
    base = simulate_predictor_baseline(eng, trace)
    assert rep.tokens_per_s > 1.5 * base.tokens_per_s
    assert rep.decode_programs <= rep.program_budget


# ----------------------------------------------------- metrics satellites
def test_serving_reports_tokens_explicitly(tiny_model, tmp_path):
    """Serving decode steps write step records with EXPLICIT token
    counts — never inferred from arg shapes (the engine's programs
    consume int32 block tables that a shape sniffer could misread)."""
    from paddle2_tpu.observability import metrics
    metrics.enable(str(tmp_path), rank=0, flush_steps=1)
    try:
        eng = _engine(tiny_model)
        eng.submit([5, 6, 7, 8, 9, 10], max_new_tokens=3)
        eng.submit([1, 2, 3, 4, 5, 6], max_new_tokens=3)
        _drain(eng)
        metrics.flush()
    finally:
        metrics.disable()
    import json
    recs = [json.loads(l) for l in
            open(os.path.join(str(tmp_path), "metrics_rank_0.jsonl"))]
    steps = [r for r in recs if r.get("type") == "step"
             and r.get("serving")]
    assert steps
    # explicit per-step token counts == active sequences, and the
    # deterministic modeled cost rides along for perf_doctor
    assert all(r["tokens"] == round(r["batch_occupancy"] * 4)
               for r in steps)
    assert all("modeled_step_s" in r for r in steps)
    snap = [r for r in recs if r.get("type") == "metrics"][-1]
    assert snap["counters"]["serving_decode_tokens_total"][""] == \
        sum(r["tokens"] for r in steps)


def test_train_step_token_heuristic_rejects_int8(tmp_path):
    """SATELLITE: an int8 2-D first arg (quantized KV / payload) must
    never be counted as tokens by the train-step heuristic; int32 ids
    still are."""
    from types import SimpleNamespace
    import json
    from paddle2_tpu.jit.train_step import TrainStepProgram
    from paddle2_tpu.observability.metrics import MetricsPlane
    fake = SimpleNamespace(_compiled={}, _scaler=None)
    pl = MetricsPlane(str(tmp_path), rank=0, flush_steps=10_000)
    int8_kv = np.zeros((4, 32), np.int8)
    TrainStepProgram._note_step_metrics(fake, pl, [int8_kv], False)
    ids32 = np.zeros((4, 32), np.int32)
    TrainStepProgram._note_step_metrics(fake, pl, [ids32], False)
    recs = [json.loads(l) for l in pl._buffer
            if '"type": "step"' in l]
    assert len(recs) == 2
    assert "tokens" not in recs[0]          # int8: NOT tokens
    assert recs[0]["samples"] == 4
    assert recs[1]["tokens"] == 4 * 32      # int32 ids: tokens


# --------------------------------------------------- inference satellites
def _save_tiny_artifact(tmp_path, name="m"):
    from paddle2_tpu import nn
    from paddle2_tpu.jit.api import InputSpec, save
    paddle.seed(1)
    layer = nn.Linear(4, 3)
    path = str(tmp_path / name)
    save(layer, path, input_spec=[InputSpec([None, 4], "float32")])
    return layer, path


def test_config_set_model_honors_params_file(tmp_path):
    """SATELLITE regression: the explicit params_file argument was
    accepted but ignored (prefix-derived path always won)."""
    from paddle2_tpu import inference
    layer, path = _save_tiny_artifact(tmp_path)
    moved = str(tmp_path / "weights_elsewhere.bin")
    os.rename(path + ".pdiparams", moved)
    cfg = inference.Config()
    cfg.set_model(path + ".pdmodel", moved)
    assert cfg.params_file() == moved
    pred = inference.create_predictor(cfg)
    x = np.ones((2, 4), np.float32)
    out = pred.run([x])[0]
    ref = layer(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-6)
    # constructor path honors it too
    cfg2 = inference.Config(path + ".pdmodel", moved)
    assert cfg2.params_file() == moved
    inference.create_predictor(cfg2)
    # prefix fallback still intact
    cfg3 = inference.Config()
    cfg3.set_model(path + ".pdmodel")
    assert cfg3.params_file() == path + ".pdiparams"


def test_predictor_pool_concurrent_handout(tmp_path):
    """SATELLITE: PredictorPool acquire/release is thread-safe."""
    from paddle2_tpu import inference
    layer, path = _save_tiny_artifact(tmp_path, "pool")
    pool = inference.PredictorPool(inference.Config(path), size=3)
    x = np.ones((1, 4), np.float32)
    ref = np.asarray(layer(paddle.to_tensor(x)).numpy())
    errors = []
    seen = set()
    mu = threading.Lock()

    def worker():
        try:
            for _ in range(5):
                p = pool.acquire(timeout=10.0)
                with mu:
                    seen.add(id(p))
                out = p.run([x])[0]
                np.testing.assert_allclose(out, ref, rtol=1e-5)
                pool.release(p)
        except Exception as e:              # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(pool._free) == 3             # every slot returned
    p = pool.acquire()
    pool.release(p)
    with pytest.raises(ValueError):
        pool.release(p)                     # double release
    assert pool.retrieve(0) is pool._preds[0]


def test_config_enable_continuous_batching_flag():
    from paddle2_tpu import inference
    cfg = inference.Config("some/model")
    assert not cfg.continuous_batching_enabled()
    cfg.enable_continuous_batching(block_size=16, max_batch=8)
    assert cfg.continuous_batching_enabled()


def test_config_create_serving_engine_requires_enable():
    from paddle2_tpu import inference
    with pytest.raises(ValueError, match="enable_continuous_batching"):
        inference.Config("x").create_serving_engine(gpt_config=None)
