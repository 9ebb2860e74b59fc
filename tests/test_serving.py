"""Serving subsystem: paged KV cache + paged-attention kernel,
continuous-batching scheduler (ISSUE 9). The ServingEngine, the
deterministic sim and the inference/metrics satellites:
``test_serving_engine.py``."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle2_tpu.serving import (BlockAllocator, BlockTable, GARBAGE_BLOCK,
                                 OutOfBlocksError, PagedKVCache, Request,
                                 SchedulerConfig, Sequence, SeqState,
                                 ContinuousBatchingScheduler,
                                 blocks_for_tokens, paged_attention_decode,
                                 paged_attention_reference)
from paddle2_tpu.serving import paged_attention as pa
from served import KERNEL_TOL, fragmented_setup as _fragmented_setup


# --------------------------------------------------------- paged attention
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
