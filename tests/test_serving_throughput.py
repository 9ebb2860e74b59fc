"""Serving throughput next tier (ISSUE 14): online-softmax/split-K
flash-decode kernel, copy-on-write prefix caching, speculative
decoding — plus the refcounted-allocator edges, doctor lanes, and the
int4 weight-only satellite."""

import numpy as np
import pytest
import jax.numpy as jnp

import paddle2_tpu as paddle
from paddle2_tpu.serving import (BlockAllocator, BlockTable, EngineConfig,
                                 GARBAGE_BLOCK, PagedKVCache, PrefixCache,
                                 SpeculativeConfig, ServingEngine,
                                 accept_drafts, blocks_for_tokens, ngram_draft,
                                 paged_attention_decode,
                                 paged_attention_reference,
                                 paged_attention_split_reference,
                                 simulate_serving)
from paddle2_tpu.serving import paged_attention as pa
from paddle2_tpu.serving.block_cache import BlockFreeError
from served import KERNEL_TOL, fragmented_setup as _fragmented_setup
from served import shared_programs  # noqa: F401

pytestmark = pytest.mark.usefixtures("shared_programs")


# ------------------------------------------- split-K flash-decode kernel
@pytest.mark.parametrize("pps", [1, 2, 3])
def test_split_kernel_matches_mirrored_reference(pps):
    """ACCEPTANCE: the split-K body == the dense reference that
    mirrors its op sequence, to KERNEL_TOL (fp32 — per-page then
    across-page reductions vs the reference's one-row reductions),
    across split widths, ragged contexts, and fragmented tables."""
    rng = np.random.default_rng(0)
    bs, H, D = 16, 2, 16
    ctx = [24, 8, 72]
    q, kp, vp, tables, _, _ = _fragmented_setup(rng, bs, ctx, H=H, D=D)
    out = paged_attention_decode(jnp.asarray(q), jnp.asarray(kp)[None],
                                 jnp.asarray(vp)[None], tables,
                                 np.asarray(ctx), pages_per_split=pps)
    ref = paged_attention_split_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), tables,
        np.asarray(ctx), pages_per_split=pps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **KERNEL_TOL)
    assert np.isfinite(np.asarray(out)).all()


def test_split_kernel_allclose_vs_global_reference():
    """The split body's per-split rescaling legally reassociates the
    softmax reductions — 1-ulp class vs the PR 9 global-softmax
    reference, never more."""
    rng = np.random.default_rng(1)
    bs, H, D = 16, 2, 16
    ctx = [48, 72]
    q, kp, vp, tables, _, _ = _fragmented_setup(rng, bs, ctx, H=H, D=D)
    out = paged_attention_decode(jnp.asarray(q), jnp.asarray(kp)[None],
                                 jnp.asarray(vp)[None], tables,
                                 np.asarray(ctx), pages_per_split=2)
    ref = paged_attention_reference(jnp.asarray(q), jnp.asarray(kp),
                                    jnp.asarray(vp), tables,
                                    np.asarray(ctx))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


def test_split_dispatch_default_is_single_softmax():
    """pages_per_split=None at a short context dispatches the
    single-split global-softmax body: bitwise the forced-single call,
    and within KERNEL_TOL of the global-softmax reference."""
    rng = np.random.default_rng(2)
    bs, H, D = 16, 2, 16
    ctx = [24, 40]
    q, kp, vp, tables, _, _ = _fragmented_setup(rng, bs, ctx, H=H, D=D)
    auto = paged_attention_decode(jnp.asarray(q), jnp.asarray(kp)[None],
                                  jnp.asarray(vp)[None], tables,
                                  np.asarray(ctx))
    forced_single = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kp)[None], jnp.asarray(vp)[None],
        tables,
        np.asarray(ctx), pages_per_split=10_000)
    ref = paged_attention_reference(jnp.asarray(q), jnp.asarray(kp),
                                    jnp.asarray(vp), tables,
                                    np.asarray(ctx))
    assert np.array_equal(np.asarray(auto), np.asarray(forced_single))
    np.testing.assert_allclose(np.asarray(auto), np.asarray(ref),
                               **KERNEL_TOL)


def test_split_kernel_bf16_allclose():
    rng = np.random.default_rng(3)
    bs, H, D = 16, 2, 16
    ctx = [24, 72]
    q, kp, vp, tables, _, _ = _fragmented_setup(rng, bs, ctx, H=H, D=D)
    qb, kb, vb = (jnp.asarray(q, jnp.bfloat16),
                  jnp.asarray(kp, jnp.bfloat16),
                  jnp.asarray(vp, jnp.bfloat16))
    out = paged_attention_decode(qb, kb[None], vb[None], tables,
                                 np.asarray(ctx), pages_per_split=2)
    ref = paged_attention_split_reference(qb, kb, vb, tables,
                                          np.asarray(ctx),
                                          pages_per_split=2)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_vmem_accounting_32k_gate():
    """The feasibility split the bench gates on: the PR 9 body's
    whole-context scratch blows the budget at 32k/D128, the auto
    split width fits, and the modeled latency sits on the KV-read
    roofline."""
    n_pages_32k = blocks_for_tokens(32768, 16)
    assert not pa.fits_single_softmax(n_pages_32k, 16, 128, "bfloat16")
    pps = pa.auto_pages_per_split(n_pages_32k, 16, 128, "bfloat16")
    assert pps < n_pages_32k
    assert pa.fits_single_softmax(pps, 16, 128, "bfloat16")
    m = pa.modeled_decode_latency_s(32768, num_heads=16, head_dim=128,
                                    dtype="bfloat16",
                                    pages_per_split=pps,
                                    peak_flops=197e12, hbm_bps=819e9)
    assert m["feasible"] and m["n_splits"] > 1
    assert m["latency_s"] <= 1.25 * m["kv_bytes"] / 819e9
    m_old = pa.modeled_decode_latency_s(32768, num_heads=16,
                                        head_dim=128, dtype="bfloat16",
                                        peak_flops=197e12,
                                        hbm_bps=819e9)
    assert not m_old["feasible"]
    # short contexts stay comfortably single-split
    assert pa.fits_single_softmax(blocks_for_tokens(2048, 16), 16, 128,
                                  "float32")


def test_kernel_pages_per_block_follows_the_dispatch():
    """The count the engine writes on ``decode.dispatch``: the compute
    block of the single-softmax body — from the shapes alone: a
    lane-dense multiple of ``128 / block_size`` pages, about a MiB of K
    a block — and 1 wherever the call goes to split-K, whose body still
    gathers one page per grid step."""
    ppb = pa.kernel_pages_per_block
    # the serving cell: 32 KB pages of bf16, 64 KB of float32
    assert ppb(64, 16, 16, 64, "bfloat16") == 32
    assert ppb(64, 16, 16, 64, "float32") == 16
    # never wider than the table, never under one 128-lane score row
    assert ppb(3, 16, 2, 16, "float32") == 8
    assert ppb(16, 4, 2, 16, "float32") == 32
    assert ppb(40, 64, 2, 16, "float32") == 40
    # forced and automatic split-K
    assert ppb(64, 16, 16, 64, "bfloat16", pages_per_split=4) == 1
    assert not pa.fits_single_softmax(2048, 16, 64, "bfloat16", None, 16)
    assert ppb(2048, 16, 16, 64, "bfloat16") == 1


# --------------------------------------------- refcounted allocator edges
def test_allocator_share_free_refcounts():
    a = BlockAllocator(num_blocks=8, block_size=16)
    blocks = a.allocate(2)
    assert a.total_allocated == 2
    a.share(blocks)
    assert all(a.refcount(b) == 2 for b in blocks)
    a.free(blocks)                      # drops one ref, frees nothing
    assert a.free_count == 5 and all(a.refcount(b) == 1
                                     for b in blocks)
    a.free(blocks)                      # last ref: back to free list
    assert a.free_count == 7
    with pytest.raises(BlockFreeError):
        a.free(blocks)                  # double free still typed
    with pytest.raises(BlockFreeError):
        a.share([blocks[0]])            # share of a free block
    with pytest.raises(BlockFreeError):
        a.share([GARBAGE_BLOCK])


def test_double_fork_then_interleaved_release():
    """Two forks off one parent, released in interleaved order: every
    shared block survives until its LAST owner lets go, and the pool
    drains to exactly full."""
    a = BlockAllocator(num_blocks=12, block_size=4)
    parent = BlockTable(a)
    for _ in range(10):                 # 2 full blocks + 2-token tail
        parent.append_slot()
    f1, copy1 = parent.fork()
    f2, copy2 = parent.fork()
    assert copy1 is not None and copy2 is not None
    shared = parent.blocks[:2]
    assert all(a.refcount(b) == 3 for b in shared)
    f1.release()
    assert all(a.refcount(b) == 2 for b in shared)
    parent.release()
    assert all(a.refcount(b) == 1 for b in shared)
    # f2 still owns the shared blocks AND its private tail copy
    assert f2.blocks[:2] == shared
    f2.release()
    assert a.free_count == a.num_blocks - 1


def test_shared_block_eviction_deferred():
    """Releasing one sharer must NOT return a shared block to the free
    list — and the prefix cache refuses to reclaim blocks live
    sequences still share."""
    a = BlockAllocator(num_blocks=8, block_size=4)
    cache = PrefixCache(a)
    t = BlockTable(a)
    toks = list(range(8))
    t.ensure_capacity(8)
    t.num_tokens = 8
    cache.insert(toks, t.blocks)        # cache holds both blocks
    blocks, n = cache.lookup(toks)
    t2 = BlockTable(a)
    t2.attach_shared(blocks)
    t2.num_tokens = 8
    assert all(a.refcount(b) == 3 for b in t.blocks)
    t.release()                         # original owner gone
    assert a.refcount(t2.blocks[0]) == 2
    # cache reclaim must refuse: t2 still shares them
    assert cache.reclaimable() == 0
    assert cache.reclaim(2) == 0
    t2.release()
    assert cache.reclaimable() == 2     # now cache-only -> reclaimable
    assert cache.reclaim(1) == 1 and len(cache) == 1


def test_append_into_shared_block_refused():
    a = BlockAllocator(num_blocks=8, block_size=4)
    t = BlockTable(a)
    for _ in range(6):
        t.append_slot()
    a.share([t.blocks[1]])              # simulate a bookkeeping bug
    with pytest.raises(BlockFreeError):
        t.append_slot()                 # tail block is shared


def test_rebuild_free_list_with_shared_survivors():
    """rebuild_free_list understands legitimately-shared blocks: a
    block claimed by several survivor tables (and the cache) rebuilds
    at its claim multiplicity, not as corruption."""
    a = BlockAllocator(num_blocks=12, block_size=4)
    shared = a.allocate(2)
    a.share(shared)                     # two table claims
    priv1 = a.allocate(1)
    priv2 = a.allocate(2)               # the "corrupt" table's blocks
    cache_hold = list(shared[:1])
    a.share(cache_hold)                 # cache claim on shared[0]
    # survivors: two tables sharing `shared`, one private table, and
    # the cache's hold; priv2's table was corrupt and is NOT a claim
    a.rebuild_free_list([shared + priv1, shared, cache_hold])
    assert a.refcount(shared[0]) == 3
    assert a.refcount(shared[1]) == 2
    assert a.refcount(priv1[0]) == 1
    assert a.refcount(priv2[0]) == 0    # implicitly returned
    assert set(priv2).issubset(set(a._free))
    # the rebuilt counts support the normal release path
    a.free(shared); a.free(shared); a.free(cache_hold); a.free(priv1)
    assert a.free_count == a.num_blocks - 1


def test_cow_tail_copy_exactness():
    """Fork CoW: the copied tail block is byte-identical, and writes
    into the fork's tail never touch the parent's."""
    a = BlockAllocator(num_blocks=8, block_size=4)
    pool = jnp.arange(2 * 8 * 4 * 2 * 3, dtype=jnp.float32).reshape(
        2, 8, 4, 6)                     # [L, N, bs, H*D]
    t = BlockTable(a)
    for _ in range(6):
        t.append_slot()
    f, copy = t.fork()
    assert copy is not None
    src, dst = copy
    pool = PagedKVCache.copy_block(pool, src, dst)
    assert np.array_equal(np.asarray(pool[:, dst]),
                          np.asarray(pool[:, src]))
    # a write into the fork's tail slot leaves the parent's bytes alone
    before = np.asarray(pool[:, src]).copy()
    pool = pool.at[:, dst, 2].set(-1.0)
    assert np.array_equal(np.asarray(pool[:, src]), before)


def test_block_table_truncate_rolls_back_surplus():
    a = BlockAllocator(num_blocks=8, block_size=4)
    t = BlockTable(a)
    for _ in range(5):
        t.append_slot()
    t.ensure_capacity(5 + 4)            # speculative over-reserve
    assert len(t.blocks) == 3
    freed = t.truncate()
    assert freed and len(t.blocks) == 2
    assert a.free_count == a.num_blocks - 1 - 2


# ------------------------------------------------------------ prefix cache
def test_prefix_cache_lookup_insert_lru():
    a = BlockAllocator(num_blocks=16, block_size=4)
    c = PrefixCache(a)
    t = BlockTable(a)
    toks = list(range(12))
    t.ensure_capacity(12); t.num_tokens = 12
    assert c.insert(toks, t.blocks) == 3
    assert c.insert(toks, t.blocks) == 0        # idempotent
    hit, n = c.lookup(toks + [77, 78])
    assert n == 12 and hit == t.blocks[:3] and c.hits == 1
    a.free(hit)                                  # undo the share
    # different prefix, same tail content: keyed by the WHOLE prefix
    other = [99] + list(range(1, 12))
    miss, n0 = c.lookup(other)
    assert miss == [] and n0 == 0 and c.misses == 1
    # peek never bumps the ledger or refcounts
    rc_before = [a.refcount(b) for b in t.blocks]
    c.lookup(toks, share=False)
    assert [a.refcount(b) for b in t.blocks] == rc_before
    assert c.hits == 1


def test_prefix_cache_shared_bytes_and_bound():
    a = BlockAllocator(num_blocks=16, block_size=4)
    c = PrefixCache(a, max_blocks=2)
    t = BlockTable(a)
    t.ensure_capacity(16); t.num_tokens = 16
    c.insert(list(range(16)), t.blocks)
    # bound enforcement is opportunistic: blocks still shared with a
    # live sequence are NEVER evicted, so the overflow defers
    assert len(c) == 4
    assert c.shared_bytes(10) == 4 * 10     # 4 blocks, 1 sharer each
    t.release()
    assert c.shared_bytes(10) == 0      # cache-only refs share nothing
    c.reclaim(len(c) - c.max_blocks)
    assert len(c) == 2                  # LRU-trimmed once free to


# ---------------------------------------------------- speculative decoding
def test_ngram_draft_and_accept():
    toks = [5, 6, 7, 8, 5, 6]
    assert ngram_draft(toks, 2, 3) == [7, 8, 5]
    assert ngram_draft([1, 2], 2, 3) == []          # too short
    assert ngram_draft([1, 2, 3, 4], 2, 3) == []    # no match
    # accept: drafts verified against the model's own continuation
    acc, bonus = accept_drafts([7, 8, 5], [7, 8, 9, 4], budget=10)
    assert acc == [7, 8] and bonus == 9             # mismatch at 5!=9
    acc, bonus = accept_drafts([7, 8, 5], [7, 8, 5, 4], budget=2)
    assert acc == [7] and bonus == 8                # budget caps
    acc, bonus = accept_drafts([], [3], budget=5)
    assert acc == [] and bonus == 3
    with pytest.raises(ValueError):
        accept_drafts([1], [1, 2], budget=0)


@pytest.fixture(scope="module")
def tiny_model():
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(0)
    cfg = gpt_tiny(use_scan=False, max_position_embeddings=128)
    return GPTForCausalLM(cfg)


def _mk_engine(model, **kw):
    defaults = dict(block_size=16, num_blocks=48, max_batch=4,
                    prefill_budget_tokens=64, max_model_len=128)
    defaults.update(kw)
    return ServingEngine(model, config=EngineConfig(**defaults))


def test_admit_undoes_hit_when_own_prefix_is_the_headroom():
    """Regression: can_allocate counts reclaimable cached blocks as
    headroom, but a request whose CACHED PREFIX is that very headroom
    pins it at commit (share -> refcount 2) — ensure_capacity must
    then fail CLEANLY: request back at the head, shared refs undone,
    nothing leaked or lost."""
    from paddle2_tpu.serving.scheduler import (
        ContinuousBatchingScheduler, Request, SchedulerConfig, Sequence)
    a = BlockAllocator(num_blocks=8, block_size=4)
    cache = PrefixCache(a)
    sched = ContinuousBatchingScheduler(
        SchedulerConfig(max_batch=4, batch_buckets=(4,),
                        page_buckets=(8,), prefill_budget_tokens=0),
        a)
    sched.prefix_cache = cache
    prefix = list(range(8))
    t = BlockTable(a)
    t.ensure_capacity(8)
    t.num_tokens = 8
    cache.insert(prefix, t.blocks)
    t.release()                          # cache-only: the 2 blocks ARE
    hog = BlockTable(a)                  # the reclaimable headroom
    hog.ensure_capacity(20)              # pin the other 5 blocks
    assert a.free_count == 0 and cache.reclaimable() == 2
    seq = Sequence(Request(0, prefix + [9, 9, 9, 9], 4), a)
    sched.submit(seq)
    admitted = sched.admit(0.0)
    assert admitted == []
    assert sched.waiting and sched.waiting[0] is seq   # still head
    assert seq.table.blocks == [] and seq.prefix_cached_tokens == 0
    # shared refs undone: cached blocks back to cache-only
    assert all(a.refcount(b) == 1 for b in cache.held_blocks())
    # once real blocks free up, the same request admits via the cache
    hog.release()
    admitted = sched.admit(1.0)
    assert admitted == [seq] and seq.prefix_cached_tokens == 8


def test_custom_buckets_plus_spec_fail_fast(tiny_model):
    """Regression: explicit batch_buckets that cannot cover the
    widest speculative verify batch must refuse at CONSTRUCTION, not
    ValueError mid-decode."""
    with pytest.raises(ValueError, match="verify rows"):
        _mk_engine(tiny_model, batch_buckets=(1, 2, 4),
                   spec=SpeculativeConfig(num_draft_tokens=3))
    # a covering explicit ladder is fine
    e = _mk_engine(tiny_model, batch_buckets=(1, 4, 16),
                   spec=SpeculativeConfig(num_draft_tokens=3))
    assert e.scheduler.config.batch_buckets[-1] == 16


# -------------------------------------------------- engine prefix caching
def _shared_trace(model, n=6, gen=8):
    rng = np.random.default_rng(3)
    sys_prompt = rng.integers(0, model.cfg.vocab_size,
                              size=48).tolist()
    out = []
    for i in range(n):
        sfx = rng.integers(0, model.cfg.vocab_size,
                           size=(8 if i % 2 else 16)).tolist()
        out.append({"arrival_t": i * 1e-4,
                    "prompt": sys_prompt + sfx,
                    "max_new_tokens": gen})
    return out


def test_engine_prefix_cache_exact_and_cheaper(tiny_model):
    """ACCEPTANCE: shared-system-prompt serving with the prefix cache
    is token-for-token identical to the unshared run while
    materializing fewer KV blocks."""
    trace = _shared_trace(tiny_model)
    e0 = _mk_engine(tiny_model)
    rep0 = simulate_serving(e0, [dict(t) for t in trace])
    toks0 = [e0.sequence(i).generated for i in range(len(trace))]
    e1 = _mk_engine(tiny_model, enable_prefix_cache=True)
    rep1 = simulate_serving(e1, [dict(t) for t in trace])
    toks1 = [e1.sequence(i).generated for i in range(len(trace))]
    assert toks1 == toks0
    assert rep1.prefix_hits >= len(trace) - 1
    assert rep1.kv_allocated_blocks < rep0.kv_allocated_blocks
    assert rep1.kv_bytes_per_request < rep0.kv_bytes_per_request
    # finished sequences left their prefix resident, cache-only
    held = e1.prefix_cache.held_blocks()
    assert held and all(e1.allocator.refcount(b) == 1 for b in held)


def test_engine_prefix_cache_eviction_recovery(tiny_model):
    """An explicit mid-decode eviction of a prefix-sharing sequence:
    re-admission re-attaches the cached prefix (blocks and KV bits
    intact) and the stream stays token-for-token (eviction exactness
    composed with sharing)."""
    trace = _shared_trace(tiny_model, n=3, gen=10)
    e0 = _mk_engine(tiny_model)
    simulate_serving(e0, [dict(t) for t in trace])
    toks0 = [e0.sequence(i).generated for i in range(len(trace))]
    e1 = _mk_engine(tiny_model, enable_prefix_cache=True,
                    prefill_budget_tokens=512)
    for r in trace:
        e1.submit(r["prompt"], r["max_new_tokens"],
                  arrival_t=r["arrival_t"])
    e1.admit_and_prefill(0.0)
    for i in range(3):
        e1.decode_once(float(i + 1))
    victim = e1.scheduler.running()[-1]
    assert victim.prefix_cached_tokens > 0 or \
        e1.prefix_cache.holds(victim.table.blocks[0])
    e1.scheduler._evict(victim, now=4.0)
    assert victim.evictions == 1
    step = 5
    while not e1.idle():
        e1.tick(float(step))
        step += 1
        assert step < 500
    toks1 = [e1.sequence(i).generated for i in range(len(trace))]
    assert toks1 == toks0


def test_validate_tables_allows_legit_sharing(tiny_model):
    """_validate_tables must NOT flag legitimately-shared prefix
    blocks — and must still catch a real cross-table scribble."""
    trace = _shared_trace(tiny_model, n=3, gen=6)
    e = _mk_engine(tiny_model, enable_prefix_cache=True,
                   prefill_budget_tokens=512)
    # drive manually so two sequences are RUNNING with shared blocks
    for r in trace:
        e.submit(r["prompt"], r["max_new_tokens"],
                 arrival_t=r["arrival_t"])
    e.admit_and_prefill(0.0)
    running = e.scheduler.running()
    assert len(running) >= 2
    shared_owned = set(running[0].table.blocks) \
        & set(running[1].table.blocks)
    assert shared_owned                  # the prefix really is shared
    active = e._validate_tables(list(running))
    assert len(active) == len(running)   # no false corruption
    assert e.scheduler.total_evictions == 0
    # now a REAL scribble: alias one sequence's private block
    victim, other = running[0], running[1]
    private = [b for b in other.table.blocks
               if b not in shared_owned]
    victim.table.blocks[-1] = private[0]
    active2 = e._validate_tables(list(e.scheduler.running()))
    assert victim not in active2 and other not in active2
    # ledger rebuilt: cache holds + survivor claims account every block
    a = e.allocator
    assert all(a.refcount(b) >= 1
               for b in e.prefix_cache.held_blocks())


def test_corrupt_chaos_with_sharing_token_invisible(tiny_model):
    """The PR 11 corrupt_block_table drill composed with prefix
    caching: recovery stays token-for-token."""
    from paddle2_tpu.distributed.fault_tolerance import chaos
    trace = _shared_trace(tiny_model, n=4, gen=8)
    e0 = _mk_engine(tiny_model, enable_prefix_cache=True)
    simulate_serving(e0, [dict(t) for t in trace])
    toks0 = [e0.sequence(i).generated for i in range(len(trace))]
    chaos.arm("corrupt_block_table:3")
    try:
        e1 = _mk_engine(tiny_model, enable_prefix_cache=True)
        simulate_serving(e1, [dict(t) for t in trace])
    finally:
        fired = {k for k, _ in chaos.fired_log()}
        chaos.disarm()
    assert "corrupt_block_table" in fired
    toks1 = [e1.sequence(i).generated for i in range(len(trace))]
    assert toks1 == toks0


def test_prefix_and_spec_compose_token_for_token(tiny_model):
    """Both features ON together == plain run, token-for-token (the
    acceptance criterion's combined-CRC gate, unit-sized)."""
    trace = _shared_trace(tiny_model, n=5, gen=8)
    e0 = _mk_engine(tiny_model)
    simulate_serving(e0, [dict(t) for t in trace])
    toks0 = [e0.sequence(i).generated for i in range(len(trace))]
    e1 = _mk_engine(tiny_model, enable_prefix_cache=True,
                    spec=SpeculativeConfig(num_draft_tokens=3))
    simulate_serving(e1, [dict(t) for t in trace])
    toks1 = [e1.sequence(i).generated for i in range(len(trace))]
    assert toks1 == toks0


# ------------------------------------------------------------- doctors
def test_doctors_surface_throughput_counters(tiny_model, tmp_path):
    from paddle2_tpu.observability import metrics
    from paddle2_tpu.tools import perf_doctor, serve_doctor
    mdir = str(tmp_path / "metrics")
    metrics.enable(mdir, rank=0, flush_steps=1)
    try:
        e = _mk_engine(tiny_model, enable_prefix_cache=True,
                       spec=SpeculativeConfig(num_draft_tokens=3))
        simulate_serving(e, _shared_trace(tiny_model, n=4, gen=8))
        metrics.flush()
    finally:
        metrics.disable()
    rep = perf_doctor.summarize(perf_doctor.load_streams(mdir),
                                warmup=0)
    cnt = rep.get("counters") or {}
    assert cnt.get("serving_prefix_hits_total", 0) > 0
    assert "serving_prefix_misses_total" in cnt
    thr = serve_doctor.load_throughput(mdir)
    assert thr["prefix_hit_rate"] is not None
    assert thr["prefix_hits"] == cnt["serving_prefix_hits_total"]
    if e.spec_accepted + e.spec_rejected:
        assert thr["spec_acceptance"] is not None
    # acceptance-rate line renders in the summary formatting
    report = {"requests": 0, "finished": 0, "shed": 0,
              "unfinished": 0,
              "exactness": {"checked": 0, "violations": []},
              "throughput": thr}
    txt = serve_doctor.format_summary(
        {**report, "finished": 0}, mdir)
    assert "serve_doctor" in txt


# ------------------------------------------------------- int4 satellite
class TestInt4WeightOnly:
    def _setup(self, m=32, k=256, n=128):
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        from paddle2_tpu.kernels import pallas_matmul as pm
        w_i4, s4 = pm.quantize_channelwise(w, 4, axis=1)
        return pm, x, w, w_i4, s4

    def test_pack_unpack_roundtrip(self):
        pm, x, w, w_i4, s4 = self._setup()
        packed = pm.pack_int4(w_i4)
        assert packed.dtype == jnp.uint8
        assert packed.shape == (w_i4.shape[0], w_i4.shape[1] // 2)
        assert np.array_equal(
            np.asarray(pm.unpack_int4(packed, w_i4.shape[1])),
            np.asarray(w_i4))
        with pytest.raises(ValueError):
            pm.pack_int4(jnp.zeros((4, 3), jnp.int8))

    def test_bound_holds_at_4_bits(self):
        """f64 reference: |y_ref - y_q| <= ||x||_1 * s/(2*qmax) at
        qmax=7, through the packed storage path."""
        pm, x, w, w_i4, s4 = self._setup()
        y4 = pm.int4_weight_only_matmul(x, pm.pack_int4(w_i4), s4)
        y_ref = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
        bound = np.asarray(pm.weight_quant_error_bound(x, s4, 4),
                           np.float64)
        err = np.abs(np.asarray(y4, np.float64) - y_ref)
        assert (err <= bound + 1e-6).all()

    def test_bound_nonvacuous_at_4_bits(self):
        """A 2-bit payload must violate the 4-bit bound, and the bound
        must beat the trivial |y| bound — same shape as the PR 10
        8-bit gate, one rung down. (The l1-norm bound grows ~linearly
        in K while |y| grows ~sqrt(K): informativeness at 4 bits needs
        the short-K regime, which is where int4 belongs anyway.)"""
        pm, x, w, w_i4, s4 = self._setup(k=64)
        w_i2, s2 = pm.quantize_channelwise(w, 2, axis=1)
        y2 = pm.int8_weight_only_matmul(x, w_i2, s2, quant_bits=2)
        y_ref = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
        bound = np.asarray(pm.weight_quant_error_bound(x, s4, 4),
                           np.float64)
        err2 = np.abs(np.asarray(y2, np.float64) - y_ref)
        assert (err2 > bound).any()
        assert bound.max() < np.abs(y_ref).max()

    def test_pallas_kernel_parity_at_4_bits(self):
        pm, x, w, w_i4, s4 = self._setup()
        y_xla = pm.int8_weight_only_matmul(x, w_i4, s4, quant_bits=4)
        y_pal = pm.int8_weight_only_matmul(
            x, w_i4, s4, quant_bits=4, block_m=32, block_n=128,
            block_k=128, interpret=True)
        np.testing.assert_allclose(np.asarray(y_pal),
                                   np.asarray(y_xla),
                                   rtol=2e-5, atol=2e-4)

    def test_weight_only_quantize_at_4_bits(self, tiny_model):
        """quant_bits=4 threads through the module swap; the dequant
        product stays within the analytic 4-bit bound."""
        import paddle2_tpu.nn as nn
        from paddle2_tpu.quantization import (WeightOnlyLinear,
                                              weight_only_quantize)
        paddle.seed(1)
        lin = nn.Linear(32, 16)
        w = np.asarray(lin.weight.numpy(), np.float64)
        holder = nn.Sequential(lin)
        weight_only_quantize(holder, quant_bits=4)
        q = holder[0]
        assert isinstance(q, WeightOnlyLinear)
        assert q.quant_bits == 4
        from paddle2_tpu.framework.tensor import Tensor
        x = np.random.default_rng(2).normal(size=(4, 32)) \
            .astype(np.float32)
        y = np.asarray(q(Tensor(jnp.asarray(x)))._data, np.float64)
        from paddle2_tpu.kernels import pallas_matmul as pm
        bound = np.asarray(pm.weight_quant_error_bound(
            jnp.asarray(x), q.w_scale._data, 4), np.float64)
        ref = np.asarray(x, np.float64) @ w
        bias = np.asarray(q.bias._data, np.float64) \
            if q.bias is not None else 0.0
        assert (np.abs(y - (ref + bias)) <= bound + 1e-5).all()
