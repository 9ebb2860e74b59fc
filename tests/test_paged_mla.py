"""``paged_mla_decode`` (interpreted) against ``paged_mla_reference`` over
what a benchmark cell does not send: tables that are one ascending run,
descending, shuffled, half run / half scattered, with shared pages and
garbage-block rows; contexts 0, 1 and on both sides of a copy group's
and a compute block's edge; bf16 and float32 pools; dead slots and dead
pages poisoned with NaN. One body adapts to each (run or no run, masked
block or not): nothing here selects a path."""

import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from paddle2_tpu.serving import paged_attention as pa
from paddle2_tpu.serving.block_cache import GARBAGE_BLOCK

L, BS, RANK, DR, H, P = 2, 8, 32, 8, 4, 96
W = pa.mla_row_width(RANK, DR)
GROUP, BLOCK = 16, 32            # pages a copy, pages a compute block
TOL = {"float32": 2e-6, "bfloat16": 2e-2}
# a copy group is 128 tokens, a compute block 256, the table 768
CONTEXTS = {
    "ends": [0, 1, 2, 7, 8, 9, 767, 768],
    "group_edges": [127, 128, 129, 383, 384, 385, 640, 641],
    "block_edges": [255, 256, 257, 511, 512, 513, 300, 700],
}


@pytest.fixture()
def small_blocks(monkeypatch):
    """Blocks of two copy groups, three blocks a table, whatever the
    dtype's page weighs."""
    def plan(dtype):
        page = BS * W * jnp.dtype(dtype).itemsize
        monkeypatch.setattr(pa, "_MLA_COPY_BYTES", GROUP * page)
        monkeypatch.setattr(pa, "_MLA_BLOCK_BYTES", BLOCK * page)
        assert pa._mla_plan(P, BS, W, dtype) == (BLOCK, GROUP)
    return plan


def tables(kind: str, rows: int, rng) -> np.ndarray:
    """``[rows, P]`` page ids below ``rows * P + 1`` (0 is the garbage
    block)."""
    n = rows * P
    own = np.arange(1, n + 1).reshape(rows, P)
    if kind == "ascending":
        return own
    if kind == "descending":
        return own[:, ::-1].copy()
    if kind == "shuffled":
        return rng.permutation(own.ravel()).reshape(rows, P)
    if kind == "half_run":
        # runs in every other group, the groups between them scattered
        t = own.copy()
        for g in range(1, P // GROUP, 2):
            cols = slice(g * GROUP, (g + 1) * GROUP)
            t[:, cols] = rng.permutation(t[:, cols].ravel()).reshape(
                rows, GROUP)
        return t
    if kind == "shared":
        # every row reads row 0's first block (a copy-on-write prefix),
        # and a page stands twice in one table
        t = own.copy()
        t[:, :BLOCK] = own[0, :BLOCK]
        t[:, BLOCK + 3] = t[:, BLOCK + 2]
        return t
    if kind == "garbage":
        # padded batch rows: every entry the garbage block
        t = own.copy()
        t[1::2] = GARBAGE_BLOCK
        return t
    raise ValueError(kind)


def inputs(dtype, rows, rng, n_blocks):
    pool = np.zeros((L, n_blocks, BS, W), np.float32)
    pool[..., :RANK + DR] = rng.normal(size=(L, n_blocks, BS, RANK + DR))
    qc = jnp.asarray(rng.normal(size=(rows, H, RANK)), dtype)
    qr = jnp.asarray(rng.normal(size=(rows, H, DR)), dtype)
    return pool, qc, qr


def check(got, want, ctx, dtype):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[ctx > 0], want[ctx > 0],
                               atol=TOL[jnp.dtype(dtype).name])
    assert not got[ctx == 0].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("contexts", sorted(CONTEXTS))
@pytest.mark.parametrize("kind", ["ascending", "descending", "shuffled",
                                  "half_run", "shared", "garbage"])
def test_against_dense_softmax(small_blocks, kind, contexts, dtype):
    small_blocks(dtype)
    rng = np.random.default_rng(zlib.crc32(f"{kind}.{contexts}".encode()))
    ctx = np.asarray(CONTEXTS[contexts], np.int32)
    rows = len(ctx)
    bt = tables(kind, rows, rng)
    if kind == "garbage":
        # a padded row stands at position 0: one key, the garbage block's
        ctx[1::2] = 1
    pool, qc, qr = inputs(dtype, rows, rng, rows * P + 1)
    pool = jnp.asarray(pool, dtype)
    got = pa.paged_mla_decode(qc, qr, pool, bt, ctx, 0.2, interpret=True,
                              layer=1)
    assert got.dtype == jnp.dtype(dtype)
    check(got, pa.paged_mla_reference(qc, qr, pool[1], bt, ctx, 0.2), ctx,
          dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", ["ascending", "shuffled"])
def test_dead_slots_and_dead_pages_never_reach_the_result(small_blocks, kind,
                                                          dtype):
    """The slots behind a context's end in its last page, every page of
    the table behind that one, the garbage block and the pool behind the
    table hold NaN; so does the other layer. The reference reads the
    same rows from a pool whose dead part is zeros."""
    small_blocks(dtype)
    rng = np.random.default_rng(7)
    ctx = np.asarray([1, 5, 120, 129, 250, 257, 512, 761], np.int32)
    rows = len(ctx)
    bt = tables(kind, rows, rng)
    clean, qc, qr = inputs(dtype, rows, rng, rows * P + 9)
    live = np.zeros(clean.shape[1:3], bool)              # [N, bs]
    for r, n in enumerate(ctx):
        for pos in range(n):
            live[bt[r, pos // BS], pos % BS] = True
    poisoned = np.where(live[None, :, :, None], clean, np.nan)
    poisoned[0] = np.nan
    got = pa.paged_mla_decode(qc, qr, jnp.asarray(poisoned, dtype), bt, ctx,
                              0.2, interpret=True, layer=1)
    want = pa.paged_mla_reference(qc, qr, jnp.asarray(clean[1], dtype), bt,
                                  ctx, 0.2)
    check(got, want, ctx, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_pool_of_fewer_blocks_than_a_copy_group(dtype):
    """Ten blocks against copy groups of 16 pages (an engine of a test's
    size): no group can be a run, and none is fetched as one."""
    assert pa._mla_plan(12, BS, W, dtype) == (16, 16)
    rng = np.random.default_rng(3)
    ctx = np.asarray([70, 9, 0], np.int32)
    bt = np.zeros((3, 12), np.int32)
    bt[0, :9] = np.arange(1, 10)            # consecutive, too few
    bt[1, :2] = [4, 5]
    pool, qc, qr = inputs(dtype, 3, rng, 10)
    pool = jnp.asarray(pool, dtype)
    got = pa.paged_mla_decode(qc, qr, pool, bt, ctx, 0.2, interpret=True,
                              layer=0)
    check(got, pa.paged_mla_reference(qc, qr, pool[0], bt, ctx, 0.2), ctx,
          dtype)
    assert pa.coalesced_pages(bt, [9, 2, 0], 16) == 0


def test_the_plan_follows_the_shapes():
    """The DeepSeek-V2 cell's: 384 pages of 16 x 640 bf16 (20 KB): blocks
    of 96 pages (1.9 MiB a half of the double buffer), copies of 16
    (320 KB). A block and a copy are whole 128-lane score rows of
    tokens, a copy divides its block, neither passes the table."""
    assert pa._mla_plan(384, 16, 640, jnp.bfloat16) == (96, 16)
    assert pa.mla_pages_per_block(384, 16, 640, jnp.bfloat16) == 96
    assert pa.mla_pages_per_copy(384, 16, 640, jnp.bfloat16) == 16
    # float32 pages weigh twice as much: half the pages a block and a copy
    assert pa._mla_plan(384, 16, 640, jnp.float32) == (48, 8)
    # a table shorter than a block: one block, one copy group
    assert pa._mla_plan(4, 8, 160, jnp.float32) == (16, 16)
    assert pa._mla_plan(20, 16, 640, jnp.bfloat16) == (24, 8)
    for n, bs, w in ((1, 16, 640), (384, 16, 640), (100, 8, 160),
                     (7, 32, 256), (4096, 16, 640)):
        for dt in (jnp.bfloat16, jnp.float32):
            ppb, ppc = pa._mla_plan(n, bs, w, dt)
            assert ppb % ppc == 0 and (ppc * bs) % 128 == 0
            assert ppb * bs * w * jnp.dtype(dt).itemsize <= 2 ** 21 \
                or ppb * bs == 128


@pytest.mark.parametrize("case,table,live,want", [
    # one row: a whole-live run, a run that is only partly live, then
    # scattered ids
    ("run_then_partial", [list(range(5, 13))], [6], 4),
    ("descending", [[12, 11, 10, 9, 8, 7, 6, 5]], [8], 0),
    ("repeated", [[5, 5, 5, 5, 6, 7, 8, 9]], [8], 4),
    ("broken_run", [[5, 6, 8, 9, 20, 21, 22, 23]], [8], 4),
    ("two_rows", [[1, 2, 3, 4, 5, 6, 7, 8], [9, 10, 11, 12, 0, 0, 0, 0]],
     [8, 4], 12),
    ("nothing_live", [[1, 2, 3, 4, 5, 6, 7, 8]], [0], 0),
    # a table that is no multiple of the group: the pad is never live
    ("short_table", [[1, 2, 3, 4, 5, 6]], [6], 4),
])
def test_coalesced_pages_by_hand(case, table, live, want):
    assert pa.coalesced_pages(np.asarray(table, np.int32), live, 4) \
        == want
