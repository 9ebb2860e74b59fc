"""Pallas paged-attention decode kernel + dense reference paths.

The decode-side half of PagedAttention (Kwon et al. SOSP'23) on the
flash kernel's machinery (``kernels/pallas_flash.py``): at decode each
sequence contributes ONE query token and attends over its whole cached
prefix, whose K/V live scattered across fixed-size blocks of the
shared pool (``serving/block_cache.py``). The sequence's block table
and context length are scalar-prefetched (``PrefetchScalarGridSpec``),
so the kernel knows where every page lies before it moves one.

The pools are token-major with a token's heads merged into one
lane-dense row, ``[layers, num_blocks, block_size, H*D]``: a page of
ALL heads is one contiguous ``[block_size, H*D]`` block (32 KB at 16
slots x 16 heads x 64 in bf16), and a separate ``D`` 64 axis would be
padded to 128 lanes, doubling the pool in HBM. The layer is an index
into the whole-model pool (a scalar the copies take in the single
body, a static block index in the split body), so a decode program
never slices (copies) a layer out of it. Both bodies compile for a
v5e chip at serving widths (``tests/test_chip_compile.py``).

Two bodies behind ONE dispatcher (:func:`paged_attention_decode`):

* **Single-split (global softmax)** — one grid step per sequence. The
  pools enter un-blocked (``memory_space=pl.ANY``) and the kernel
  walks the row's LIVE pages (``ceil(ctx / block_size)``; dead pages
  and the garbage block behind them never move) in *compute blocks*
  of many pages: K into half of a double buffer and V to its place
  in a context-resident buffer, by async copies driven by the block
  table — read in aligned groups of entries (:func:`_decode_plan`), of
  which one that is all live and holds consecutive page ids arrives in
  ONE copy of K and one of V (which groups are such runs is decided
  from the table alone, once a program: :func:`_page_runs`), any other
  page by copies of its own (:func:`_start_block`, shared with the
  latent body) — started one block ahead: the last block of a row
  starts the first block of the next, so the copy latency is paid once
  a call. All heads ride one query tile (row ``h`` holds
  head ``h`` in its own lanes of the ``H*D`` row and zeros elsewhere),
  so ONE dot per block gives every head its lane-dense ``[R, tokens]``
  score rows and ONE dot gives every head its own output lanes. The
  block size follows from the shapes (:func:`_pages_per_block`), not
  from a caller. Then the softmax runs ONCE over the whole context:
  ``dot(q, k) * scale`` -> mask with ``finfo.min`` -> ``max / exp /
  sum / divide`` in f32 -> ``dot(p, v)``, the op sequence of
  :func:`paged_attention_reference` (dense gather through the same
  table) and of ``kernels/attention._sdpa_xla``. Scores are f32 for
  every input dtype. The kernel reduces per block and then across
  blocks where the reference reduces one ``[1, S]`` row, so fp32
  agreement is a few ulp (tests: ``rtol=atol=2e-6``), not bitwise.
  Pad slots hold ``finfo.min`` scores (exactly-0.0 probability).
  VMEM scales with the context (scores and the resident V:
  :func:`decode_scratch_vmem_bytes`): past :data:`VMEM_FIT_BUDGET`
  this body is dispatched under :data:`VMEM_RAISED_BYTES` as long as
  half of that holds the scratch, at twice the compiler's own limit the
  compiler refuses it — 32k contexts are what the split body exists
  for.

* **Split-K flash-decode** — ISSUE 14: the context is carved into
  splits of ``pages_per_split`` pages; each split runs the flash
  epilogue over its own bounded scores (max ``m``, denominator ``l``,
  UNNORMALIZED value accumulator ``o``) and emits ``(m_i, l_i, o_i)``
  partials; a tiny cross-split reduction (:func:`_merge_splits`,
  jitted XLA) rescales by ``exp(m_i - max m)`` and normalizes once.
  VMEM is bounded by the SPLIT, not the context — any context length
  fits. Acceptance: a few ulp (fp32) vs
  :func:`paged_attention_split_reference` (the dense twin of the
  split body's op sequence) and vs the global-softmax reference.
  This body still gathers ONE ``[block_size, 128]``-lane page of one
  *lane group* per grid step through a ``BlockSpec`` (with ``D`` 64 a
  128-lane page carries TWO heads; the query rides as an ``[8, 128]``
  tile) into PAGE-major scratch (``[P, 8, bs]`` scores, ``[P, bs,
  128]`` values): about 0.19 us a page on a v5e whatever its size
  (PERF.md section 6, PR 24). No benchmark cell reaches it.

Dispatch: ``pages_per_split=None`` (the default) picks the
single-split body whenever its scratch fits the VMEM budget (or half
of the raised limit, which it then asks for) and falls over to split-K
with an auto-halved split width beyond it (:func:`auto_pages_per_split`). The deterministic accounting
(:func:`decode_scratch_vmem_bytes`, :func:`modeled_decode_latency_s`)
is what ``bench.py --serving-throughput`` gates the 32k story on.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kernels._platform import interpret_default
from ..kernels.pallas_flash import NEG_INF

__all__ = ["paged_attention_decode", "paged_attention_reference",
           "paged_mla_decode", "paged_mla_reference", "mla_row_width",
           "mla_pages_per_block", "mla_pages_per_copy", "coalesced_pages",
           "paged_attention_split_reference", "gathered_dense_kv",
           "decode_scratch_vmem_bytes", "fits_single_softmax",
           "auto_pages_per_split", "kernel_pages_per_block",
           "kernel_pages_per_copy",
           "modeled_decode_latency_s",
           "VMEM_BYTES", "VMEM_FIT_BUDGET"]

# Scoped VMEM one kernel may claim on a v5e core: the limit the chip's
# compiler enforces ("Scoped allocation with size 16.02M and limit
# 16.00M", the refusal a 2048-page single-softmax body draws — see
# tests/test_chip_compile.py, which compiles a body AT the fit budget),
# and the share of it a decode body's score/value scratch may take —
# the double-buffered q/k/v/o blocks and the compiler's own temporaries
# share the rest.
VMEM_BYTES = 16 * 2 ** 20
VMEM_FIT_BUDGET = VMEM_BYTES // 2
# A table whose context-resident scratch is past that budget still takes
# the single-softmax body, under a scoped limit raised as the flash walk
# raises its own (kernels/pallas_flash._walk_params: half of the core's
# 128 MiB), as long as the scratch fits half of THAT: 576 pages of 1,024
# lanes in bf16 (9,216 positions, 21 MB) do, 32k positions do not.
VMEM_RAISED_BYTES = 64 * 2 ** 20


def _precision(dtype):
    # mirror ops.linalg._mxu_precision: bf16/f16 pinned to DEFAULT so
    # the MXU keeps its native-rate path; f32 inherits the global
    # setting — the same choice _sdpa_xla and the dense references make
    if jnp.dtype(dtype) in (jnp.bfloat16, jnp.float16):
        return jax.lax.Precision.DEFAULT
    return None


def _page_scores(q_ref, k_ref, scale, first_col, ctx, fill):
    """One page's masked scores ``[R, bs]`` f32 — row ``r`` is head
    ``r`` of the lane group (its query is zero outside its own lanes,
    so the other heads' keys contribute exact zeros). The whole tile
    rides the dot: a 1-row slice of an 8-row tile is a layout the
    chip's compiler refuses."""
    q = q_ref[...]                                # (R, W)
    k = k_ref[...]                                # (bs, W)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        precision=_precision(q.dtype),
        preferred_element_type=jnp.float32) * scale
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + first_col
    return jnp.where(cols < ctx, s, fill)


def _page_sum(x, op):
    """Reduce a per-page ``[P, R, bs]`` buffer over pages and slots
    (lanes first, then the leading page axis) -> ``[1, R, 1]``."""
    return op(op(x, axis=2, keepdims=True), axis=0, keepdims=True)


def _weighted_values(p, v_buf):
    """``sum_j p[j] @ v[j]``: ``[P, R, bs] x [P, bs, W] -> [R, W]`` f32
    (a page-batched dot, then the page sum); row ``r``'s answer is in
    head ``r``'s lanes."""
    o = jax.lax.dot_general(
        p.astype(v_buf.dtype), v_buf[...], (((2,), (1,)), ((0,), (0,))),
        precision=_precision(v_buf.dtype),
        preferred_element_type=jnp.float32)       # (P, 8, D)
    return jnp.sum(o, axis=0)


def _block_softmax(s):
    """Global softmax over a ``[n_blocks, R, T]`` f32 score buffer —
    ``max / exp / sum / divide``, the op sequence of
    ``jax.nn.softmax(f32)``; NOT the online-softmax recurrence (whose
    per-block rescaling is a different rounding chain)."""
    e = jnp.exp(s - _page_sum(s, jnp.max))
    return e / _page_sum(e, jnp.sum)


def _block_scores(q, k, scale, first_col, ctx):
    """A compute block's masked scores ``[R, T]`` f32 of the query tile
    ``q`` ``[R, W]`` against the block's keys ``k`` ``[T, W]`` (row
    ``r``'s query is zero outside its own key/value head's lanes);
    columns from ``ctx`` on hold ``finfo.min``."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        precision=_precision(q.dtype),
        preferred_element_type=jnp.float32) * scale
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + first_col
    return jnp.where(cols < ctx, s, jnp.finfo(jnp.float32).min)


def _block_values(p, v):
    """A compute block's ``p v``: probabilities ``[R, T]`` f32 over the
    block's values ``[T, W]`` -> ``[R, W]`` f32."""
    return jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        precision=_precision(v.dtype),
        preferred_element_type=jnp.float32)


def _pages(ref, lead, at, pages: int):
    """``pages`` whole pages of ``ref`` from page ``at`` on, behind the
    leading indices ``lead``: one page is an index, more are a slice."""
    return ref.at[(*lead, at if pages == 1 else pl.ds(at, pages))]


def _start_block(bt_ref, run_ref, row, i, n_live, ppb, ppc, copies):
    """Start the copies of the ``n_live`` leading pages of compute block
    ``i`` of ``row``. The table is read in aligned groups of ``ppc``
    entries: a group that is all live and that ``run_ref`` marks as
    consecutive page ids is ONE run, any other goes a page at a time —
    all live: straight-line descriptors, whose address arithmetic the
    scheduler interleaves (a third of the bundles of a loop step a
    page); the ragged last group: a loop. ``copies(blk, at, pages)``:
    the descriptors that bring ``pages`` pages from page ``blk`` of the
    pool on to page ``at`` of the block; dead pages never move."""
    def group(g, carry):
        first = i * ppb + g * ppc
        left = n_live - g * ppc
        run = (left >= ppc) & (run_ref[row, i * (ppb // ppc) + g] == 1)

        def page(j, c=None):
            for cp in copies(bt_ref[row, first + j], g * ppc + j, 1):
                cp.start()
            return c

        @pl.when(run)
        def _run():
            for cp in copies(bt_ref[row, first], g * ppc, ppc):
                cp.start()

        @pl.when(jnp.logical_not(run) & (left >= ppc))
        def _scattered():
            for j in range(ppc):
                page(j)

        @pl.when(left < ppc)
        def _ragged():
            jax.lax.fori_loop(0, left, page, 0)
        return carry
    jax.lax.fori_loop(0, (n_live + ppc - 1) // ppc, group, 0)


def _wait_block(n_live, ppc, arrived):
    """Wait for the ``n_live`` pages :func:`_start_block` started: the
    semaphore counts bytes, so ``arrived(pages)`` — a descriptor of as
    many pages — waits for them whatever copies brought them, a group
    at a time and then the ragged rest a page at a time."""
    def wait(pages):
        def one(_, carry):
            arrived(pages).wait()
            return carry
        return one
    jax.lax.fori_loop(0, n_live // ppc, wait(ppc), 0)
    jax.lax.fori_loop(0, n_live % ppc, wait(1), 0)


def _decode_kernel(bt_ref, len_ref, run_ref, layer_ref, q_ref, k_hbm, v_hbm,
                   o_ref, k_buf, v_buf, s_buf, slot_ref, sem, *, scale,
                   block_size, pages_per_block, pages_per_copy, n_pages,
                   batch):
    """One grid step = one sequence, all heads: walk the row's LIVE
    pages in compute blocks of ``pages_per_block`` pages. A block's K
    pages land in one half of the ``k_buf`` double buffer and its V
    pages at their place in the context-resident ``v_buf`` while the
    block before it is scored — a run of ``pages_per_copy`` consecutive
    pages in ONE copy of K and one of V, any other page by a copy of
    its own (:func:`_start_block`); the row's last block starts the
    NEXT row's first block, so the copy latency is exposed once per
    call, not once per row. That one block of V arrives while this
    row's is still being read, so the first block of every odd row
    lives in a spare block behind the context's."""
    b = pl.program_id(0)
    layer = layer_ref[0]
    ppb, ppc, bs = pages_per_block, pages_per_copy, block_size
    tokens = ppb * bs
    width = k_buf.shape[-1]
    fill = jnp.finfo(jnp.float32).min

    def live_pages(row):
        return jnp.minimum((len_ref[row] + bs - 1) // bs, n_pages)

    def block_pages(row, i):
        # dead pages (and the garbage block behind them) never move
        return jnp.clip(live_pages(row) - i * ppb, 0, ppb)

    def v_page(row, i):
        # where block i of this row starts in v_buf
        spare = v_buf.shape[0] - ppb
        return jnp.where((i == 0) & (row % 2 == 1), spare, i * ppb)

    def start(row, i, kslot):
        v_first = v_page(row, i)

        def copies(blk, at, pages):
            # contiguous [pages, bs, H*D] of K and of V, straight out of
            # the whole-model pools
            return (pltpu.make_async_copy(
                        _pages(k_hbm, (layer,), blk, pages),
                        _pages(k_buf, (kslot,), at, pages), sem.at[kslot]),
                    pltpu.make_async_copy(
                        _pages(v_hbm, (layer,), blk, pages),
                        _pages(v_buf, (), v_first + at, pages),
                        sem.at[kslot]))
        _start_block(bt_ref, run_ref, row, i, block_pages(row, i), ppb, ppc,
                     copies)

    def wait(row, i, kslot):
        def arrived(pages):
            # K's pages and V's: twice as many pages of one pool
            return pltpu.make_async_copy(
                v_hbm.at[layer, pl.ds(0, 2 * pages)],
                v_buf.at[pl.ds(0, 2 * pages)], sem.at[kslot])
        _wait_block(block_pages(row, i), ppc, arrived)

    @pl.when(b == 0)
    def _prime():
        # a dead page of a live block is multiplied by an exactly-0
        # probability: whatever the buffer holds there must be finite
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    ctx = len_ref[b]
    # a row without a key still takes one (empty) step: it is the step
    # that starts the next row's copies; its output is 0, as before
    n_blocks = jnp.maximum((live_pages(b) + ppb - 1) // ppb, 1)
    # dead blocks: finfo.min scores (exactly-0 probability after the
    # f32 softmax)
    s_buf[...] = jnp.full_like(s_buf, fill)
    q = q_ref[...]                                # (R, H*D)

    def score_block(i, kslot):
        nxt = 1 - kslot

        more = i + 1 < n_blocks

        @pl.when(more | (b + 1 < batch))
        def _next():
            # this row's next block, or the next row's first: ONE site
            # for the copies' code, the longest of the body
            start(jnp.where(more, b, b + 1), jnp.where(more, i + 1, 0), nxt)

        wait(b, i, kslot)
        s_buf[i] = _block_scores(q, k_buf[kslot].reshape(tokens, width),
                                 scale, i * tokens, ctx)
        return nxt

    slot_ref[0] = jax.lax.fori_loop(0, n_blocks, score_block,
                                    slot_ref[0])
    s_buf[...] = _block_softmax(s_buf[...])

    def weigh_block(i, acc):
        v = v_buf[pl.ds(v_page(b, i), ppb)].reshape(tokens, width)
        return acc + _block_values(s_buf[i], v)

    o = jax.lax.fori_loop(0, n_blocks, weigh_block,
                          jnp.zeros(o_ref.shape, jnp.float32))
    o_ref[...] = jnp.where(ctx > 0, o, 0.0).astype(o_ref.dtype)


# ------------------------------------------------- VMEM / cost accounting
def _tile_pad(n: int, tile: int) -> int:
    return -(-int(n) // tile) * tile


def _lane_group(num_heads: int, head_dim: int, num_q_heads: int = None):
    """``(key/value heads per group, query rows R, lane width W)`` of
    one page of ``num_heads`` key/value heads: a 128-lane block carries
    ``128 // D`` heads; a ``D`` that is a multiple of 128 is its own
    block; anything else takes the whole ``H*D`` row (small test
    models). The rows are the group's query heads (``num_q_heads /
    num_heads`` to a key/value head), padded to the 8-row tile."""
    if head_dim % 128 == 0:
        hg = 1
    elif 128 % head_dim == 0 and num_heads % (128 // head_dim) == 0:
        hg = 128 // head_dim
    else:
        hg = num_heads
    kv_group = (num_q_heads or num_heads) // num_heads
    return hg, _tile_pad(hg * kv_group, 8), hg * head_dim


def _page_vmem_bytes(block_size, width, dtype) -> int:
    """One ``[bs, W]`` page of K or V in VMEM, padded to whole
    (sublane x 128-lane) tiles."""
    it = jnp.dtype(dtype).itemsize
    sublane = 8 * 4 // it                 # f32 8, bf16 16, int8 32
    return _tile_pad(block_size, sublane) * _tile_pad(width, 128) * it


def _group_scratch_bytes(n_pages, block_size, rows, width, dtype) -> int:
    """The split body's page-major scratch for one lane group: the
    ``[P, R, bs]`` f32 score buffer — a 16-slot page still takes a
    full 128-lane row — plus the ``[P, bs, W]`` gathered-V buffer."""
    scores = rows * _tile_pad(block_size, 128) * 4
    return int(n_pages) * (scores
                           + _page_vmem_bytes(block_size, width, dtype))


# What one compute block's K pages should weigh: large enough that a
# block's copies take about a microsecond (far above the cost of a
# loop step), small enough that a row's ragged tail wastes little of
# the two dots. And the most ONE copy brings of a run of consecutive
# pages, of K and again of V: a descriptor a page costs the scalar core
# more than a page of 8-16 KB takes to arrive, in a loop nothing
# overlaps (PERF.md section 6, PR 43).
_BLOCK_TARGET_BYTES = 2 ** 20
_COPY_BYTES = 2 ** 18


def _block_plan(n_pages: int, block_size: int, width: int, dtype,
                block_bytes: int, copy_bytes: int) -> tuple:
    """``(pages a compute block, pages a copy)`` of a paged body, from
    what the code can see (``width``: a row of the pool). Both are whole
    128-lane score rows of tokens; a block weighs about ``block_bytes``
    and is no wider than the table; a copy divides the block and weighs
    at most ``copy_bytes``."""
    lane_dense = 128 // math.gcd(128, int(block_size))
    page_bytes = int(block_size) * int(width) * jnp.dtype(dtype).itemsize
    k = max(min(block_bytes // page_bytes,
                _tile_pad(n_pages, lane_dense)) // lane_dense, 1)
    fit = max(copy_bytes // (lane_dense * page_bytes), 1)
    d = max(d for d in range(1, k + 1) if k % d == 0 and d <= fit)
    return k * lane_dense, d * lane_dense


def _decode_plan(n_pages: int, block_size: int, width: int, dtype,
                 pool_blocks: int = None) -> tuple:
    """``(pages a compute block, pages a copy)`` of the single-softmax
    body: a block's K pages weigh about :data:`_BLOCK_TARGET_BYTES` and
    the K double buffer stays within an eighth of the scoped VMEM; a
    copy weighs at most :data:`_COPY_BYTES` a pool. A pool of fewer
    than two copies' blocks (an engine of a test's size) goes a page a
    copy: the body waits for a group's K and V on one descriptor of
    twice a copy's pages of the pool."""
    ppb, ppc = _block_plan(n_pages, block_size, width, dtype,
                           min(_BLOCK_TARGET_BYTES, VMEM_BYTES // 16),
                           _COPY_BYTES)
    if pool_blocks is not None and pool_blocks < 2 * ppc:
        ppc = 1
    return ppb, ppc


def _pages_per_block(n_pages: int, block_size: int, width: int,
                     dtype) -> int:
    """Pages per compute block of the single-softmax body
    (:func:`_decode_plan`)."""
    return _decode_plan(n_pages, block_size, width, dtype)[0]


def decode_scratch_vmem_bytes(n_pages: int, block_size: int,
                              head_dim: int, dtype="float32",
                              num_heads: int = None,
                              num_kv_heads: int = None) -> int:
    """VMEM scratch bytes that scale with the context, as the chip
    lays them out. With ``num_heads``: the single-softmax body, all
    heads at once — the lane-dense ``[R, tokens]`` f32 scores plus
    the context-resident V buffer ``[P, bs, H*D]`` (the K double
    buffer and V's spare block are one compute block each whatever
    the context, and live in the other half of the VMEM). With
    ``num_kv_heads`` fewer than ``num_heads`` (grouped-query) the score
    rows are the query heads' and the V rows ``H_kv*D`` wide: 256 pages
    of 16 slots at 32 query over 8 key/value heads of 64 in bf16 are
    256 x (32 x 16 x 4 + 16 x 512 x 2) = 4.5 MiB of the 8 MiB budget.
    Without ``num_heads``: the split body's scratch for one standard
    128-lane group (``R`` 8, ``W`` ``max(D, 128)``), which is what the
    planners price."""
    if num_heads is None:
        return _group_scratch_bytes(n_pages, block_size, 8,
                                    max(int(head_dim), 128), dtype)
    scores = _tile_pad(num_heads, 8) * int(block_size) * 4
    return int(n_pages) * (scores + _page_vmem_bytes(
        block_size, (num_kv_heads or num_heads) * int(head_dim), dtype))


def fits_single_softmax(n_pages: int, block_size: int, head_dim: int,
                        dtype="float32", budget: int = None,
                        num_heads: int = None,
                        num_kv_heads: int = None) -> bool:
    """Can the global-softmax body serve this context at all? False
    at 32k: its whole-context scratch blows the VMEM budget — the
    feasibility half of the bench's 32k gate."""
    if budget is None:
        budget = VMEM_FIT_BUDGET
    return decode_scratch_vmem_bytes(n_pages, block_size, head_dim,
                                     dtype, num_heads,
                                     num_kv_heads) <= budget


def auto_pages_per_split(n_pages: int, block_size: int, head_dim: int,
                         dtype="float32", budget: int = None,
                         num_heads: int = None,
                         num_kv_heads: int = None) -> int:
    """Largest halving of ``n_pages`` whose per-split scratch — the
    split body's, for one lane group of ``num_heads`` x ``head_dim``
    (the standard group without ``num_heads``) — fits the VMEM budget
    (deterministic — no device probing)."""
    if budget is None:
        budget = VMEM_FIT_BUDGET
    rows, width = ((8, max(int(head_dim), 128)) if num_heads is None
                   else _lane_group(num_kv_heads or num_heads, head_dim,
                                    num_heads)[1:])
    pps = max(int(n_pages), 1)
    while pps > 1 and _group_scratch_bytes(
            pps, block_size, rows, width, dtype) > budget:
        pps = -(-pps // 2)
    return pps


def modeled_decode_latency_s(ctx_tokens: int, num_heads: int,
                             head_dim: int, batch: int = 1,
                             dtype="float32", block_size: int = 16,
                             pages_per_split=None,
                             peak_flops=None, hbm_bps=None) -> dict:
    """Deterministic cost x rate model of one paged-attention decode
    step (attention only — the projections are priced by the runner's
    program costs): HBM traffic = K+V streamed once plus, for a split
    kernel, the ``(o, m, l)`` partials' round-trip; FLOPs = the two
    row dots per (batch, head). Returns the modeled seconds next to a
    ``feasible`` verdict from the VMEM accounting — a body whose
    scratch cannot fit has NO latency to model, which is how the PR 9
    kernel fails the 32k gate."""
    from ..observability.cost_model import chip_peak
    if peak_flops is None or hbm_bps is None:
        p, h, _ = chip_peak()
        peak_flops = peak_flops if peak_flops is not None else p
        hbm_bps = hbm_bps if hbm_bps is not None else h
    it = jnp.dtype(dtype).itemsize
    n_pages = -(-int(ctx_tokens) // int(block_size))
    if pages_per_split is None:
        pps = n_pages
    else:
        pps = min(int(pages_per_split), n_pages)
    n_splits = -(-n_pages // pps)
    feasible = fits_single_softmax(pps, block_size, head_dim, dtype)
    kv_bytes = 2.0 * ctx_tokens * num_heads * head_dim * it * batch
    # split partials: o [S, D] f32 + m/l scalars per (b, h), written
    # then re-read by the merge
    part_bytes = (2.0 * batch * num_heads * n_splits * (head_dim + 2)
                  * 4 if n_splits > 1 else 0.0)
    flops = 2.0 * 2.0 * ctx_tokens * num_heads * head_dim * batch
    latency = max(flops / peak_flops, (kv_bytes + part_bytes) / hbm_bps)
    return {"feasible": feasible, "latency_s": latency,
            "kv_bytes": kv_bytes, "partial_bytes": part_bytes,
            "flops": flops, "n_splits": n_splits,
            "pages_per_split": pps,
            "scratch_vmem_bytes": decode_scratch_vmem_bytes(
                pps, block_size, head_dim, dtype)}


# ------------------------------------------- split-K flash-decode body
def _decode_kernel_split(bt_ref, len_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, s_buf, v_buf, *,
                         scale, block_size, pages_per_split, n_pages):
    """One (batch, head, split) program: gather the split's pages,
    then the flash epilogue over the split's bounded scores —
    ``m_i = max``, ``p = exp(s - m_i)``, ``l_i = sum p``,
    ``o_i = p @ V`` (UNNORMALIZED) — written out as partials for the
    cross-split merge. A fully-dead split (every page past the
    context) emits ``m = -inf, l = 0, o = 0`` and the merge drops it.
    """
    b = pl.program_id(0)
    sp = pl.program_id(2)
    j = pl.program_id(3)                 # page within this split
    jg = sp * pages_per_split + j        # global page index

    @pl.when(j == 0)
    def _init():
        s_buf[...] = jnp.full_like(s_buf, NEG_INF)
        v_buf[...] = jnp.zeros_like(v_buf)

    ctx = len_ref[b]

    @pl.when((jg * block_size < ctx) & (jg < n_pages))
    def _gather():
        s_buf[j] = _page_scores(q_ref, k_ref, scale, jg * block_size,
                                ctx, NEG_INF)
        v_buf[j] = v_ref[...]

    @pl.when(j == pages_per_split - 1)
    def _partial():
        s = s_buf[...]                              # (P, R, bs) f32
        m = _page_sum(s, jnp.max)                   # -inf when dead
        safe_m = jnp.where(m == NEG_INF, 0.0, m)
        p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - safe_m))
        l = _page_sum(p, jnp.sum)
        o_ref[...] = _weighted_values(p, v_buf)     # (R, W) f32
        m_ref[...] = jnp.broadcast_to(m[0], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l[0], l_ref.shape)


def _merge_splits(o_parts, m, l, out_dtype):
    """Cross-split reduction (f32): rescale every split's partial by
    ``exp(m_i - max m)``, sum, normalize once. ``o_parts``
    ``[B, H, S, D]``; ``m``/``l`` ``[B, H, S]``."""
    m_max = jnp.max(m, axis=2, keepdims=True)           # (B, H, 1)
    safe = jnp.where(m_max == NEG_INF, 0.0, m_max)
    w = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - safe))  # (B, H, S)
    l_tot = jnp.sum(w * l, axis=2)                       # (B, H)
    o = jnp.sum(w[..., None] * o_parts, axis=2)          # (B, H, D)
    l_safe = jnp.where(l_tot == 0.0, 1.0, l_tot)
    return (o / l_safe[..., None]).astype(out_dtype)


def _spread_query(q, hg: int, rows: int, dtype, kv_group: int = 1):
    """``[B, H, D] -> [B, G, R, hg*D]``: one lane group holds ``hg``
    key/value heads and the ``hg * kv_group`` query heads that read
    them; row ``r`` of group ``g`` holds query head ``g*hg*kv_group +
    r`` in the lanes of ITS key/value head, ``[(r // kv_group)*D, ...
    + D)``, and zeros elsewhere; rows past ``hg * kv_group`` are zero
    padding up to the 8-row tile. ``kv_group`` 1 is multi-head
    attention: row ``r`` in lanes ``[r*D, (r+1)*D)``."""
    B, H, D = q.shape
    hq = hg * kv_group
    own = (jnp.arange(hq)[:, None] // kv_group
           == jnp.arange(hg)[None, :]).astype(q.dtype)
    spread = (q.reshape(B, H // hq, hq, 1, D)
              * own[None, None, :, :, None]).reshape(
        B, H // hq, hq, hg * D)
    return jnp.pad(spread, ((0, 0), (0, 0), (0, rows - hq),
                            (0, 0))).astype(dtype)


def _own_lanes(x, hg: int, head_dim: int, kv_group: int = 1):
    """Inverse of :func:`_spread_query` on a kernel output
    ``[..., R, hg*D]``: keep each row's own key/value head's lanes ->
    ``[..., hg * kv_group, D]``."""
    lead = x.shape[:-2]
    x = x[..., :hg * kv_group, :].reshape(
        lead + (hg, kv_group, hg, head_dim))
    # [..., kv_group, D, hg] -> [..., hg, kv_group, D]
    x = jnp.moveaxis(jnp.diagonal(x, axis1=-4, axis2=-2), -1, -3)
    return x.reshape(lead + (hg * kv_group, head_dim))


def _split_width(n_pages, block_size, num_heads, head_dim, dtype,
                 pages_per_split, num_kv_heads=None):
    """Pages per split; ``n_pages`` means the single-softmax body, under
    the raised limit where only that holds the table (a table it cannot
    hold is split in two at least, whatever a split could hold)."""
    if pages_per_split is not None:
        return max(1, min(int(pages_per_split), n_pages))
    if fits_single_softmax(n_pages, block_size, head_dim, dtype,
                           VMEM_RAISED_BYTES // 2, num_heads, num_kv_heads):
        return n_pages
    fit = (block_size, head_dim, dtype, None, num_heads, num_kv_heads)
    return min(auto_pages_per_split(n_pages, *fit), -(-n_pages // 2))


def kernel_pages_per_block(n_pages: int, block_size: int, num_heads: int,
                           head_dim: int, dtype,
                           pages_per_split=None,
                           num_kv_heads: int = None) -> int:
    """Pages :func:`paged_attention_decode` gathers per step at these
    shapes: the compute block of the single-softmax body, 1 where it
    dispatches to split-K (one page per grid step)."""
    if _split_width(n_pages, block_size, num_heads, head_dim, dtype,
                    pages_per_split, num_kv_heads) < n_pages:
        return 1
    return _pages_per_block(n_pages, block_size,
                            (num_kv_heads or num_heads) * head_dim, dtype)


def kernel_pages_per_copy(n_pages: int, block_size: int, num_heads: int,
                          head_dim: int, dtype, pages_per_split=None,
                          num_kv_heads: int = None,
                          pool_blocks: int = None) -> int:
    """Pages ONE copy of :func:`paged_attention_decode` brings of K (and
    one of V) where a row's table allows, at these shapes over a pool
    of ``pool_blocks`` blocks: the table is read in aligned groups of
    this many entries, and a group that is all live and holds
    consecutive page ids is one copy (:func:`coalesced_pages`); 1 where
    every page goes alone (split-K, a pool without room for a run)."""
    if _split_width(n_pages, block_size, num_heads, head_dim, dtype,
                    pages_per_split, num_kv_heads) < n_pages:
        return 1
    return _decode_plan(n_pages, block_size,
                        (num_kv_heads or num_heads) * head_dim, dtype,
                        pool_blocks)[1]


def paged_attention_decode(q, k_pool, v_pool, block_tables, ctx_lens,
                           scale=None, interpret=None,
                           pages_per_split=None, layer=0, name="paged_decode"):
    """Paged decode attention.

    q: ``[B, Q, H, D]`` (paddle layout) — ``Q`` query positions per
    sequence: 1 for a decoder that appends a token a step; ``Q`` > 1 for
    one that carries a BLOCK of positions a step, all of which see the
    same context (``ctx_lens`` ends at the block's end, so they see each
    other in both directions). The ``Q`` positions ride as ``Q`` times as
    many query heads of each key/value head: one query tile, ONE walk
    of the sequence's live pages, and with ``Q`` 1 the program is what
    it was.
    k_pool/v_pool: the whole model's shared pools
    ``[L, num_blocks, block_size, H_kv*D]`` (a token's key/value heads
    merged into one row); ``layer`` names the (static) layer to read. A
    caller holding one layer's pool passes ``pool[None]``. The pool's
    width says how many key/value heads there are: with ``H_kv < H``
    (grouped-query) query head ``h`` reads key/value head ``h // (H /
    H_kv)`` — the same kernel bodies, whose query tile puts head ``h``
    in that head's lanes.
    block_tables: int32 ``[B, n_pages]`` physical block ids per
    sequence (pad rows with the garbage block).
    ctx_lens: int32 ``[B]`` valid keys per sequence (including the
    token just appended). Returns ``[B, Q, H, D]``.

    ``pages_per_split``: split-K width for the flash-decode body; ``None``
    auto-dispatches (the single-softmax body whenever its whole-context
    scratch fits the VMEM budget or, under the limit it then asks for,
    half of :data:`VMEM_RAISED_BYTES`; else :func:`auto_pages_per_split`),
    a value forces split-K whenever more than one split results. ``name``:
    the single-softmax body's kernel name (a caller's own walk, own name).
    """
    B, Q, H, D = q.shape
    layer = int(layer)
    bs = k_pool.shape[2]
    n_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = interpret_default()
    Hkv = k_pool.shape[3] // D
    if Q > 1:
        # [B, Q, H_kv, g, D] -> key/value head major: the Q x g query
        # rows of a key/value head lie together, as a group's do
        g = H // Hkv
        heads = jnp.swapaxes(q.reshape(B, Q, Hkv, g, D), 1, 2)
        out = paged_attention_decode(
            heads.reshape(B, 1, H * Q, D), k_pool, v_pool, block_tables,
            ctx_lens, scale, interpret, pages_per_split, layer)
        return jnp.swapaxes(out.reshape(B, Hkv, Q, g, D), 1, 2).reshape(
            B, Q, H, D)
    pps = _split_width(n_pages, bs, H, D, k_pool.dtype, pages_per_split,
                       Hkv)
    bt = jnp.asarray(block_tables, jnp.int32)
    ln = jnp.asarray(ctx_lens, jnp.int32)
    if pps < n_pages:
        hg, rows, _ = _lane_group(Hkv, D, H)
        qr = _spread_query(q[:, 0], hg, rows, k_pool.dtype, H // Hkv)
        out = _paged_decode_split(qr, k_pool, v_pool, bt, ln, layer,
                                  float(scale), pps, hg, D, interpret,
                                  H // Hkv)
        return out.astype(q.dtype)[:, None]

    ppb, ppc = _decode_plan(n_pages, bs, Hkv * D, k_pool.dtype,
                            k_pool.shape[1])
    # past the fit budget the body asks for the raised limit (a forced
    # single softmax keeps the compiler's own)
    vmem = None if pages_per_split is not None or fits_single_softmax(
        n_pages, bs, D, k_pool.dtype, None, H, Hkv) else VMEM_RAISED_BYTES
    return _decode_single(
        q, k_pool, v_pool, bt, ln, jnp.asarray(layer, jnp.int32), name=name,
        scale=float(scale), interpret=interpret, ppb=ppb, ppc=ppc, vmem=vmem)


@functools.partial(jax.jit, static_argnames=(
    "scale", "interpret", "ppb", "ppc", "name", "vmem"))
def _decode_single(q, k_pool, v_pool, bt, ln, layer, *, scale, interpret,
                   ppb, ppc, name="paged_decode", vmem=None):
    """The single-softmax body's call, jitted with the layer a traced
    scalar: the 24 calls of a decode program are ONE traced and lowered
    kernel called 24 times, not 24 (0.2 s each to trace and lower on
    the serving host, and the same again for the cost analysis)."""
    B, _, H, D = q.shape
    bs = k_pool.shape[2]
    n_pages = bt.shape[1]
    # all heads ride ONE query tile: row h holds query head h in the
    # lanes of its key/value head of the merged H_kv*D row
    Hkv = k_pool.shape[3] // D
    rows, width = _tile_pad(H, 8), Hkv * D
    qr = _spread_query(q[:, 0], Hkv, rows, k_pool.dtype, H // Hkv)[:, 0]
    n_blocks = -(-n_pages // ppb)
    # the table in whole compute blocks (the pad is never live), and
    # which of its groups are runs: decided from the table alone, the
    # same for every layer of a program
    bt = jnp.pad(bt, ((0, 0), (0, n_blocks * ppb - n_pages)))
    runs = _page_runs(bt, ppc).astype(jnp.int32)

    def tile():
        return pl.BlockSpec((None, rows, width),
                            lambda b, bt, ln, runs, layer: (b, 0, 0))

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_size=bs,
                          pages_per_block=ppb, pages_per_copy=ppc,
                          n_pages=n_pages, batch=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            # the pools stay where they are; the layer is an index the
            # copies take, so no program slices a layer out
            in_specs=[tile(), pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile(),
            scratch_shapes=[
                pltpu.VMEM((2, ppb, bs, width), k_pool.dtype),
                pltpu.VMEM(((n_blocks + 1) * ppb, bs, width),
                           v_pool.dtype),
                pltpu.VMEM((n_blocks, rows, ppb * bs), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, rows, width), q.dtype),
        # rows run in order: each starts the copies of the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name=name,
    )(bt, ln, runs, layer.reshape(1), qr, k_pool, v_pool)
    # [B, R, H_kv*D] -> own lanes [B, H, D] -> [B, 1, H, D]
    return _own_lanes(out, Hkv, D, H // Hkv).reshape(B, 1, H, D)


def _paged_decode_split(qr, k_pool, v_pool, bt, ln, layer, scale, pps,
                        hg, head_dim, interpret, kv_group=1):
    """Split-K driver: pad the table out to whole splits, run the
    flash-decode body per (batch, lane group, split), merge the
    partials in one tiny jitted XLA reduction. Returns ``[B, H, D]``
    f32."""
    B, groups, rows, width = qr.shape
    bs = k_pool.shape[2]
    n_pages = bt.shape[1]
    n_splits = -(-n_pages // pps)
    pad_pages = n_splits * pps
    if pad_pages > n_pages:
        # padded pages point at block 0 (the garbage block); the
        # in-kernel (jg < n_pages) guard keeps them out of the scores
        bt = jnp.pad(bt, ((0, 0), (0, pad_pages - n_pages)))

    def part(lanes):
        return pl.BlockSpec((None, None, None, rows, lanes),
                            lambda b, g, sp, j, bt, ln: (b, g, sp, 0, 0))

    def page():
        return pl.BlockSpec((None, None, bs, width),
                            lambda b, g, sp, j, bt, ln:
                            (layer, bt[b, sp * pps + j], 0, g))

    def partial_shape(lanes):
        return jax.ShapeDtypeStruct((B, groups, n_splits, rows, lanes),
                                    jnp.float32)

    o_parts, m, l = pl.pallas_call(
        functools.partial(_decode_kernel_split, scale=scale,
                          block_size=bs, pages_per_split=pps,
                          n_pages=n_pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, groups, n_splits, pps),
            in_specs=[
                pl.BlockSpec((None, None, rows, width),
                             lambda b, g, sp, j, bt, ln: (b, g, 0, 0)),
                page(), page(),
            ],
            out_specs=[part(width), part(128), part(128)],
            scratch_shapes=[
                pltpu.VMEM((pps, rows, bs), jnp.float32),
                pltpu.VMEM((pps, bs, width), v_pool.dtype),
            ]),
        out_shape=[partial_shape(width), partial_shape(128),
                   partial_shape(128)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
        name="paged_decode_split",
    )(bt, ln, qr, k_pool, v_pool)
    # per-head partials: [B, G, S, R, *] -> [B, H, S, *]
    hq = hg * kv_group
    heads = B, groups * hq, n_splits
    o_parts = jnp.swapaxes(_own_lanes(o_parts, hg, head_dim, kv_group),
                           2, 3).reshape(heads + (head_dim,))
    m, l = (jnp.swapaxes(x[:, :, :, :hq, 0], 2, 3).reshape(heads)
            for x in (m, l))
    return _merge_split_jit("float32")(o_parts, m, l)


@functools.lru_cache(maxsize=None)
def _merge_split_jit(out_dtype: str):
    return jax.jit(functools.partial(_merge_splits,
                                     out_dtype=jnp.dtype(out_dtype)))


# --------------------------------------------- latent attention (MLA)
def mla_row_width(rank: int, rope_dim: int) -> int:
    """Lanes of a token's row in a latent pool: the latent, then the
    rotary key padded with zeros to whole 128-lane groups (the chip's
    copies address a pool's minor axis in groups of 128)."""
    return int(rank) + _tile_pad(rope_dim, 128)


# What the latent body holds at a time and moves at a time: one half of
# its double buffer (a compute block's pages: 96 pages of 20 KB at the
# DeepSeek-V2 cell's widths, a [128, 1536] score tile — the widest of
# 48 / 96 / 144 pages on the chip, because the per-block cost of the
# running softmax is paid half as often while the ragged last block
# still wastes less than it saves), and the most ONE copy brings (a run
# of consecutive pages: 16 pages — a descriptor a page cost the scalar
# core 34 bundles in a loop nothing overlaps, more than the page's
# transfer takes). Readings: PERF.md section 6, PR 36.
_MLA_BLOCK_BYTES = 2 ** 21
_MLA_COPY_BYTES = 5 * 2 ** 16


def _mla_plan(n_pages: int, block_size: int, width: int, dtype) -> tuple:
    """``(pages a compute block, pages a copy)`` of
    :func:`paged_mla_decode` (:func:`_block_plan` at
    :data:`_MLA_BLOCK_BYTES` and :data:`_MLA_COPY_BYTES`)."""
    return _block_plan(n_pages, block_size, width, dtype, _MLA_BLOCK_BYTES,
                       _MLA_COPY_BYTES)


def mla_pages_per_block(n_pages: int, block_size: int, width: int,
                        dtype) -> int:
    """Pages per compute block of :func:`paged_mla_decode` (``width``:
    a row of the pool) — the block is what the body holds at a time,
    whatever the context."""
    return _mla_plan(n_pages, block_size, width, dtype)[0]


def mla_pages_per_copy(n_pages: int, block_size: int, width: int,
                       dtype) -> int:
    """Pages ONE copy of :func:`paged_mla_decode` brings where a row's
    table allows: the table is read in aligned groups of this many
    entries, and a group that is all live and holds consecutive page
    ids is one copy (:func:`coalesced_pages`)."""
    return _mla_plan(n_pages, block_size, width, dtype)[1]


def _page_runs(tables, pages_per_copy: int):
    """``[B, P]`` block tables (``P`` a multiple of ``pages_per_copy``;
    numpy on the host, traced on the device) -> ``[B, P //
    pages_per_copy]`` booleans: the group's entries are consecutive
    ascending page ids — ONE contiguous stretch of the pool."""
    g = tables.reshape(tables.shape[0], -1, pages_per_copy)
    return (g[..., 1:] - g[..., :-1] == 1).all(-1)


def coalesced_pages(tables, live_pages, pages_per_copy: int) -> int:
    """Of the live pages of a step's rows (``tables`` ``[B, P]``,
    ``live_pages`` a row), how many a paged body fetches in copies of a
    whole run: the pages of the aligned groups of ``pages_per_copy``
    entries that are all live and one run (a copy of one page coalesces
    nothing). On the host, from the tables the step is built from: the
    count ``decode.dispatch`` carries."""
    tables = np.asarray(tables)
    ppc = int(pages_per_copy)
    if ppc == 1:
        return 0
    pad = _tile_pad(tables.shape[1], ppc) - tables.shape[1]
    runs = _page_runs(np.pad(tables, ((0, 0), (0, pad))), ppc)
    whole = (np.arange(runs.shape[1])[None] + 1) * ppc \
        <= np.asarray(live_pages)[:, None]
    return int((runs & whole).sum()) * ppc


def _mla_attend(q_ref, page_ref, acc, m_sc, l_sc, scale, live=None):
    """One compute block ``page_ref`` ``[pages, bs, W]`` into the running
    softmax of :func:`_mla_kernel` (``acc`` ``[R, rank]``, ``m_sc`` /
    ``l_sc`` the running maximum and denominator over 128 lanes).
    ``live``: the block's leading columns that are context, where the
    context ends in it — scores behind them are masked with
    ``finfo.min`` and their values with zeros; None: every column."""
    rank = acc.shape[-1]
    page = page_ref[...].reshape(-1, page_ref.shape[-1])      # (T, W)
    s = jax.lax.dot_general(
        q_ref[...], page, (((1,), (1,)), ((), ())),
        precision=_precision(page.dtype),
        preferred_element_type=jnp.float32) * scale           # (R, T)
    c = page[:, :rank]
    if live is not None:
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < live, s, jnp.finfo(jnp.float32).min)
        slots = jax.lax.broadcasted_iota(jnp.int32, c.shape, 0)
        c = jnp.where(slots < live, c, jnp.zeros_like(c))
    m_prev = m_sc[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # a masked column: exp(finfo.min - finite) is exactly 0
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_sc[...] = jnp.broadcast_to(
        alpha * l_sc[:, :1] + jnp.sum(p, axis=1, keepdims=True), l_sc.shape)
    m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p.astype(page.dtype), c, (((1,), (0,)), ((), ())),
        precision=_precision(page.dtype),
        preferred_element_type=jnp.float32)                   # (R, rank)


def _mla_kernel(bt_ref, len_ref, run_ref, layer_ref, q_ref, pool_hbm, o_ref,
                buf, acc, m_sc, l_sc, slot_ref, sem, *, scale, block_size,
                pages_per_block, pages_per_copy, n_pages, batch):
    """One grid step = one sequence, all query heads against the ONE
    shared latent head: the row's LIVE pages are walked in compute
    blocks of ``pages_per_block`` pages, each page — a token's ``[c | r
    | 0]`` rows — fetched ONCE into one half of a double buffer, one
    block ahead (a row's last block starts the next row's first). The
    table is read in aligned groups of ``pages_per_copy`` entries: a
    group that is all live and that ``run_ref`` marks as consecutive
    page ids arrives in ONE copy, any other a live page at a time; a
    block's bytes are waited for a group at a time whatever brought
    them (the semaphore counts bytes). Per block: ``s = q [c | r]^T *
    scale`` in f32 (one dot: the query is ``[q_c | q_r | 0]``), the
    online-softmax recurrence (running max, denominator, accumulator),
    ``acc += p c`` with ``c`` the page's leading lanes: the bytes that
    were the keys are the values. Only the block the context ENDS in
    can hold a dead column: it alone is masked — its scores with
    ``finfo.min``, its values with zeros, so that nothing a dead slot
    or a page that never moved holds reaches the result. Nothing in
    VMEM grows with the context, so a row of any length takes this one
    body."""
    b = pl.program_id(0)
    layer = layer_ref[0]
    ppb, ppc, bs = pages_per_block, pages_per_copy, block_size
    tokens = ppb * bs

    def live_pages(row):
        return jnp.minimum((len_ref[row] + bs - 1) // bs, n_pages)

    def block_pages(row, i):
        # dead pages (and the garbage block behind them) never move
        return jnp.clip(live_pages(row) - i * ppb, 0, ppb)

    def start(row, i, slot):
        def copies(blk, at, pages):
            return (pltpu.make_async_copy(
                _pages(pool_hbm, (layer,), blk, pages),
                _pages(buf, (slot,), at, pages), sem.at[slot]),)
        _start_block(bt_ref, run_ref, row, i, block_pages(row, i), ppb, ppc,
                     copies)

    def wait(row, i, slot):
        def arrived(pages):
            return pltpu.make_async_copy(pool_hbm.at[layer, pl.ds(0, pages)],
                                         buf.at[slot, pl.ds(0, pages)],
                                         sem.at[slot])
        _wait_block(block_pages(row, i), ppc, arrived)

    @pl.when(b == 0)
    def _prime():
        slot_ref[0] = 0
        start(0, 0, 0)

    ctx = len_ref[b]
    # a row without a key still takes one (empty) step: it is the step
    # that starts the next row's copies; its output is 0
    n_blocks = jnp.maximum((live_pages(b) + ppb - 1) // ppb, 1)
    acc[...] = jnp.zeros_like(acc)
    m_sc[...] = jnp.full_like(m_sc, jnp.finfo(jnp.float32).min)
    l_sc[...] = jnp.zeros_like(l_sc)

    def block(i, slot):
        nxt = 1 - slot

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start(b, i + 1, nxt)

        @pl.when((i + 1 == n_blocks) & (b + 1 < batch))
        def _next_row():
            start(b + 1, 0, nxt)

        wait(b, i, slot)
        page = buf.at[slot]

        @pl.when((i + 1) * tokens <= ctx)
        def _whole():
            _mla_attend(q_ref, page, acc, m_sc, l_sc, scale)

        @pl.when((i * tokens < ctx) & (ctx < (i + 1) * tokens))
        def _ragged():
            _mla_attend(q_ref, page, acc, m_sc, l_sc, scale,
                        live=ctx - i * tokens)
        return nxt

    slot_ref[0] = jax.lax.fori_loop(0, n_blocks, block, slot_ref[0])
    o = acc[...] / jnp.where(ctx > 0, l_sc[:, :1], 1.0)
    o_ref[...] = jnp.where(ctx > 0, o, 0.0).astype(o_ref.dtype)


def paged_mla_decode(q_c, q_r, pool, block_tables, ctx_lens, scale,
                     interpret=None, layer=0):
    """Paged decode attention over a LATENT cache (multi-head latent
    attention with the up-projections absorbed into the query and the
    output): every one of the ``H`` query heads reads the one shared
    head of a token, and the values are its latent itself.

    q_c ``[B, H, rank]`` (the content query through ``W_UK``), q_r ``[B,
    H, dr]`` (the rotary query); pool ``[L, N, bs, W]`` the whole
    model's latent pool, a token's row ``[c (rank) | r (dr) | zeros]``
    with ``W`` = :func:`mla_row_width` (``layer`` names the layer read:
    an index the copies take); block_tables int32 ``[B, n_pages]``;
    ctx_lens int32 ``[B]`` (the token just appended included).
    ``softmax((q_c c^T + q_r r^T) * scale) c`` in float32 -> ``[B, H,
    rank]`` in q_c's dtype. ONE body for every context and every table:
    it streams the row's live pages in compute blocks
    (:func:`mla_pages_per_block`), each page read once for scores and
    values, and fetches what the table holds as runs of consecutive
    pages a run at a time (:func:`mla_pages_per_copy`)."""
    if interpret is None:
        interpret = interpret_default()
    ppb, ppc = _mla_plan(block_tables.shape[1], pool.shape[2],
                         pool.shape[3], pool.dtype)
    if pool.shape[1] < ppc:
        # a pool of fewer blocks than a copy group holds no run of one
        # (an engine of a test's size): every page a copy of its own
        ppc = 1
    return _mla_decode(
        q_c, q_r, pool, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(ctx_lens, jnp.int32), jnp.asarray(int(layer), jnp.int32),
        scale=float(scale), interpret=interpret, ppb=ppb, ppc=ppc)


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "ppb", "ppc"))
def _mla_decode(q_c, q_r, pool, bt, ln, layer, *, scale, interpret, ppb,
                ppc):
    """The call, jitted with the layer a traced scalar: a decode
    program's layers are ONE traced and lowered kernel."""
    B, H, rank = q_c.shape
    bs, width = pool.shape[2], pool.shape[3]
    n_pages = bt.shape[1]
    rows = _tile_pad(H, 8)
    q = jnp.concatenate([q_c, q_r], -1).astype(pool.dtype)
    q = jnp.pad(q, ((0, 0), (0, rows - H), (0, width - q.shape[-1])))
    # the table in whole compute blocks (the pad is never live), and
    # which of its groups are runs: decided from the table alone
    bt = jnp.pad(bt, ((0, 0), (0, _tile_pad(n_pages, ppb) - n_pages)))
    runs = _page_runs(bt, ppc).astype(jnp.int32)

    def tile(lanes):
        return pl.BlockSpec((None, rows, lanes),
                            lambda b, bt, ln, runs, layer: (b, 0, 0))

    out = pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, block_size=bs,
                          pages_per_block=ppb, pages_per_copy=ppc,
                          n_pages=n_pages, batch=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            # the pool stays where it is; the layer is an index the
            # copies take, so no program slices a layer out
            in_specs=[tile(width), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile(rank),
            scratch_shapes=[
                pltpu.VMEM((2, ppb, bs, width), pool.dtype),
                pltpu.VMEM((rows, rank), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, rows, rank), q_c.dtype),
        # rows run in order: each starts the copies of the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_mla_decode",
    )(bt, ln, runs, layer.reshape(1), q, pool)
    return out[:, :H]


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_mla_reference(q_c, q_r, pool, block_tables, ctx_lens, scale):
    """Dense twin of :func:`paged_mla_decode` over ONE layer's pool
    ``[N, bs, W]``: gather the rows through the block table, one
    float32 softmax over the whole context."""
    bt = jnp.asarray(block_tables, jnp.int32)
    rank, dr = q_c.shape[-1], q_r.shape[-1]
    rows = pool[bt].reshape(bt.shape[0], -1, pool.shape[-1])  # [B, S, W]
    c, r = rows[..., :rank], rows[..., rank:rank + dr]
    prec = _precision(pool.dtype)
    s = (jnp.einsum("bhr,bsr->bhs", q_c.astype(c.dtype), c, precision=prec,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bsd->bhs", q_r.astype(r.dtype), r, precision=prec,
                      preferred_element_type=jnp.float32)) * scale
    seen = jnp.arange(c.shape[1])[None, None] < ctx_lens[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, jnp.finfo(jnp.float32).min), -1)
    return jnp.einsum("bhs,bsr->bhr", p.astype(c.dtype), c, precision=prec,
                      preferred_element_type=jnp.float32).astype(q_c.dtype)


def gathered_dense_kv(pool, block_tables, num_heads: int):
    """Dense ``[B, n_pages*block_size, H_kv, D]`` view of every
    sequence's K or V through its block table (one vectorized gather
    over one layer's ``[N, bs, H_kv*D]`` pool; ``num_heads`` is the
    pool's key/value-head count)."""
    g = pool[jnp.asarray(block_tables, jnp.int32)]   # [B, P, bs, H*D]
    return g.reshape(g.shape[0], -1, num_heads,
                     g.shape[-1] // num_heads)


# reference programs cached per (shape, dtype, scale): eager per-op
# dispatch lets XLA compile each op alone and round reductions
# differently (observed 1-ulp drift CPU-side), so the reference always
# runs jitted
_REF_CACHE: dict = {}


def paged_attention_reference(q, k_pool, v_pool, block_tables, ctx_lens,
                              scale=None):
    """Dense reference over ONE layer's ``[N, bs, H_kv*D]`` pool (its
    width says how many key/value heads; query head ``h`` reads
    key/value head ``h // (H / H_kv)``): gather
    K/V through the block table, then the single-softmax body's op
    sequence — per (sequence, head) single-row 2-D dots, ``finfo.min``
    pad mask, one ``jax.nn.softmax(f32)`` — compiled as ONE jitted
    program. The kernel reduces per page and then across pages where
    this reduces one ``[1, S]`` row, so fp32 agreement is a few ulp
    (``tests/test_serving.py`` ``KERNEL_TOL``, rtol = atol = 2e-6),
    not bitwise. It IS bitwise-equal (fp32) to a jitted
    ``nn.functional.flash_attention`` on H=1 slices of the contiguous
    K/V at block-aligned contexts: at H=1 the dense path's batched
    einsum collapses to the same 2-D ``dot_general``, while an
    H-batched gemm is free to reassociate its reduction (observed
    1-ulp drift on XLA CPU) — which is why this reference loops heads
    instead of batching them. At ragged contexts the padded-width
    softmax/out reductions may regroup and drift 1 ulp vs the
    exact-width dense path."""
    B, _, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    key = (tuple(q.shape), str(jnp.asarray(q).dtype),
           tuple(k_pool.shape), int(np.asarray(block_tables).shape[1]),
           float(scale))
    fn = _REF_CACHE.get(key)
    if fn is None:
        fn = jax.jit(functools.partial(_reference_impl, scale=float(scale),
                                       B=B, H=H))
        if len(_REF_CACHE) > 256:
            _REF_CACHE.clear()
        _REF_CACHE[key] = fn
    return fn(jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
              jnp.asarray(block_tables, jnp.int32),
              jnp.asarray(ctx_lens, jnp.int32))


def paged_attention_split_reference(q, k_pool, v_pool, block_tables,
                                    ctx_lens, scale=None,
                                    pages_per_split=1):
    """Dense twin of the SPLIT-K body over ONE layer's
    ``[N, bs, H*D]`` pool: gather K/V through the block table, then the
    split kernel's op sequence — per-page single-row score dots,
    per-split ``max/exp/sum`` and the unnormalized ``p @ V`` partial
    dot (f32 accumulation), then the exact :func:`_merge_splits`
    reduction the kernel path runs. The kernel reduces per page and
    then across the split's pages where this reduces one row per
    split, so fp32 agreement with the kernel is a few ulp (``KERNEL_TOL``,
    rtol = atol = 2e-6), as it is vs the global-softmax
    :func:`paged_attention_reference` (the per-split rescaling
    reassociates the softmax reductions)."""
    B, _, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    key = ("split", tuple(q.shape), str(jnp.asarray(q).dtype),
           tuple(k_pool.shape), int(np.asarray(block_tables).shape[1]),
           float(scale), int(pages_per_split))
    fn = _REF_CACHE.get(key)
    if fn is None:
        # TWO compiled stages, mirroring the kernel path's program
        # structure (pallas partials, then the shared merge program):
        # fusing partials + merge into one XLA program lets the
        # compiler reassociate across the boundary (~1 ulp observed on
        # CPU), so the reference reuses the EXACT _merge_split_jit
        # program the kernel path runs
        fn = jax.jit(functools.partial(
            _split_partials_impl, scale=float(scale), B=B, H=H,
            pps=int(pages_per_split)))
        if len(_REF_CACHE) > 256:
            _REF_CACHE.clear()
        _REF_CACHE[key] = fn
    o_parts, m, l = fn(jnp.asarray(q), jnp.asarray(k_pool),
                       jnp.asarray(v_pool),
                       jnp.asarray(block_tables, jnp.int32),
                       jnp.asarray(ctx_lens, jnp.int32))
    out = _merge_split_jit(str(jnp.dtype(jnp.asarray(q).dtype)))(
        o_parts, m, l)
    return out[:, None]                              # (B, 1, H, D)


def _split_partials_impl(q, k_pool, v_pool, block_tables, ctx_lens, *,
                         scale, B, H, pps):
    """Dense mirror of the split kernel's per-(batch, head, split)
    partial computation: returns ``(o_parts [B,H,S,D] f32,
    m [B,H,S] f32, l [B,H,S] f32)``."""
    D = q.shape[-1]
    Hkv = k_pool.shape[-1] // D
    g = H // Hkv
    kd = gathered_dense_kv(k_pool, block_tables, Hkv)  # [B,S_pad,Hkv,D]
    vd = gathered_dense_kv(v_pool, block_tables, Hkv)
    prec = _precision(q.dtype)
    bs = k_pool.shape[1]
    n_pages = block_tables.shape[1]
    n_splits = -(-n_pages // pps)
    all_o, all_m, all_l = [], [], []
    for b in range(B):
        heads_o, heads_m, heads_l = [], [], []
        for h in range(H):
            parts_o, parts_m, parts_l = [], [], []
            for sp in range(n_splits):
                cols = []
                vals = []
                for j in range(pps):
                    jg = sp * pps + j
                    if jg >= n_pages:
                        # padded page: NEG_INF scores, zero V — the
                        # kernel's untouched-scratch state
                        cols.append(jnp.full((1, bs), NEG_INF,
                                             jnp.float32))
                        vals.append(jnp.zeros((bs, D), q.dtype))
                        continue
                    lo = jg * bs
                    s = jax.lax.dot_general(
                        q[b, :, h], kd[b, lo:lo + bs, h // g],
                        (((1,), (1,)), ((), ())),
                        precision=prec) * scale       # (1, bs)
                    valid = (jnp.arange(bs) + lo) < ctx_lens[b]
                    s = jnp.where(valid[None, :],
                                  s.astype(jnp.float32), NEG_INF)
                    # the kernel skips pages wholly past the context:
                    # its scratch keeps NEG_INF/0 there
                    dead = jnp.asarray(lo, jnp.int32) >= ctx_lens[b]
                    cols.append(jnp.where(dead, NEG_INF, s))
                    vals.append(jnp.where(
                        dead, jnp.zeros_like(vd[b, lo:lo + bs, h // g]),
                        vd[b, lo:lo + bs, h // g]))
                s = jnp.concatenate(cols, axis=1)     # (1, S_split) f32
                v = jnp.concatenate(vals, axis=0)     # (S_split, D)
                m = jnp.max(s, axis=1, keepdims=True)
                safe_m = jnp.where(m == NEG_INF, 0.0, m)
                p = jnp.exp(s - safe_m)
                p = jnp.where(s == NEG_INF, 0.0, p)
                l = jnp.sum(p, axis=1, keepdims=True)
                o = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                parts_o.append(o[0])                  # (D,) f32
                parts_m.append(m[0, 0])
                parts_l.append(l[0, 0])
            heads_o.append(jnp.stack(parts_o))        # (S, D)
            heads_m.append(jnp.stack(parts_m))        # (S,)
            heads_l.append(jnp.stack(parts_l))
        all_o.append(jnp.stack(heads_o))              # (H, S, D)
        all_m.append(jnp.stack(heads_m))
        all_l.append(jnp.stack(heads_l))
    return (jnp.stack(all_o), jnp.stack(all_m), jnp.stack(all_l))


def _reference_impl(q, k_pool, v_pool, block_tables, ctx_lens, *,
                    scale, B, H):
    Hkv = k_pool.shape[-1] // q.shape[-1]
    g = H // Hkv
    kd = gathered_dense_kv(k_pool, block_tables, Hkv)  # [B,S_pad,Hkv,D]
    vd = gathered_dense_kv(v_pool, block_tables, Hkv)
    prec = _precision(q.dtype)
    s_pad = kd.shape[1]
    out = []
    for b in range(B):
        valid = jnp.arange(s_pad) < ctx_lens[b]
        heads = []
        for h in range(H):
            s = jax.lax.dot_general(
                q[b, :, h], kd[b, :, h // g], (((1,), (1,)), ((), ())),
                precision=prec) * scale              # (1, S_pad)
            s = jnp.where(valid[None, :], s, jnp.finfo(s.dtype).min)
            p = jax.nn.softmax(s.astype(jnp.float32),
                               axis=-1).astype(q.dtype)
            heads.append(jax.lax.dot_general(
                p, vd[b, :, h // g], (((1,), (0,)), ((), ())),
                precision=prec))                     # (1, D)
        out.append(jnp.stack(heads, axis=1))         # (1, H, D)
    return jnp.stack(out)                            # (B, 1, H, D)
