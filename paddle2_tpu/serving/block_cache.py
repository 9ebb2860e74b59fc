"""Paged KV cache: fixed-size blocks + per-sequence block tables.

The vLLM PagedAttention memory model (Kwon et al. SOSP'23) adapted to
the TPU serving engine: the KV cache for ALL sequences lives in one
pool of fixed-size blocks per layer, and each sequence owns an ordered
list of block ids (its *block table*). Appending a token never copies
anything — the new K/V lands in the next free slot of the sequence's
last block, and a fresh block is taken from the free list only when
the last one fills. Fragmentation is bounded to < one block per
sequence instead of the (max_seq_len - actual_len) waste of a
contiguous per-request cache — the source of the >= 45% memory win the
serving bench gates.

Host/device split:

* :class:`BlockAllocator` / :class:`BlockTable` are pure-host
  bookkeeping (free list, per-sequence id lists, high-water mark) —
  cheap python between decode steps, never traced.
* :class:`PagedKVCache` owns the device pools — one
  ``[layers, num_blocks, block_size, heads * head_dim]`` array for K
  and one for V — and the jnp scatter/gather helpers the compiled
  decode program uses. The pools are donated through the decode
  program, so appends are in-place on device.

Block 0 is RESERVED as the garbage block: padded (inactive) rows of a
bucketed decode batch point their table entries at it, so their
writes land somewhere harmless and never clobber a live sequence.

**Copy-on-write sharing (ISSUE 14 / ROADMAP 2(a)).** Every allocated
block carries a REFCOUNT. ``allocate`` hands out blocks at refcount 1;
:meth:`BlockAllocator.share` adds an owner; ``free`` drops one
reference and only returns the block to the free list at refcount 0 —
so releasing a sequence that shares a system-prompt prefix can never
yank blocks out from under its siblings (eviction of a shared block is
DEFERRED by construction). Shared blocks are always FULL blocks
(appends only ever touch a private tail), which is what makes sharing
read-only and therefore exact:

* :class:`PrefixCache` — content-addressed cache of full prompt-prefix
  blocks, keyed by the block-aligned token prefix itself (a chain of
  prefix tuples, so identical content under different prefixes never
  conflates). A lookup shares the longest cached prefix into a new
  sequence's table; the cache holds its OWN reference on every cached
  block, so finished sequences leave their prefix KV resident. LRU
  eviction reclaims cache-only (refcount-1) blocks when the allocator
  runs dry — via the allocator's reclaimer hook, so schedulers see the
  reclaimable headroom without knowing the cache exists.
* :meth:`BlockTable.fork` — CoW duplication of a live sequence: full
  blocks are shared (refcount bump), ONLY the partial tail block is
  copied (:meth:`PagedKVCache.copy_block` moves the device bytes), so
  a fork costs at most one block regardless of context length.
* :meth:`BlockAllocator.rebuild_free_list` recomputes refcounts as
  claim MULTIPLICITY across the surviving tables (+ the cache's
  holds): a block claimed by two survivors is legitimately shared
  state, not corruption — the PR 11 recovery path understands sharing.
"""

from __future__ import annotations

import zlib
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["BlockAllocator", "BlockTable", "PagedKVCache",
           "PrefixCache", "HostKVTier", "audit_kv_ledger",
           "blocks_for_tokens", "GARBAGE_BLOCK", "BlockFreeError"]

# physical block id every padded/inactive batch row writes into
GARBAGE_BLOCK = 0

# jitted prefill-scatter programs, keyed by array signature
_PREFILL_SCATTER_CACHE: Dict = {}
# the HLO module of ``scatter_prefill``'s jitted entry ("jit_" + the
# function's name): the key of its launch ordinals (``profiler.launch``)
SCATTER_MODULE = "jit_p2t_kv_scatter_prefill"


def blocks_for_tokens(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` (ceil division)."""
    return -(-int(n_tokens) // int(block_size))


class OutOfBlocksError(RuntimeError):
    """Free list exhausted — the scheduler turns this into an eviction."""


class BlockFreeError(ValueError):
    """A ``free()`` that would corrupt the free list: double-free,
    free of the reserved garbage block 0, an out-of-range id, or a
    duplicate WITHIN the freed list itself. The allocator validates
    the whole list before mutating anything, so a raised free leaves
    the free list exactly as it was. (``ValueError`` base keeps
    pre-typed ``except ValueError`` callers working.)"""


class BlockAllocator:
    """Free-list allocator over ``num_blocks`` fixed-size blocks.

    Block 0 (:data:`GARBAGE_BLOCK`) is reserved at construction and is
    never handed out. ``high_water`` tracks the peak number of
    simultaneously-allocated blocks — the serving bench compares
    ``high_water * block_bytes`` against the contiguous
    max-seq-len cache a non-paged engine would have to reserve."""

    def __init__(self, num_blocks: int, block_size: int,
                 state_slots: int = 0):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved)")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # the second kind of state (a model whose layers keep a
        # fixed-size recurrent state per sequence, not keys and values):
        # ``state_slots`` slots 1..n of a device pool, one per running
        # sequence, handed out and taken back HERE with the blocks, so
        # that one object answers "can this sequence be admitted". Slot
        # 0 is the garbage slot of padded batch rows, as block 0 is.
        self.state_slots = int(state_slots)
        self._free_slots: List[int] = list(range(self.state_slots, 0, -1))
        # LIFO free list: recently-freed blocks are re-used first (their
        # pool slots are warm in cache on real hardware)
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self.high_water = 0
        # CoW plane: per-block reference count (0 = free), an array so
        # that the engine's per-tick table validation reads it whole.
        # total_allocated counts allocate() handouts MONOTONICALLY and
        # NOT share() bumps — it is the "KV bytes actually materialized"
        # numerator the prefix-cache bench gate divides by requests.
        self._rc = np.zeros(self.num_blocks, np.int64)
        self.total_allocated = 0
        # optional reclaimer (the PrefixCache): consulted when the free
        # list alone cannot cover a request — must expose
        # reclaimable() -> int and reclaim(n) -> int
        self._reclaimer = None

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    def refcount(self, block: int) -> int:
        """Current owner count of ``block`` (0 = on the free list)."""
        block = int(block)
        return int(self._rc[block]) if 0 <= block < self.num_blocks else 0

    def refcounts(self) -> np.ndarray:
        """Owner count of every block, ``[num_blocks]`` (0 = free); the
        allocator's own array, not to be written."""
        return self._rc

    def set_reclaimer(self, reclaimer) -> None:
        """Install the cache that can give blocks back on demand
        (``reclaimable()``/``reclaim(n)`` protocol; None clears)."""
        self._reclaimer = reclaimer

    def _reclaimable(self) -> int:
        return self._reclaimer.reclaimable() if self._reclaimer else 0

    def can_allocate(self, n: int) -> bool:
        return n <= len(self._free) + self._reclaimable()

    # -- state slots -----------------------------------------------------
    @property
    def state_slots_used(self) -> int:
        return self.state_slots - len(self._free_slots)

    def can_admit(self, n_blocks: int) -> bool:
        """Blocks AND (where the model keeps one) a state slot."""
        return self.can_allocate(n_blocks) and (
            not self.state_slots or bool(self._free_slots))

    def take_state_slot(self) -> int:
        if not self._free_slots:
            raise OutOfBlocksError(
                f"all {self.state_slots} state slots are in use")
        return self._free_slots.pop()

    def free_state_slot(self, slot: int) -> None:
        slot = int(slot)
        if not (0 < slot <= self.state_slots) or slot in self._free_slots:
            raise BlockFreeError(f"bad or double free of state slot {slot}")
        self._free_slots.append(slot)

    def allocate(self, n: int = 1) -> List[int]:
        if n > len(self._free) and self._reclaimer is not None:
            # cached prefix blocks nobody references are headroom, not
            # occupancy: LRU-evict just enough of them
            self._reclaimer.reclaim(n - len(self._free))
        if n > len(self._free):
            raise OutOfBlocksError(
                f"need {n} blocks, {len(self._free)} free "
                f"(of {self.num_blocks - 1} usable)")
        out = [self._free.pop() for _ in range(n)]
        self._rc[out] = 1
        self.total_allocated += n
        self.high_water = max(self.high_water, self.used_count)
        return out

    def share(self, blocks: List[int]) -> List[int]:
        """Add one owner to each (already-allocated) block — the CoW
        primitive behind prefix hits and :meth:`BlockTable.fork`.
        Validates every id BEFORE bumping anything (sharing a free or
        out-of-range block would be silent cross-request KV bleed)."""
        blocks = [int(b) for b in blocks]
        for b in blocks:
            if b == GARBAGE_BLOCK:
                raise BlockFreeError(
                    f"share of reserved garbage block {GARBAGE_BLOCK}")
            if not (0 < b < self.num_blocks):
                raise BlockFreeError(f"bad block id {b} (usable range "
                                     f"1..{self.num_blocks - 1})")
            if self._rc[b] < 1:
                raise BlockFreeError(
                    f"share of unallocated block {b}")
        for b in blocks:
            self._rc[b] += 1
        return blocks

    def free(self, blocks: List[int]) -> None:
        """Drop one reference per block; blocks reaching refcount 0
        return to the free list. Every id is validated BEFORE any
        mutation: out-of-range, the reserved garbage block
        (:data:`GARBAGE_BLOCK`), already-free ids, and duplicates
        inside ``blocks`` itself all raise :class:`BlockFreeError`
        instead of silently corrupting the LIFO free list (a corrupt
        list hands the same block to two sequences — cross-request KV
        bleed, the worst silent failure a serving engine can have).
        A shared block survives the free with one owner fewer — the
        deferred-eviction contract."""
        seen = set()
        for b in blocks:
            if b == GARBAGE_BLOCK:
                raise BlockFreeError(
                    f"free of reserved garbage block {GARBAGE_BLOCK}")
            if not (0 < b < self.num_blocks):
                raise BlockFreeError(f"bad block id {b} (usable range "
                                     f"1..{self.num_blocks - 1})")
            if self._rc[b] < 1:
                raise BlockFreeError(f"double free of block {b}")
            if b in seen:
                raise BlockFreeError(
                    f"block {b} appears twice in one free() call")
            seen.add(b)
        # last block first: the next allocation pops them in the order
        # they were freed in — a table freed whole comes back ascending
        # where it was ascending, runs of consecutive pages that ONE
        # copy of a paged kernel fetches (paged_attention.
        # coalesced_pages); still LIFO by sequence
        for b in reversed(blocks):
            self._rc[b] -= 1
            if self._rc[b] == 0:
                self._free.append(b)

    def rebuild_free_list(self, live_block_lists,
                          live_state_slots=()) -> None:
        """Recovery path: recompute the free list — and the refcounts
        — from the surviving claims. Used after a block-table
        corruption, when one table's ids can no longer be trusted
        enough to ``free()`` them (a corrupt id could double-free a
        live block). Ground truth is the surviving tables (plus the
        prefix cache's holds, which the engine passes as one more
        claim list); a block claimed by SEVERAL survivors is
        legitimately shared and its refcount is rebuilt as the claim
        multiplicity. The corrupted sequence's blocks implicitly
        return to the pool."""
        claims: Dict[int, int] = {}
        for blocks in live_block_lists:
            for b in blocks:
                b = int(b)
                if b == GARBAGE_BLOCK:
                    continue
                claims[b] = claims.get(b, 0) + 1
        bad = [b for b in claims if not (0 < b < self.num_blocks)]
        if bad:
            raise BlockFreeError(
                f"rebuild_free_list given out-of-range ids {bad} — "
                f"survivors must be validated tables")
        self._rc[:] = 0
        for b, n in claims.items():
            self._rc[b] = n
        self._free = [b for b in range(self.num_blocks - 1, 0, -1)
                      if b not in claims]
        self.high_water = max(self.high_water, len(claims))
        held = {int(s) for s in live_state_slots}
        self._free_slots = [s for s in range(self.state_slots, 0, -1)
                            if s not in held]


class BlockTable:
    """One sequence's ordered block ids + token count.

    ``num_tokens`` counts K/V entries actually written; appends extend
    the table lazily through the owning allocator."""

    def __init__(self, allocator: BlockAllocator):
        self._alloc = allocator
        self.blocks: List[int] = []
        self.num_tokens = 0
        # the sequence's slot of the state pool (None: not admitted, or
        # a model without such state); taken with the first blocks,
        # given back by release()
        self.state_slot: Optional[int] = None

    @property
    def capacity(self) -> int:
        return len(self.blocks) * self._alloc.block_size

    def ensure_capacity(self, n_tokens: int) -> None:
        """Grow the table to hold ``n_tokens`` total. Raises
        :class:`OutOfBlocksError` (eviction trigger) when the free
        list cannot cover the growth — the table is left unchanged."""
        need = blocks_for_tokens(n_tokens, self._alloc.block_size) \
            - len(self.blocks)
        if self._alloc.state_slots and self.state_slot is None:
            if need > 0 and not self._alloc.can_allocate(need):
                raise OutOfBlocksError(f"need {need} blocks")
            self.state_slot = self._alloc.take_state_slot()
        if need > 0:
            self.blocks.extend(self._alloc.allocate(need))

    def append_slot(self) -> tuple:
        """(physical_block, offset) for the NEXT token, growing the
        table if the current block is full. Bumps ``num_tokens``.
        Appending INTO a shared block (refcount > 1) is refused: the
        CoW invariant is that shared blocks are always FULL (prefix
        hits and forks only ever share whole blocks), so a shared
        append target means the bookkeeping upstream is broken and a
        write would bleed into a sibling sequence's KV."""
        self.ensure_capacity(self.num_tokens + 1)
        bs = self._alloc.block_size
        target = self.blocks[self.num_tokens // bs]
        if self.num_tokens % bs and self._alloc.refcount(target) > 1:
            raise BlockFreeError(
                f"append into shared block {target} (refcount "
                f"{self._alloc.refcount(target)}) — shared blocks are "
                f"read-only; fork() copies the partial tail")
        slot = (target, self.num_tokens % bs)
        self.num_tokens += 1
        return slot

    def attach_shared(self, blocks: List[int]) -> None:
        """Adopt already-shared blocks (the caller — a prefix-cache
        hit — bumped their refcounts) as this table's leading blocks.
        Only valid on an EMPTY table: shared blocks are a prefix, by
        construction."""
        if self.blocks:
            raise BlockFreeError(
                "attach_shared on a non-empty table — shared prefix "
                "blocks must come first")
        self.blocks = [int(b) for b in blocks]

    def fork(self) -> Tuple["BlockTable", Optional[Tuple[int, int]]]:
        """Copy-on-write duplicate of this table: full blocks are
        SHARED (refcount bump — zero bytes moved), only the partial
        tail block is freshly allocated. Returns ``(new_table,
        copy)`` where ``copy`` is ``(src_block, dst_block)`` for the
        device-side tail copy the caller must perform
        (:meth:`PagedKVCache.copy_block` on both pools), or ``None``
        when the token count is block-aligned."""
        bs = self._alloc.block_size
        n_full = self.num_tokens // bs
        new = BlockTable(self._alloc)
        shared = self.blocks[:n_full]
        if shared:
            self._alloc.share(shared)
        new.blocks = list(shared)
        copy = None
        if self.num_tokens % bs:
            src = self.blocks[n_full]
            dst = self._alloc.allocate(1)[0]
            new.blocks.append(dst)
            copy = (src, dst)
        new.num_tokens = self.num_tokens
        return new, copy

    def truncate(self) -> List[int]:
        """Roll back surplus tail blocks past what ``num_tokens``
        needs — the speculative-decoding rejection path (a verify
        round reserves ``k + 1`` slots up front; the rejected tail's
        blocks go straight back). Returns the freed block ids."""
        keep = blocks_for_tokens(self.num_tokens, self._alloc.block_size)
        surplus = self.blocks[keep:]
        if surplus:
            self._alloc.free(surplus)
            self.blocks = self.blocks[:keep]
        return surplus

    def release(self) -> None:
        """Drop this table's reference on every block (eviction /
        finish); unshared blocks return to the allocator, shared ones
        stay with their surviving owners."""
        if self.blocks:
            self._alloc.free(self.blocks)
        self.blocks = []
        self.num_tokens = 0
        if self.state_slot is not None:
            # no clear: the next owner's prefill overwrites the slot
            self._alloc.free_state_slot(self.state_slot)
            self.state_slot = None

    def padded(self, n_pages: int) -> np.ndarray:
        """int32 table row padded to ``n_pages`` with the garbage
        block (safe for bucketed kernels: dead pages are masked by the
        context length, and padded-row writes land in block 0)."""
        row = np.full((n_pages,), GARBAGE_BLOCK, np.int32)
        row[:len(self.blocks)] = self.blocks
        return row


class PagedKVCache:
    """Device pools for a whole model: K and V, each
    ``[attention layers, num_blocks, block_size, num_kv_heads *
    head_dim]`` — token-major, a token's key/value heads merged into one
    lane-dense row: the chip pads the minor dimension to 128 lanes, so a
    separate ``head_dim`` 64 axis would double the pool in HBM, and the
    paged kernel's DMA addresses ``[block_size, H_kv*D]`` pages of
    exactly this shape. ``num_layers`` counts the layers that HAVE keys
    and values and ``num_kv_heads`` the heads they keep (a grouped-query
    model keeps fewer than it has query heads; ``num_heads`` is the
    same number under its older name). A new token is one contiguous
    row, so the decode append is a plain row scatter the compiler
    updates in place.

    ``kv_widths = (K row width, V row width)`` replaces ``num_kv_heads *
    head_dim`` twice for a cache that is not "heads x head_dim, twice".
    A LATENT cache (multi-head latent attention) keeps ONE vector a
    token and layer — the latent and the rotary key, read as keys and as
    values — in the K pool and has no V pool at all: ``(row width, 0)``,
    ``v`` None; per-head keys and values are never stored. The bytes a
    block holds (:attr:`block_bytes`, what the allocator's ledger and
    the pool's live share count) follow the row widths.

    ``state_kinds`` (with ``state_slots``) adds the fixed-size state a
    sequence keeps beside its blocks: an ordered mapping ``name ->
    ((state layers, *per-layer shape), dtype)``, one pool ``[state
    layers, slots + 1, *per-layer shape]`` a kind in :attr:`states`
    (dtype ``None``: the cache's; slot 0 is the padded rows' garbage
    slot). A model may keep several kinds of different shape and type in
    one layer (a convolution's last inputs in the cache's dtype, a
    recurrent state in float32): ONE slot id, handed out by the
    :class:`BlockAllocator`, serves all of a sequence's kinds.

    ``tokens`` (``token_rows`` wide, the widest decode batch) is the
    last decode step's output tokens, kept on the device: the next step
    takes a row's input token from it where the host has not read that
    step back yet (``PagedRunner.decode``). ``firsts``, as wide, holds
    the first tokens of prefills the host has not read back yet
    (:meth:`keep_first`): the same step takes those there too. For a
    block-diffusion family (``block_length`` B: a step carries a block
    of B positions a sequence, ``serving/blockdiff.py``) what stays on
    the device instead is the BLOCK in flight of every row of the last
    step: ``block_ids`` ``[token_rows, B]`` and ``block_masked`` (which
    of them are not fixed yet), which the next pass takes where they lie.

    Pools start zeroed; stale data in freed blocks and slots is
    harmless — the paged-attention kernel masks every slot past a
    sequence's context length (masked probabilities are exactly 0.0 in
    fp32), and a state slot is overwritten whole by its next owner's
    prefill."""

    def __init__(self, num_layers: int, num_blocks: int, block_size: int,
                 num_kv_heads: int, head_dim: int, dtype="float32",
                 state_kinds=None, state_slots: int = 0,
                 token_rows: int = 1, block_length: Optional[int] = None,
                 kv_widths: Optional[Tuple[int, int]] = None):
        import jax
        import jax.numpy as jnp
        self.num_layers = int(num_layers)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.num_kv_heads = self.num_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = jnp.dtype(dtype)
        self.kv_widths = tuple(int(w) for w in (
            kv_widths or (num_kv_heads * head_dim,) * 2))
        self.k, self.v = (
            jnp.zeros((num_layers, num_blocks, block_size, w), self.dtype)
            if w else None for w in self.kv_widths)
        # committed to its device, as every later step's output is: an
        # uncommitted first value would give the first decode call a
        # signature of its own, and the second a compilation
        device = next(iter(self.k.devices()))
        self.tokens = jax.device_put(
            jnp.zeros((int(token_rows),), jnp.int32), device)
        self.firsts = jax.device_put(jnp.zeros_like(self.tokens), device)
        self.block_ids = self.block_masked = None
        if block_length is not None:
            shape = (int(token_rows), int(block_length))
            self.block_ids = jax.device_put(jnp.zeros(shape, jnp.int32),
                                            device)
            self.block_masked = jax.device_put(jnp.zeros(shape, bool),
                                               device)
        self.states = {
            name: jnp.zeros((shape[0], int(state_slots) + 1)
                            + tuple(shape[1:]), kind_dtype or self.dtype)
            for name, (shape, kind_dtype) in (state_kinds or {}).items()}

    def write_state(self, slot: int, states) -> None:
        """``pool[:, slot] = state`` for every kind (one sequence's
        prefilled states, in the kinds' order, each ``[state layers,
        *per-layer shape]``): ONE jitted program with the pools donated
        and the slot a runtime scalar."""
        import jax
        import jax.numpy as jnp
        pools = tuple(self.states.values())
        key = ("state",) + tuple((tuple(p.shape), str(p.dtype))
                                 for p in pools)
        fn = _PREFILL_SCATTER_CACHE.get(key)
        if fn is None:
            def p2t_state_write(pools, sts, sl):
                with jax.named_scope("state_write"):
                    return tuple(pool.at[:, sl].set(st.astype(pool.dtype))
                                 for pool, st in zip(pools, sts))
            fn = _PREFILL_SCATTER_CACHE[key] = jax.jit(
                p2t_state_write, donate_argnums=(0,))
        pools = fn(pools, tuple(states), jnp.asarray(int(slot), jnp.int32))
        self.states = dict(zip(self.states, pools))

    def keep_first(self, row: int, out) -> None:
        """``firsts[row] = out[0]``: the first token of a prefill (the
        head of its program's int32 array ``out``) is put where the next
        decode step can take it, without the host having seen it. One
        jitted program, the row a runtime scalar."""
        import jax
        fn = _PREFILL_SCATTER_CACHE.get("first_token")
        if fn is None:
            def p2t_first_token(firsts, out, row):
                return firsts.at[row].set(out[0])
            fn = _PREFILL_SCATTER_CACHE["first_token"] = jax.jit(
                p2t_first_token)
        self.firsts = fn(self.firsts, out, np.int32(row))

    @property
    def block_bytes(self) -> int:
        """Bytes one block holds across K+V (or the one latent pool)
        and all layers."""
        return (self.num_layers * self.block_size * sum(self.kv_widths)
                * self.dtype.itemsize)

    def bytes_for_blocks(self, n_blocks: int) -> int:
        return n_blocks * self.block_bytes

    @property
    def state_slot_bytes(self) -> int:
        """Bytes one sequence's slot holds across all state kinds and
        their layers (0 for a model without such state)."""
        return sum(p.size // p.shape[1] * p.dtype.itemsize
                   for p in self.states.values())

    def contiguous_bytes(self, batch: int, max_seq_len: int) -> int:
        """What a contiguous per-request max-seq-len cache would
        reserve for ``batch`` sequences — the paged-vs-contiguous
        comparator the serving bench gates on."""
        return (self.num_layers * batch * max_seq_len
                * sum(self.kv_widths) * self.dtype.itemsize)

    # -- device ops (traced inside the compiled programs) ---------------
    @staticmethod
    def scatter_decode(pool, layer, phys, slot, new_kv):
        """Write one new token per sequence into ONE layer's lane:
        ``pool[layer, phys[b], slot[b]] = new_kv[b]``.
        pool: [L, N, bs, H_kv*D]; phys/slot: int32 [B]; new_kv:
        [B, H_kv, D]. Traced inside the compiled decode program (which
        donates the pool), per layer — the decode loop appends each
        layer's K/V right where it is produced."""
        return pool.at[layer, phys, slot].set(
            new_kv.reshape(new_kv.shape[0], -1))

    @staticmethod
    def scatter_prefill(pool, layer_kv, block_row, n_tokens, block_size,
                        start: int = 0, by_layer: bool = False):
        """Write a prefilled sequence's K/V into its blocks as ONE
        jitted scatter with the pool DONATED — the eager per-page
        ``.at[].set`` loop this replaces copied the ENTIRE pool once
        per page per lane (O(pool x pages) allocator traffic at
        production pool sizes). pool: [L, N, bs, H*D]; layer_kv:
        [L, T, H, D] (T >= n_tokens when the prefill ran padded);
        block_row: int array [n_pages] physical ids. ``start`` skips
        the leading positions — a prefix-cache hit must NOT rewrite
        the shared blocks it reads (their bytes belong to every
        sharer), so only the private tail ``[start, n_tokens)`` is
        scattered. The tiny scatter program is cached per
        (pool, T, start, n_tokens) signature.

        ``by_layer`` writes one layer at a time, the decode append's own
        scatter (``pool[layer, phys, slot] = rows``), which the chip's
        compiler updates in place: the all-layers form above makes two
        whole-pool copies in temporaries 1.6 x the pool (8.1 GB for a
        5 GB latent pool: it does not load beside the weights). The
        latent cache takes it; the K/V pools keep the all-layers form
        until the benchmark can show its repair (ROADMAP S1 / W1)."""
        import jax
        import jax.numpy as jnp
        start = int(start)
        if start >= int(n_tokens):
            return pool
        idx = np.arange(start, int(n_tokens))
        phys = jnp.asarray(np.asarray(block_row)[idx // block_size],
                           jnp.int32)
        slot = jnp.asarray(idx % block_size, jnp.int32)
        key = (tuple(pool.shape), str(pool.dtype),
               tuple(layer_kv.shape), start, int(n_tokens), bool(by_layer))
        from ..profiler import build, launch
        fn = _PREFILL_SCATTER_CACHE.get(key)
        if fn is not None:
            out = fn(pool, layer_kv, phys, slot)
            launch(SCATTER_MODULE)
            return out
        n = int(n_tokens)

        # the function's name is the HLO module's in a device trace
        def p2t_kv_scatter_prefill(p, kv, ph, sl):
            with jax.named_scope("kv_write"):
                rows = kv[:, start:n].reshape(kv.shape[0], n - start, -1)
                if not by_layer:
                    return p.at[:, ph, sl].set(rows)
                for layer in range(rows.shape[0]):
                    p = p.at[layer, ph, sl].set(rows[layer])
                return p

        fn = jax.jit(p2t_kv_scatter_prefill, donate_argnums=(0,))
        if len(_PREFILL_SCATTER_CACHE) > 1024:
            _PREFILL_SCATTER_CACHE.clear()
        _PREFILL_SCATTER_CACHE[key] = fn
        with build("kv_scatter_prefill",
                   f"{layer_kv.shape[1]}:{start}:{n}"):
            out = fn(pool, layer_kv, phys, slot)
        launch(SCATTER_MODULE)
        return out

    @staticmethod
    def copy_block(pool, src: int, dst: int):
        """Device-side CoW tail copy for :meth:`BlockTable.fork`:
        ``pool[:, dst] = pool[:, src]`` across all layers, as one
        jitted donated program (cached per pool signature)."""
        import jax
        import jax.numpy as jnp
        key = ("copy", tuple(pool.shape), str(pool.dtype))
        fn = _PREFILL_SCATTER_CACHE.get(key)
        if fn is None:
            fn = jax.jit(
                lambda p, s, d: p.at[:, d].set(p[:, s]),
                donate_argnums=(0,))
            _PREFILL_SCATTER_CACHE[key] = fn
        return fn(pool, jnp.asarray(int(src), jnp.int32),
                  jnp.asarray(int(dst), jnp.int32))

    @staticmethod
    def gather_dense(pool_layer, block_row, n_pages):
        """Dense [n_pages*bs, H*D] rows of one sequence's K or V via
        its block table — the reference path's gather."""
        import jax.numpy as jnp
        idx = jnp.asarray(block_row[:n_pages], jnp.int32)
        g = pool_layer[idx]                      # [P, bs, H*D]
        return g.reshape((-1,) + g.shape[2:])


class HostKVTier:
    """Pinned-host-DRAM spill tier for cold prefix blocks (ISSUE 16).

    The second rung of the HBM -> host -> peer-DCN KV ladder: when the
    allocator's reclaimer would DISCARD a cold cached prefix block,
    the block's raw K/V bytes are copied here first — keyed by the
    SAME chained prefix-tuple key the :class:`PrefixCache` uses, so a
    later hit on the spilled prefix fetches the bytes back instead of
    re-prefilling. Host entries are BYTES, not allocator block ids:
    the allocator's ownership invariant (free + referenced == usable,
    every block owned exactly once) is untouched by spilling, which is
    what keeps ``rebuild_free_list`` auditable across tiers.

    Every payload is stamped with a CRC at spill time and verified at
    fetch: a scribbled spill (chaos ``corrupt_spill_block``, a real
    host-DMA fault) is DROPPED at fetch, so the consumer falls back to
    re-prefill — corruption can cost time, never correctness. The tier
    keeps its own LRU ledger; ``capacity_blocks`` bounds occupancy
    (oldest spills evicted — the ladder's final discard)."""

    def __init__(self, capacity_blocks: Optional[int] = None):
        # key -> (k_bytes, v_bytes, crc); _lru tracks recency
        self._entries: Dict[tuple, Tuple[np.ndarray, np.ndarray, int]] = {}
        self._lru: "OrderedDict[tuple, None]" = OrderedDict()
        self.capacity_blocks = capacity_blocks
        self.spilled = 0          # put()s (blocks entering the tier)
        self.fetched = 0          # pop()s (blocks promoted back to HBM)
        self.evictions = 0        # LRU discards past capacity
        self.corrupt_drops = 0    # CRC mismatches dropped at get()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries

    @staticmethod
    def _crc(k_np: np.ndarray, v_np: np.ndarray) -> int:
        return zlib.crc32(v_np.tobytes(), zlib.crc32(k_np.tobytes()))

    def put(self, key: tuple, k_np: np.ndarray, v_np: np.ndarray) -> None:
        """Spill one block's K/V bytes under ``key`` (host-owned
        copies; the CRC is stamped from the copies so a later fetch
        verifies exactly what was stored)."""
        k = np.array(k_np, copy=True)
        v = np.array(v_np, copy=True)
        self._entries[key] = (k, v, self._crc(k, v))
        self._lru[key] = None
        self._lru.move_to_end(key)
        self.spilled += 1
        while self.capacity_blocks is not None and \
                len(self._entries) > self.capacity_blocks:
            old, _ = self._lru.popitem(last=False)
            del self._entries[old]
            self.evictions += 1

    def get(self, key: tuple
            ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Verified, NON-destructive read. A CRC mismatch drops the
        entry and returns None — the caller re-prefills; serving a
        scribbled payload would be silent KV corruption."""
        ent = self._entries.get(key)
        if ent is None:
            return None
        k, v, crc = ent
        if self._crc(k, v) != crc:
            del self._entries[key]
            del self._lru[key]
            self.corrupt_drops += 1
            return None
        self._lru.move_to_end(key)
        return k, v

    def pop(self, key: tuple) -> None:
        """Retire ``key`` after a successful promotion back to HBM —
        a prefix lives in exactly one tier at a time."""
        if key in self._entries:
            del self._entries[key]
            del self._lru[key]
            self.fetched += 1

    def keys(self) -> List[tuple]:
        return list(self._entries)

    def corrupt_one(self) -> Optional[tuple]:
        """Chaos helper (``corrupt_spill_block``): flip one byte of
        the OLDEST entry's K payload, keeping the stored CRC — the
        next ``get`` must detect it. Returns the key hit (None when
        the tier is empty). Deterministic: oldest entry, first byte."""
        for key in self._lru:
            k, v, crc = self._entries[key]
            k = np.array(k, copy=True)
            raw = k.view(np.uint8).reshape(-1)
            raw[0] ^= 0xFF
            self._entries[key] = (k, v, crc)
            return key
        return None


def audit_kv_ledger(allocator: BlockAllocator, live_block_lists,
                    prefix_cache: Optional["PrefixCache"] = None,
                    in_migration=(), host_tier: Optional[HostKVTier] = None,
                    live_state_slots=(), state_pools=None) -> Dict[str, int]:
    """Cross-tier ownership audit (ISSUE 16): every usable block is
    owned EXACTLY once — on the free list, or referenced with a
    refcount equal to its claim multiplicity across the live tables,
    the prefix cache's own holds, and any in-migration claim list —
    and ``free + claimed == usable``. Host-tier entries are byte
    payloads, never allocator ids, so they cannot alias device blocks
    by construction; the audit reports their count so the property
    test can close the whole ladder. The state slots close the same
    way: every slot 1..n is free or claimed by exactly one entry of
    ``live_state_slots`` — ONE id for all of a sequence's state kinds,
    so with ``state_pools`` (``PagedKVCache.states``) every kind's pool
    must have exactly the allocator's slots (and the garbage slot).
    Raises :class:`BlockFreeError` on any
    violation; returns the tier census when clean."""
    claims: Dict[int, int] = {}
    lists = [list(l) for l in live_block_lists]
    if prefix_cache is not None:
        lists.append(prefix_cache.held_blocks())
    lists.append(list(in_migration))
    for lst in lists:
        for b in lst:
            b = int(b)
            if b == GARBAGE_BLOCK:
                continue
            claims[b] = claims.get(b, 0) + 1
    free = list(allocator._free)
    usable = allocator.num_blocks - 1
    if len(set(free)) != len(free):
        raise BlockFreeError("free list holds a duplicate id")
    for b in free:
        if not (0 < b < allocator.num_blocks):
            raise BlockFreeError(f"free list holds bad id {b}")
        if b in claims:
            raise BlockFreeError(
                f"block {b} is both free and claimed — owned twice")
    for b, c in claims.items():
        if not (0 < b < allocator.num_blocks):
            raise BlockFreeError(f"claim on out-of-range block {b}")
        if allocator.refcount(b) != c:
            raise BlockFreeError(
                f"block {b}: refcount {allocator.refcount(b)} != claim "
                f"multiplicity {c}")
    for b in np.flatnonzero(allocator.refcounts()).tolist():
        if b not in claims:
            raise BlockFreeError(
                f"block {b} allocated (rc={allocator.refcount(b)}) but "
                f"claimed by no table, cache, or migration")
    if len(free) + len(claims) != usable:
        raise BlockFreeError(
            f"ledger does not close: {len(free)} free + {len(claims)} "
            f"claimed != {usable} usable")
    slots = [int(x) for x in live_state_slots]
    free_slots = list(allocator._free_slots)
    if sorted(slots + free_slots) != list(range(1, allocator.state_slots
                                                + 1)):
        raise BlockFreeError(
            f"state slots do not close: claimed {sorted(slots)}, free "
            f"{sorted(free_slots)}, of {allocator.state_slots}")
    for name, pool in (state_pools or {}).items():
        if pool.shape[1] != allocator.state_slots + 1:
            raise BlockFreeError(
                f"state kind {name!r} has {pool.shape[1] - 1} slots, the "
                f"allocator hands out {allocator.state_slots}")
    return {"free": len(free), "claimed": len(claims),
            "host_tier": len(host_tier) if host_tier is not None else 0,
            "in_migration": len(list(in_migration)),
            "state_slots_free": len(free_slots),
            "state_slots_claimed": len(slots),
            "state_kinds": len(state_pools or {})}


class PrefixCache:
    """Content-addressed cache of full prompt-prefix blocks (CoW
    prefix sharing, the vLLM automatic-prefix-caching design).

    Keying: block ``i`` of a prompt is cached under the TUPLE of the
    first ``(i+1) * block_size`` tokens — a chain of prefix keys, so a
    block's identity includes everything before it (the same 16 tokens
    after two different prefixes hold DIFFERENT KV — position and
    history are baked into the values). KV at a position depends only
    on the tokens at and before it, so any request whose prompt starts
    with a cached prefix can share those blocks bit-exactly.

    Reference discipline: the cache holds its OWN reference on every
    cached block (``share`` at insert), so cached KV survives its
    inserting sequence. A block whose only reference is the cache's
    (refcount 1) is *reclaimable*; the allocator's reclaimer hook
    LRU-evicts exactly as many as a starved ``allocate`` needs. Blocks
    still shared with live sequences (refcount > 1) are NEVER
    reclaimed — eviction of a shared block is deferred until its last
    sequence releases it.
    """

    def __init__(self, allocator: BlockAllocator,
                 max_blocks: Optional[int] = None,
                 host_tier: Optional[HostKVTier] = None):
        self._alloc = allocator
        self.block_size = allocator.block_size
        # prefix-key tuple -> block id; _lru tracks use recency for
        # reclaim order (oldest first)
        self._entries: Dict[tuple, int] = {}
        self._lru: "OrderedDict[tuple, int]" = OrderedDict()
        self.max_blocks = max_blocks
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # ISSUE 16 tiering: host-DRAM spill tier + the device-byte I/O
        # hooks (engine-installed: gather(block) -> (k, v) host arrays,
        # scatter(block, k, v) writes them back) and an optional peer
        # source (fleet-installed: missing keys -> payloads + modeled
        # DCN seconds). All None = PR 13 HBM-only behavior.
        self.host_tier = host_tier
        self._gather = None
        self._scatter = None
        self._peer_fetch = None
        self.host_fetches = 0
        self.peer_fetches = 0
        self.spills = 0
        # per-lookup attribution for the admission path's stall
        # accounting (the engine charges spill_fetch_s from these)
        self.last_host_fetched = 0
        self.last_peer_fetched = 0
        self.last_peer_fetch_s = 0.0
        allocator.set_reclaimer(self)

    def set_spill_io(self, gather, scatter) -> None:
        """Install the device-byte movers the spill tier rides on:
        ``gather(block) -> (k_np, v_np)`` and
        ``scatter(block, k_np, v_np)`` (the engine owns the pools —
        they are reassigned after every donated program, so the cache
        must go through closures, not a pool reference)."""
        self._gather = gather
        self._scatter = scatter

    def set_peer_source(self, fetch) -> None:
        """Install the fleet's peer tier: ``fetch(missing_keys) ->
        (payloads, modeled_seconds)`` returns device bytes for a
        leading run of ``missing_keys`` from ONE peer over DCN — or
        ``([], 0.0)`` when no peer holds them or the modeled transfer
        loses to modeled re-prefill (the registry owns that cost-model
        decision)."""
        self._peer_fetch = fetch

    def __len__(self) -> int:
        return len(self._entries)

    def _keys(self, tokens) -> List[tuple]:
        bs = self.block_size
        return [tuple(tokens[:(i + 1) * bs])
                for i in range(len(tokens) // bs)]

    # -- lookup / insert -------------------------------------------------
    def lookup(self, tokens, share: bool = True
               ) -> Tuple[List[int], int]:
        """Longest cached block-aligned prefix of ``tokens`` ->
        ``(blocks, n_cached_tokens)``. With ``share=True`` (the commit
        path) every returned block gains this sequence's reference and
        the hit/miss ledger advances; ``share=False`` peeks (admission
        feasibility checks)."""
        keys = self._keys(tokens)
        blocks: List[int] = []
        for key in keys:
            b = self._entries.get(key)
            if b is None:
                break
            blocks.append(b)
            if share:
                self._lru.move_to_end(key)
        self.last_host_fetched = 0
        self.last_peer_fetched = 0
        self.last_peer_fetch_s = 0.0
        if share:
            if blocks:
                # share the HBM chain FIRST: the fetch loops below
                # allocate, which may trigger reclaim — the
                # requester's references pin these blocks (refcount 2)
                # so the reclaimer cannot evict them mid-lookup
                self._alloc.share(blocks)
            self._fetch_host(keys, blocks)
            self._fetch_peer(keys, blocks)
            if blocks:
                self.hits += 1
            else:
                self.misses += 1
        return blocks, len(blocks) * self.block_size

    def _adopt_fetched(self, key: tuple, payload) -> Optional[int]:
        """Promote one fetched payload into a fresh HBM block owned by
        the cache (allocate's reference) AND shared to the requester.
        Returns the block id, or None when the pool cannot cover it
        (the caller stops fetching — re-prefill covers the rest)."""
        if self._scatter is None:
            return None
        try:
            nb = self._alloc.allocate(1)[0]
        except OutOfBlocksError:
            return None
        self._scatter(nb, payload[0], payload[1])
        self._entries[key] = nb
        self._lru[key] = nb
        self._alloc.share([nb])
        return nb

    def _fetch_host(self, keys: List[tuple], blocks: List[int]) -> int:
        """Extend a commit-path lookup's chain from the host tier:
        verified payloads are scattered back into fresh HBM blocks
        (spill-tier promotion). Stops at the first miss, CRC drop, or
        allocation failure — everything past that re-prefills."""
        if self.host_tier is None:
            return 0
        fetched = 0
        for key in keys[len(blocks):]:
            payload = self.host_tier.get(key)
            if payload is None:
                break
            nb = self._adopt_fetched(key, payload)
            if nb is None:
                break
            self.host_tier.pop(key)
            blocks.append(nb)
            fetched += 1
        self.host_fetches += fetched
        self.last_host_fetched = fetched
        return fetched

    def _fetch_peer(self, keys: List[tuple], blocks: List[int]) -> int:
        """Extend the chain from a peer engine over DCN (the fleet
        registry's cost-model decision already chose transfer over
        re-prefill when this returns payloads)."""
        if self._peer_fetch is None:
            return 0
        missing = keys[len(blocks):]
        if not missing:
            return 0
        payloads, seconds = self._peer_fetch(missing)
        if not payloads:
            return 0
        fetched = 0
        for key, payload in zip(missing, payloads):
            nb = self._adopt_fetched(key, payload)
            if nb is None:
                break
            blocks.append(nb)
            fetched += 1
        if fetched:
            self.peer_fetches += fetched
            self.last_peer_fetched = fetched
            # a partial promotion pays for the blocks it landed
            self.last_peer_fetch_s = float(seconds) * (fetched
                                                       / len(payloads))
        return fetched

    def cached_prefix_tokens(self, tokens) -> int:
        """Read-only: the longest block-aligned prefix of ``tokens``
        servable WITHOUT recompute from this engine's tiers (HBM chain
        + host-tier extension). No references taken, no fetches — the
        prefix-affinity router and the peer advertisement both consult
        this."""
        n = 0
        for key in self._keys(tokens):
            if key in self._entries or (self.host_tier is not None
                                        and key in self.host_tier):
                n += 1
            else:
                break
        return n * self.block_size

    def export_chain(self, keys: List[tuple]
                     ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Gather the payload bytes for a leading run of ``keys`` this
        engine holds (HBM first, then host tier) — the peer-fetch /
        migration SOURCE side. Stops at the first miss or corrupt
        spill. Copies leave the local tiers untouched."""
        out: List[Tuple[np.ndarray, np.ndarray]] = []
        for key in keys:
            b = self._entries.get(key)
            if b is not None and self._gather is not None:
                out.append(self._gather(b))
                continue
            payload = (self.host_tier.get(key)
                       if self.host_tier is not None else None)
            if payload is None:
                break
            out.append(payload)
        return out

    def insert(self, tokens, blocks: List[int],
               n_prefix_tokens: Optional[int] = None) -> int:
        """Register the FULL blocks covering ``tokens[:n_prefix]``
        (default: the whole list) from a just-prefilled table. Already
        -cached prefixes are skipped (the owning sequence simply keeps
        its private copy — correct either way, the cached block serves
        future lookups). Each newly cached block gains the cache's own
        reference. Returns how many blocks were newly cached."""
        n = len(tokens) if n_prefix_tokens is None \
            else min(int(n_prefix_tokens), len(tokens))
        added = 0
        for i, key in enumerate(self._keys(list(tokens)[:n])):
            if key in self._entries:
                continue
            b = int(blocks[i])
            self._alloc.share([b])
            self._entries[key] = b
            self._lru[key] = b
            added += 1
        if self.max_blocks is not None and len(self._entries) > \
                self.max_blocks:
            self.reclaim(len(self._entries) - self.max_blocks)
        return added

    # -- accounting ------------------------------------------------------
    def held_blocks(self) -> List[int]:
        """Every block the cache itself holds a reference on — ONE
        claim list for ``rebuild_free_list`` (the cache is a survivor
        too)."""
        return list(self._entries.values())

    def holds(self, block: int) -> bool:
        return int(block) in set(self._entries.values())

    def shared_bytes(self, block_bytes: int) -> int:
        """KV bytes currently deduplicated: for every cached block,
        each reference beyond the first would have been a private copy
        without the cache."""
        return sum(max(self._alloc.refcount(b) - 1, 0)
                   for b in self._entries.values()) * int(block_bytes)

    # -- reclaim (the allocator hook) ------------------------------------
    def reclaimable(self) -> int:
        """Blocks the cache could hand back RIGHT NOW: cached blocks
        whose only reference is the cache's own."""
        return sum(1 for b in self._entries.values()
                   if self._alloc.refcount(b) == 1)

    def reclaim(self, n: int) -> int:
        """Evict up to ``n`` reclaimable blocks, least-recently-used
        first; blocks still shared with live sequences are skipped
        (deferred until their last release). With a host tier wired
        (ISSUE 16) eviction prefers SPILL over discard: the block's
        bytes move to host DRAM under the same prefix key before the
        HBM block returns to the free list, so cache pressure degrades
        to a fetch, not a recompute. Returns how many were actually
        freed."""
        if n <= 0:
            return 0
        freed = 0
        for key in list(self._lru.keys()):
            if freed >= n:
                break
            b = self._entries[key]
            if self._alloc.refcount(b) != 1:
                continue
            if self.host_tier is not None and self._gather is not None:
                k_np, v_np = self._gather(b)
                self.host_tier.put(key, k_np, v_np)
                self.spills += 1
            del self._entries[key]
            del self._lru[key]
            self._alloc.free([b])
            self.evictions += 1
            freed += 1
        return freed
