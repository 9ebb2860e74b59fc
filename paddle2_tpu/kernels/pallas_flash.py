"""Pallas TPU flash attention, forward + backward.

Replaces the reference's CUDA flash kernels
(paddle/phi/kernels/gpu/flash_attn_kernel.cu, third_party/flashattn) with
TPU-native kernels that never write the score matrix to HBM:

- the WALK (``_fwd_walk_kernel`` / ``_bwd_walk_kernel``), taken whenever
  a head's Q, K, V (and dO, O, dQ, dK, dV) fit VMEM: grid (B, H), one
  step a (batch row, head), the tiles of the score matrix walked INSIDE
  the step by a statically unrolled loop (no grid step a tile). The
  forward walks q tiles: a tile's rows meet the strip of columns their
  mask lets them see in ONE QK^T, take a direct softmax over it (the
  strip is resident, so no running maximum) and ONE PV. The backward is
  ONE fused call that walks k tiles: a tile's columns meet the strip of
  rows that see them, one QK^T, one dO V^T and one exp a tile, dK and dV
  of the tile complete when it ends, dQ accumulated across tiles in
  VMEM. Tiles wholly above the staircase are never touched, tiles wholly
  under it take NO mask, only the tiles the staircase crosses build one
  (``_row_strips`` / ``_col_strips``). lse crosses HBM as one f32 row a
  head, (B, H, 1, S), turned between row and column in the kernel;
  delta = rowsum(dO * O) is computed in the backward from the tiles it
  holds.
- the GRID kernels, for sequences whose heads do not fit VMEM (and for
  Sq > Sk under a causal mask, where rows see nothing): forward grid
  (B, H, nq, nk) with the k-axis innermost, a VMEM scratch accumulator
  carrying (o_acc, row-max m, row-sum l) across k steps; backward in two
  kernels (flash-2 split), dK/dV with the q-axis innermost, dQ with the
  k-axis innermost, both seeded by delta. Tiles wholly above the
  staircase are skipped via pl.when; lse/delta ride in (…, Sq, 128)-lane
  f32 buffers there.
- with Sq != Sk the diagonal is bottom-right aligned (flash-attn v2.1,
  the XLA fallback); the values' width may differ from q/k's (forward).
- the causal mask is BLOCK-causal by a static block length: inside the
  kernels ``causal`` is that length (0: no mask, 1: plain causal, a
  power of two B: a row sees the columns of its own block of B positions
  whole, later ones included, and of every earlier block — the mask a
  block-diffusion decoder prefills under). Forward and backward both.

Two entries over the one ``_flash``: ``flash_attention_bshd`` takes the
reference flash API's (batch, seq, heads, dim) and swaps axes around the
kernels; ``flash_attention_bhsd`` takes the kernels' own (batch, heads,
seq, dim) as it comes. Compute is f32 on the MXU (bf16 in, f32 softmax).

Every ``pallas_call`` has a ``name=``: a device trace shows the kernel
under it, and the benchmark's readers find it by it. A kernel's Mosaic
payload holds the file paths and line numbers of its body AND of its call
sites, and is in JAX's compile-cache key: an edit that moves lines above
a kernel here, or above the call in ``kernels/attention.py``, makes each
program holding it miss the cache once (a ``jax.named_scope`` does not).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._platform import interpret_default

# Upper bounds on a tile's rows and columns, for both kinds of kernel
# (the walk's own tile is *_WALK_TILE or this, whichever is smaller; the
# grid's tile is this). Env-overridable for per-chip tuning
# (incubate.autotune searches these).
import os as _os
DEFAULT_BLOCK_Q = int(_os.environ.get("FLAGS_flash_block_q", 1024))
DEFAULT_BLOCK_K = int(_os.environ.get("FLAGS_flash_block_k", 1024))
# backward kernels may prefer different tiles than forward
BWD_BLOCK_Q = int(_os.environ.get("FLAGS_flash_bwd_block_q", 0)) or None
BWD_BLOCK_K = int(_os.environ.get("FLAGS_flash_bwd_block_k", 0)) or None
# The walk's tile (rows of a q tile, columns of a k tile), by direction.
# Read on a v5e at B 8 / S 1024 / H 16 / D 64 (PERF.md section 6, PR 33;
# the same order held at D 128 / S 2048 and at S 3072): a K or V tile
# held in the MXU is paid for by the rows that stream past it, so the
# forward, whose tiles stream a q tile's rows, does best at 512 (12 of
# the square's 16 tiles of 256) and loses at 128 though that does 9 of
# 16; the backward's tiles stream whole strips of rows and do best at
# 256 (10 of 16).
FWD_WALK_TILE = 512
BWD_WALK_TILE = 256
# What a walk's step may hold in VMEM by `_walk_bytes`' reckoning (a v5e
# core has 128 MiB); beyond it the grid kernels run. The compiler is
# given twice that as its limit: the reckoning leaves Mosaic's own
# temporaries out.
WALK_VMEM_BYTES = 32 << 20
NEG_INF = float("-inf")


def _fit_block(s: int, want: int):
    """Largest power-of-two block <= `want` that divides `s`, or None when
    no 8-row-aligned tiling exists. Requested block sizes are preferences,
    never correctness hazards: every divisible S gets a valid grid."""
    b = 1 << (min(want, s).bit_length() - 1)
    while b >= 8:
        if s % b == 0:
            return b
        b //= 2
    return None


def _last_col(row, block):
    """The last column a (bottom-right aligned) row sees under the
    causal mask of block length ``block``: itself, or the end of its
    block (``block`` a power of two). Scalars and iotas alike."""
    return row if block == 1 else row | (block - 1)


def _causal_keep(rows, cols, block):
    return _last_col(rows, block) >= cols


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.DEFAULT)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b
_TN = ((0,), (0,))      # a.T @ b


def _online_softmax_step(s, v, acc, m_sc, l_sc):
    """Shared flash-fwd tile update: online softmax recurrence over the
    masked score tile `s` (NEG_INF = masked). Mutates acc/m_sc/l_sc."""
    m_prev = m_sc[:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(s - safe_m)
    p = jnp.where(s == NEG_INF, 0.0, p)
    alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - safe_m))
    l_new = alpha * l_sc[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc[:] = acc[:] * alpha + _dot(p.astype(v.dtype), v, _NN)
    m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
    l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)


def _flash_finalize(o_ref, lse_ref, acc, m_sc, l_sc):
    """Shared flash-fwd epilogue: normalize and emit (o, lse)."""
    l = l_sc[:, :1]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc[:] / safe_l).astype(o_ref.dtype)
    m = m_sc[:, :1]
    lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(safe_l))
    lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _scores(q, k, scale):
    return _dot(q, k, _NT) * scale


def _bwd_p_ds(s, lse, delta, do, v):
    """Shared flash-bwd tile math of the grid kernels: probabilities p
    and score cotangent ds from the masked tile `s` and saved (lse,
    delta), rows that see nothing guarded."""
    p = jnp.exp(s - jnp.where(lse == NEG_INF, 0.0, lse))
    p = jnp.where((s == NEG_INF) | (lse == NEG_INF), 0.0, p)
    dp = _dot(do, v, _NT)
    return p, p * (dp - delta)


# ------------------------------------------------------------- the walk

def _tile_kind(r0, r1, c0, c1, block, offset):
    """What the (block-)causal mask does to the tile of rows r0..r1 and
    columns c0..c1 (inclusive; the diagonal bottom-right aligned by
    ``offset``): 0 every entry masked, 1 the staircase crosses it,
    2 no entry masked. A row sees columns 0.._last_col(row), and that
    grows with the row: the tile's corners decide."""
    if not block or _last_col(r0 + offset, block) >= c1:
        return 2
    return 1 if _last_col(r1 + offset, block) >= c0 else 0


def _row_strips(Sq, Sk, tq, tk, block, offset):
    """The forward walk's plan. Per q tile (lo, hi): columns [0, lo) are
    seen by every row of the tile, [lo, hi) by some — the tiles the
    staircase crosses —, [hi, Sk) by none. Kinds only fall along a row
    of tiles, so counting them places the two edges."""
    out = []
    for r0 in range(0, Sq, tq):
        kinds = [_tile_kind(r0, r0 + tq - 1, c0, c0 + tk - 1, block, offset)
                 for c0 in range(0, Sk, tk)]
        out.append((kinds.count(2) * tk, (len(kinds) - kinds.count(0)) * tk))
    return out


def _col_strips(Sq, Sk, tq, tk, block, offset):
    """The backward walk's plan. Per k tile (lo, hi): rows [0, lo) see
    none of the tile's columns, [lo, hi) some, [hi, Sq) all. Kinds only
    rise down a column of tiles."""
    out = []
    for c0 in range(0, Sk, tk):
        kinds = [_tile_kind(r0, r0 + tq - 1, c0, c0 + tk - 1, block, offset)
                 for r0 in range(0, Sq, tq)]
        out.append((kinds.count(0) * tq, (len(kinds) - kinds.count(2)) * tq))
    return out


def _masked(s, rows0, cols0, block):
    """The score tile ``s`` (its first row and column at rows0 — offset
    included — and cols0) under the mask: NEG_INF where not seen."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + rows0
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + cols0
    return jnp.where(_causal_keep(rows, cols, block), s, NEG_INF)


def _fold_scale(scale):
    """(factor for the q tile, factor for the f32 scores): a power of
    two folds into the bf16 q tile exactly (an XLA-side pre-scale would
    cost a full extra HBM pass on q); any other scale stays on the f32
    scores, where it rounds nothing."""
    return (scale, 1.0) if math.frexp(scale)[0] == 0.5 else (1.0, scale)


def _times(x, factor):
    return x if factor == 1.0 else x * jnp.asarray(factor, x.dtype)


def _walk_bytes(Sq, Sk, D, itemsize, tq, tk, backward, Dv=None):
    """VMEM a walk's step holds, roughly: the head's operands and
    results double-buffered (D and the values' Dv padded to 128 lanes),
    the backward's three f32 scratches, the widest strip's temporaries."""
    lanes, v_lanes = (-(-d // 128) * 128 for d in (D, Dv or D))
    if backward:
        io = 2 * (4 * Sq + 4 * Sk) * lanes * itemsize
        return io + 3 * Sq * 128 * 4 + Sq * tk * (16 + 2 * itemsize)
    io = 2 * (Sq + Sk) * (lanes + v_lanes) * itemsize
    return io + tq * Sk * (8 + itemsize)


def _walks(Sq, Sk, D, dtype, causal, block_q, block_k, backward, Dv=None):
    """The walk's tiles (rows of a q tile, columns of a k tile: the
    direction's tile, no more than the caller's bound, fitted to the
    sequence) if this shape takes the walk, else None: every row sees a
    column (causal with Sq > Sk leaves rows that see none: the grid
    kernels guard those), tiles the chip can slice and turn
    (lane-aligned, or the whole axis), and the head fits VMEM."""
    tile = BWD_WALK_TILE if backward else FWD_WALK_TILE
    tq = _fit_block(Sq, min(block_q, tile))
    tk = _fit_block(Sk, min(block_k, tile))
    fits = _walk_bytes(Sq, Sk, D, jnp.dtype(dtype).itemsize, tq, tk,
                       backward, Dv) <= WALK_VMEM_BYTES
    aligned = (tq % 128 == 0 or tq == Sq) and (tk % 128 == 0 or tk == Sk)
    return (tq, tk) if fits and aligned and (
        not causal or Sk >= Sq) else None


def _walk_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=2 * WALK_VMEM_BYTES)


def _lanes(x, n):
    """(rows, 128) with every lane of a row alike -> (rows, n): whole
    copies side by side where n allows it (a slice of one lane would be
    spread again by a permute a vreg), else the one lane broadcast."""
    if n % 128:
        return x[:, :1]
    return x if n == 128 else jnp.concatenate([x] * (n // 128), axis=1)


def _col_to_row(x):
    """(n, 1) f32 -> (1, n): through the transpose unit, 128 lanes wide."""
    return jnp.broadcast_to(x, (x.shape[0], 128)).T[:1]


def _row_to_cols(x):
    """(1, n) f32 -> (n, 128), row i holding x[0, i] in every lane."""
    return jnp.broadcast_to(x, (128, x.shape[1])).T


# ---------------------------------------------------------------- forward

def _fwd_walk_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                     offset, tq, tk):
    """One (batch row, head): q tiles walked in the step, each against
    the strip of columns it sees — one QK^T, a direct softmax (the row
    max is finite: every row sees a column), one PV. Only the strip's
    tail [lo, hi), which the staircase crosses, is masked. A sequence of
    one tile is one strip: the plain softmax over the masked square."""
    Sq, Sk = q_ref.shape[2], k_ref.shape[2]
    q_scale, s_scale = _fold_scale(scale)
    strips = _row_strips(Sq, Sk, tq, tk, causal, offset)
    for i, (lo, hi) in enumerate(strips):
        rows = slice(i * tq, (i + 1) * tq)
        q = _times(q_ref[0, 0, rows], q_scale)
        s = _times(_dot(q, k_ref[0, 0, :hi], _NT), s_scale)
        if hi > lo:
            tail = _masked(s[:, lo:], i * tq + offset, lo, causal)
            s = jnp.concatenate([s[:, :lo], tail], axis=1) if lo else tail
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)                # masked: exp(-inf - finite) = 0
        l = jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0, :hi]
        o = _dot(p.astype(v.dtype), v, _NN)
        o_ref[0, 0, rows] = (o / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, rows] = _col_to_row(m + jnp.log(l))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc,
                *, scale, causal, block_q, block_k, nk, offset):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    q_start = iq * block_q
    k_start = ik * block_k
    run = True
    if causal:
        # skip tiles entirely above the (bottom-right aligned) staircase
        run = k_start <= _last_col(q_start + offset + block_q - 1, causal)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]                              # (Bq, D) native dtype
        k = k_ref[0, 0]                              # (Bk, D)
        v = v_ref[0, 0]                              # (Bk, D)
        # native-dtype (bf16) MXU matmul with f32 accumulation — casting the
        # operands to f32 would fall off the MXU fast path (~8x slower)
        s = _scores(q, k, scale)                      # (Bq, Bk) f32
        if causal:
            s = _masked(s, q_start + offset, k_start, causal)
        _online_softmax_step(s, v, acc, m_sc, l_sc)

    @pl.when(ik == nk - 1)
    def _finalize():
        _flash_finalize(o_ref, lse_ref, acc, m_sc, l_sc)


def _clamp_blocks_for_dtype(dtype, block_q, block_k):
    """Non-bf16 inputs double the VMEM a tile needs: the 1024x1024
    defaults that fit bf16 blow the scoped-vmem budget for f32 (compile
    fails with a stack OOM). Halve the blocks for >=4-byte dtypes."""
    if jnp.dtype(dtype).itemsize >= 4:
        return min(block_q, 512), min(block_k, 512)
    return block_q, block_k


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    """q,k: (B, H, S, D), v: (B, H, Sk, Dv) — returns (o, lse)."""
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    block_q, block_k = _clamp_blocks_for_dtype(q.dtype, block_q, block_k)
    walk = _walks(Sq, Sk, D, q.dtype, causal, block_q, block_k, False, Dv)
    if walk:
        spec_q, spec_k, spec_v, spec_o = (pl.BlockSpec(
            (1, 1, s, d), lambda b, h: (b, h, 0, 0)) for s, d in (
            (Sq, D), (Sk, D), (Sk, Dv), (Sq, Dv)))
        o, lse = pl.pallas_call(
            functools.partial(_fwd_walk_kernel, scale=scale, causal=causal,
                              offset=Sk - Sq, tq=walk[0], tk=walk[1]),
            grid=(B, H),
            in_specs=[spec_q, spec_k, spec_v],
            out_specs=[spec_o, pl.BlockSpec((1, 1, 1, Sq),
                                            lambda b, h: (b, h, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
                       jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32)],
            compiler_params=_walk_params(),
            name="flash_fwd",
            interpret=interpret,
        )(q, k, v)
        return o, lse[:, :, 0]

    bq, bk = _fit_block(Sq, block_q), _fit_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, nk=nk,
                               offset=Sk - Sq)
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 128), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return o, lse[..., 0]


# --------------------------------------------------------------- backward

def _bwd_walk_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                     dq_ref, dk_ref, dv_ref, dq_acc, lse_sc, delta_sc,
                     *, scale, causal, offset, tq, tk):
    """One (batch row, head), dQ, dK and dV from ONE score/probability
    computation a tile (the two-kernel flash-2 split recomputes scores
    twice: measured 2.45 -> 1.70 ms/layer at the GPT bench shape on v5e
    when the single-tile fused body replaced it). k tiles are walked in
    the step, each against the strip of rows that see it: the strip's
    head [lo, hi), which the staircase crosses, is masked, the rest is
    not. dK and dV of a tile are whole when its strip is done; dQ adds
    up across tiles in VMEM. Every row sees a column, so lse is finite
    and no masked-row guards are needed."""
    Sq, Sk = q_ref.shape[2], k_ref.shape[2]
    q_scale, s_scale = _fold_scale(scale)
    # the rows' two statistics as columns alike in every lane, once a head
    lse_sc[:] = _row_to_cols(lse_ref[0, 0])
    delta_sc[:] = jnp.broadcast_to(jnp.sum(
        do_ref[0, 0].astype(jnp.float32) * o_ref[0, 0].astype(jnp.float32),
        axis=1, keepdims=True), delta_sc.shape)
    strips = _col_strips(Sq, Sk, tq, tk, causal, offset)
    for j, (lo, hi) in enumerate(strips):
        cols = slice(j * tk, (j + 1) * tk)
        k = k_ref[0, 0, cols]
        q = _times(q_ref[0, 0, lo:], q_scale)
        do = do_ref[0, 0, lo:]
        s = _times(_dot(q, k, _NT), s_scale)
        if hi > lo:
            head = _masked(s[:hi - lo], lo + offset, j * tk, causal)
            s = jnp.concatenate([head, s[hi - lo:]], axis=0) \
                if hi < Sq else head
        p = jnp.exp(s - _lanes(lse_sc[lo:], tk))  # masked: exp(-inf) = 0
        dp = _dot(do, v_ref[0, 0, cols], _NT)
        ds = (p * (dp - _lanes(delta_sc[lo:], tk))).astype(q.dtype)
        dv_ref[0, 0, cols] = _dot(p.astype(do.dtype), do, _TN).astype(
            dv_ref.dtype)
        dk_ref[0, 0, cols] = _times(_dot(ds, q, _TN), s_scale).astype(
            dk_ref.dtype)
        if j:
            dq_acc[lo:] += _dot(ds, k, _NN)
        else:               # every row sees the first tile: lo is 0
            dq_acc[:] = _dot(ds, k, _NN)
    dq_ref[0, 0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, block_q, block_k, nq, offset):
    iq = pl.program_id(3)
    ik = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = iq * block_q
    k_start = ik * block_k
    run = True
    if causal:
        run = _last_col(q_start + offset + block_q - 1, causal) >= k_start

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]                                 # (Bq, D)
        k = k_ref[0, 0]                                 # (Bk, D)
        v = v_ref[0, 0]                                 # (Bk, D)
        do = do_ref[0, 0]                               # (Bq, D)
        lse = lse_ref[0, 0][:, :1]                      # (Bq, 1)
        delta = delta_ref[0, 0][:, :1]                  # (Bq, 1)
        s = _scores(q, k, scale)                       # (Bq, Bk)
        if causal:
            s = _masked(s, q_start + offset, k_start, causal)
        p, ds = _bwd_p_ds(s, lse, delta, do, v)
        # dV += P^T dO ; dK += dS^T Q * scale
        dv_acc[:] += _dot(p.astype(do.dtype), do, _TN)
        dk_acc[:] += _dot(ds.astype(q.dtype), q, _TN) * scale

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc,
                   *, scale, causal, block_q, block_k, nk, offset):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = iq * block_q
    k_start = ik * block_k
    run = True
    if causal:
        run = k_start <= _last_col(q_start + offset + block_q - 1, causal)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = _scores(q, k, scale)
        if causal:
            s = _masked(s, q_start + offset, k_start, causal)
        _p, ds = _bwd_p_ds(s, lse, delta, do, v)
        dq_acc[:] += _dot(ds.astype(k.dtype), k, _NN) * scale

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k,
               interpret):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_q = BWD_BLOCK_Q or block_q
    block_k = BWD_BLOCK_K or block_k
    block_q, block_k = _clamp_blocks_for_dtype(q.dtype, block_q, block_k)
    walk = _walks(Sq, Sk, D, q.dtype, causal, block_q, block_k, True)
    if walk:
        spec_q = pl.BlockSpec((1, 1, Sq, D), lambda b, h: (b, h, 0, 0))
        spec_k = pl.BlockSpec((1, 1, Sk, D), lambda b, h: (b, h, 0, 0))
        spec_r = pl.BlockSpec((1, 1, 1, Sq), lambda b, h: (b, h, 0, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_walk_kernel, scale=scale, causal=causal,
                              offset=Sk - Sq, tq=walk[0], tk=walk[1]),
            grid=(B, H),
            in_specs=[spec_q, spec_k, spec_k, spec_q, spec_q, spec_r],
            out_specs=[spec_q, spec_k, spec_k],
            out_shape=[jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
                       jax.ShapeDtypeStruct((B, H, Sk, D), k.dtype),
                       jax.ShapeDtypeStruct((B, H, Sk, D), v.dtype)],
            scratch_shapes=[pltpu.VMEM((Sq, D), jnp.float32),
                            pltpu.VMEM((Sq, 128), jnp.float32),
                            pltpu.VMEM((Sq, 128), jnp.float32)],
            compiler_params=_walk_params(),
            name="flash_bwd",
            interpret=interpret,
        )(q, k, v, o, do, lse[:, :, None])
        return dq, dk, dv

    bq, bk = _fit_block(Sq, block_q), _fit_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                              # (B, H, Sq)
    lse_b = jnp.broadcast_to(lse[..., None], (B, H, Sq, 128))
    delta_b = jnp.broadcast_to(delta[..., None], (B, H, Sq, 128))

    q_spec_kmaj = pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, ik, iq: (b, h, iq, 0))
    k_spec_kmaj = pl.BlockSpec((1, 1, bk, D),
                               lambda b, h, ik, iq: (b, h, ik, 0))
    r_spec_kmaj = pl.BlockSpec((1, 1, bq, 128),
                               lambda b, h, ik, iq: (b, h, iq, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nq=nq, offset=Sk - Sq),
        grid=(B, H, nk, nq),
        in_specs=[q_spec_kmaj, k_spec_kmaj, k_spec_kmaj, q_spec_kmaj,
                  r_spec_kmaj, r_spec_kmaj],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse_b, delta_b)

    q_spec_qmaj = pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, iq, ik: (b, h, iq, 0))
    k_spec_qmaj = pl.BlockSpec((1, 1, bk, D),
                               lambda b, h, iq, ik: (b, h, ik, 0))
    r_spec_qmaj = pl.BlockSpec((1, 1, bq, 128),
                               lambda b, h, iq, ik: (b, h, iq, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nk=nk, offset=Sk - Sq),
        grid=(B, H, nq, nk),
        in_specs=[q_spec_qmaj, k_spec_qmaj, k_spec_qmaj, q_spec_qmaj,
                  r_spec_qmaj, r_spec_qmaj],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse_b, delta_b)
    return dq, dk, dv


# -------------------------------------------------------------- public API

def supported(q_shape, k_shape, block_q=DEFAULT_BLOCK_Q,
              block_k=DEFAULT_BLOCK_K) -> bool:
    """Kernel shape constraints (reference flash_attn has analogous ones).
    Block sizes self-fit to the sequence (largest divisor), so any S with
    an 8-row-aligned tiling is supported regardless of the requested
    blocks — including the backward-block env overrides."""
    B, Sq, H, D = q_shape
    Sk = k_shape[1]
    return (_fit_block(Sq, block_q) is not None
            and _fit_block(Sk, block_k) is not None
            and _fit_block(Sq, BWD_BLOCK_Q or block_q) is not None
            and _fit_block(Sk, BWD_BLOCK_K or block_k) is not None
            and D <= 256 and k_shape[2] == H)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return o


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    # name the residuals so rematerialization policies can pin them:
    # under jax.checkpoint with kernels.attention.remat_policy() the saved
    # (o, lse) let the backward run WITHOUT re-executing the forward
    # pallas kernel (the usual flash-under-remat trap)
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    if v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            "flash attention with value heads of another width than the "
            "query/key heads has a forward only")
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, g, scale, causal,
                            block_q, block_k, interpret)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ------------------------------------------------------------- varlen
# Packed (cu_seqlens) attention: the whole ragged batch stays ONE packed
# [T, H, D] sequence (reference flash_attn_unpadded,
# python/paddle/nn/functional/flash_attention.py:593 — no densify). Each
# row carries a segment id and a causal offset; the mask is
#   same-segment AND k_off <= q_off
# where q_off = local_q_pos + (len_k - len_q) (bottom-right alignment per
# sequence) and k_off = local_k_pos. Tiles whose segment ranges cannot
# intersect are SKIPPED dynamically (pl.when on the loaded id blocks) —
# the varlen analog of the causal triangle skip.

def _mk_varlen_mask(sq, oq, sk, ok):
    # sq/oq: (Bq, 1) int32; sk/ok: (1, Bk) int32 -> (Bq, Bk) bool.
    # 2-D operands throughout: 1-D slices would force Mosaic relayouts
    # that blow the scoped-VMEM budget.
    return (sq == sk) & (ok <= oq)


def _fwd_kernel_varlen(q_ref, k_ref, v_ref, sq_ref, oq_ref, sk_ref, ok_ref,
                       o_ref, lse_ref, acc, m_sc, l_sc, *, scale, nk):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    sq = sq_ref[0, 0][:, :1]          # (Bq, 1)
    sk = sk_ref[0, 0][:1]              # (1, Bk)
    # dynamic tile skip: segments are sorted, so a tile is dead unless
    # [min(sk), max(sk)] intersects [min(sq), max(sq)]
    run = (jnp.min(sk) <= jnp.max(sq)) & (jnp.max(sk) >= jnp.min(sq))

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        oq = oq_ref[0, 0][:, :1]
        ok = ok_ref[0, 0][:1]
        s = _scores(q, k, scale)
        s = jnp.where(_mk_varlen_mask(sq, oq, sk, ok), s, NEG_INF)
        _online_softmax_step(s, v, acc, m_sc, l_sc)

    @pl.when(ik == nk - 1)
    def _finalize():
        _flash_finalize(o_ref, lse_ref, acc, m_sc, l_sc)


def _bwd_dkv_kernel_varlen(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           sq_ref, oq_ref, sk_ref, ok_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *, scale, nq):
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    sq = sq_ref[0, 0][:, :1]          # (Bq, 1)
    sk = sk_ref[0, 0][:1]              # (1, Bk)
    run = (jnp.min(sk) <= jnp.max(sq)) & (jnp.max(sk) >= jnp.min(sq))

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        oq = oq_ref[0, 0][:, :1]
        ok = ok_ref[0, 0][:1]
        s = _scores(q, k, scale)
        s = jnp.where(_mk_varlen_mask(sq, oq, sk, ok), s, NEG_INF)
        p, ds = _bwd_p_ds(s, lse, delta, do, v)
        dv_acc[:] += _dot(p.astype(do.dtype), do, _TN)
        dk_acc[:] += _dot(ds.astype(q.dtype), q, _TN) * scale

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel_varlen(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          sq_ref, oq_ref, sk_ref, ok_ref, dq_ref, dq_acc,
                          *, scale, nk):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    sq = sq_ref[0, 0][:, :1]          # (Bq, 1)
    sk = sk_ref[0, 0][:1]              # (1, Bk)
    run = (jnp.min(sk) <= jnp.max(sq)) & (jnp.max(sk) >= jnp.min(sq))

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        oq = oq_ref[0, 0][:, :1]
        ok = ok_ref[0, 0][:1]
        s = _scores(q, k, scale)
        s = jnp.where(_mk_varlen_mask(sq, oq, sk, ok), s, NEG_INF)
        _p, ds = _bwd_p_ds(s, lse, delta, do, v)
        dq_acc[:] += _dot(ds.astype(k.dtype), k, _NN) * scale

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _lane(x, T):
    """[T] int32 -> [1, 1, T, 128] lane-tiled q-side metadata."""
    return jnp.broadcast_to(x.astype(jnp.int32)[None, None, :, None],
                            (1, 1, T, 128))


def _lane_k(x, T):
    """[T] int32 -> [1, 1, 8, T] sublane-tiled k-side metadata (read as a
    (1, bk) lane-major block — no transpose in the kernel)."""
    return jnp.broadcast_to(x.astype(jnp.int32)[None, None, None, :],
                            (1, 1, 8, T))


def _varlen_fwd(q, k, v, sq, oq, sk, ok, scale, block_q, block_k,
                interpret):
    """q,k,v: (1, H, T, D). Returns (o, lse)."""
    _, H, Tq, D = q.shape
    Tk = k.shape[2]
    bq, bk = _fit_block(Tq, block_q), _fit_block(Tk, block_k)
    nq, nk = Tq // bq, Tk // bk
    q_meta = pl.BlockSpec((1, 1, bq, 128),
                          lambda b, h, iq, ik: (0, 0, iq, 0))
    k_meta = pl.BlockSpec((1, 1, 8, bk),
                          lambda b, h, iq, ik: (0, 0, 0, ik))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_varlen, scale=scale, nk=nk),
        grid=(1, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            q_meta, q_meta, k_meta, k_meta,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 128), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((1, H, Tq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_fwd_varlen",
        interpret=interpret,
    )(q, k, v, _lane(sq, Tq), _lane(oq, Tq), _lane_k(sk, Tk),
      _lane_k(ok, Tk))
    return o, lse[..., 0]


def _varlen_bwd(q, k, v, o, lse, do, sq, oq, sk, ok, scale, block_q,
                block_k, interpret):
    _, H, Tq, D = q.shape
    Tk = k.shape[2]
    block_q = BWD_BLOCK_Q or block_q
    block_k = BWD_BLOCK_K or block_k
    bq, bk = _fit_block(Tq, block_q), _fit_block(Tk, block_k)
    nq, nk = Tq // bq, Tk // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse_b = jnp.broadcast_to(lse[..., None], (1, H, Tq, 128))
    delta_b = jnp.broadcast_to(delta[..., None], (1, H, Tq, 128))
    sq_l, oq_l = _lane(sq, Tq), _lane(oq, Tq)
    sk_l, ok_l = _lane_k(sk, Tk), _lane_k(ok, Tk)

    qm = lambda b, h, ik, iq: (b, h, iq, 0)
    km = lambda b, h, ik, iq: (b, h, ik, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_varlen, scale=scale, nq=nq),
        grid=(1, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), qm), pl.BlockSpec((1, 1, bk, D), km),
            pl.BlockSpec((1, 1, bk, D), km), pl.BlockSpec((1, 1, bq, D), qm),
            pl.BlockSpec((1, 1, bq, 128), qm),
            pl.BlockSpec((1, 1, bq, 128), qm),
            pl.BlockSpec((1, 1, bq, 128),
                         lambda b, h, ik, iq: (0, 0, iq, 0)),
            pl.BlockSpec((1, 1, bq, 128),
                         lambda b, h, ik, iq: (0, 0, iq, 0)),
            pl.BlockSpec((1, 1, 8, bk),
                         lambda b, h, ik, iq: (0, 0, 0, ik)),
            pl.BlockSpec((1, 1, 8, bk),
                         lambda b, h, ik, iq: (0, 0, 0, ik)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, H, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((1, H, Tk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_bwd_dkv_varlen",
        interpret=interpret,
    )(q, k, v, do, lse_b, delta_b, sq_l, oq_l, sk_l, ok_l)

    qn = lambda b, h, iq, ik: (b, h, iq, 0)
    kn = lambda b, h, iq, ik: (b, h, ik, 0)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_varlen, scale=scale, nk=nk),
        grid=(1, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), qn), pl.BlockSpec((1, 1, bk, D), kn),
            pl.BlockSpec((1, 1, bk, D), kn), pl.BlockSpec((1, 1, bq, D), qn),
            pl.BlockSpec((1, 1, bq, 128), qn),
            pl.BlockSpec((1, 1, bq, 128), qn),
            pl.BlockSpec((1, 1, bq, 128),
                         lambda b, h, iq, ik: (0, 0, iq, 0)),
            pl.BlockSpec((1, 1, bq, 128),
                         lambda b, h, iq, ik: (0, 0, iq, 0)),
            pl.BlockSpec((1, 1, 8, bk),
                         lambda b, h, iq, ik: (0, 0, 0, ik)),
            pl.BlockSpec((1, 1, 8, bk),
                         lambda b, h, iq, ik: (0, 0, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((1, H, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_bwd_dq_varlen",
        interpret=interpret,
    )(q, k, v, do, lse_b, delta_b, sq_l, oq_l, sk_l, ok_l)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _flash_varlen(q, k, v, sq, oq, sk, ok, scale, block_q, block_k,
                  interpret):
    o, _ = _varlen_fwd(q, k, v, sq, oq, sk, ok, scale, block_q, block_k,
                       interpret)
    return o


def _flash_varlen_fwd(q, k, v, sq, oq, sk, ok, scale, block_q, block_k,
                      interpret):
    o, lse = _varlen_fwd(q, k, v, sq, oq, sk, ok, scale, block_q, block_k,
                         interpret)
    return o, (q, k, v, o, lse, sq, oq, sk, ok)


def _flash_varlen_bwd(scale, block_q, block_k, interpret, res, g):
    q, k, v, o, lse, sq, oq, sk, ok = res
    dq, dk, dv = _varlen_bwd(q, k, v, o, lse, g, sq, oq, sk, ok, scale,
                             block_q, block_k, interpret)
    return dq, dk, dv, None, None, None, None


_flash_varlen.defvjp(_flash_varlen_fwd, _flash_varlen_bwd)


# eager calls must hit a CACHED jitted entry: rebuilding the pallas_call
# closure per call would re-trace (and re-run the Mosaic compiler) every
# time — jit-per-config gives the C++ dispatch fast path instead
_JIT_CACHE: dict = {}


def _cached_jit(key, builder):
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(builder())
        _JIT_CACHE[key] = fn
    return fn


def flash_attention_varlen_packed(q, k, v, seg_q, off_q, seg_k, off_k,
                                  scale=None, block_q=None, block_k=None,
                                  interpret=None):
    """Packed varlen flash attention.

    q: [Tq, H, D], k/v: [Tk, H, D] packed rows (pad T to a multiple of 8
    with seg id -1 / -2 rows). seg_*: int32 [T] per-row segment ids
    (sorted ascending; padding must use ids that never match). off_*:
    int32 [T] causal offsets — mask keeps (seg equal) & (off_k <= off_q);
    pass off_q = local_q_pos + (len_k - len_q), off_k = local_k_pos for
    per-sequence bottom-right-aligned causal, or off_q = +inf-like large
    values for non-causal. Differentiable (pallas fwd+bwd)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = interpret_default()
    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    cfg = (float(scale), int(block_q), int(block_k), bool(interpret))
    def builder():
        def flash_varlen(q, k, v, sq, oq, sk, ok):
            return jnp.swapaxes(_flash_varlen(
                jnp.swapaxes(q, 0, 1)[None], jnp.swapaxes(k, 0, 1)[None],
                jnp.swapaxes(v, 0, 1)[None], sq, oq, sk, ok, *cfg)[0], 0, 1)
        return flash_varlen
    fn = _cached_jit(("varlen",) + cfg, builder)
    return fn(q, k, v, jnp.asarray(seg_q, jnp.int32),
              jnp.asarray(off_q, jnp.int32),
              jnp.asarray(seg_k, jnp.int32),
              jnp.asarray(off_k, jnp.int32))


def flash_attention_bshd(q, k, v, causal=False, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         interpret=None, causal_block=1):
    """Flash attention on (batch, seq, heads, dim) arrays (reference
    flash_attn qkv layout). Differentiable via the Pallas backward kernels;
    falls back to the XLA path when shapes are unsupported.
    ``causal_block`` (with ``causal``): the block length of the causal
    mask — 1 is plain causal; B > 1, a power of two, lets a position see
    its whole block of B, forward and backward alike."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    causal_block = int(causal_block)
    if not supported(q.shape, k.shape, block_q, block_k):
        from .attention import _sdpa_xla
        return _sdpa_xla(q, k, v, causal=causal, scale=scale,
                         causal_block=causal_block)
    if causal_block < 1 or causal_block & (causal_block - 1):
        raise ValueError(
            f"flash attention masks by blocks of a power of two, not "
            f"{causal_block}")
    if interpret is None:
        interpret = interpret_default()
    # inside the kernels `causal` is the mask's block length, 0 for none
    cfg = (float(scale), causal_block if causal else 0, int(block_q),
           int(block_k), bool(interpret))
    def builder():
        def flash_bshd(q, k, v):
            return jnp.swapaxes(
                _flash(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                       jnp.swapaxes(v, 1, 2), *cfg), 1, 2)
        return flash_bshd
    fn = _cached_jit(("bshd",) + cfg, builder)
    return fn(q, k, v)


def flash_attention_bhsd(q, k, v, causal=False, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         interpret=None, causal_block=1):
    """Flash attention on (batch, heads, seq, dim) arrays: the kernels'
    own layout, for a caller whose projections write head-major and
    whose output projection contracts over (heads, dim) — nothing is
    transposed on the way in or out. Everything else is
    :func:`flash_attention_bshd`'s: the same kernels, backward, block
    arguments, and the XLA path for the shapes it sends there."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    causal_block = int(causal_block)
    if not supported(*((x.shape[0], x.shape[2], x.shape[1], x.shape[3])
                       for x in (q, k)), block_q, block_k):
        from .attention import _sdpa_xla_bhsd
        return _sdpa_xla_bhsd(q, k, v, causal=causal, scale=scale,
                              causal_block=causal_block)
    if causal_block < 1 or causal_block & (causal_block - 1):
        raise ValueError(
            f"flash attention masks by blocks of a power of two, not "
            f"{causal_block}")
    if interpret is None:
        interpret = interpret_default()
    cfg = (float(scale), causal_block if causal else 0, int(block_q),
           int(block_k), bool(interpret))
    def builder():
        def flash_bhsd(q, k, v):
            return _flash(q, k, v, *cfg)
        return flash_bhsd
    fn = _cached_jit(("bhsd",) + cfg, builder)
    return fn(q, k, v)
