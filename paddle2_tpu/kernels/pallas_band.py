"""Pallas TPU kernel for the band of a sliding-window attention layer
over a whole sequence: position i sees ``i - (window - 1) .. i``.

The flash walk's premises (``pallas_flash.py``: every strip of columns
starts at column 0, a whole head resident, one head a step, a backward
twin) are not the band's, so this is a kernel of its own that shares the
flash file's pure helpers and none of its bodies:

- **The layout the projections write.** q and the output are ``[B, S,
  heads x hd]``, k and v ``[B, S, kv heads x hd]``: a head is a run of
  ``hd`` lanes, a block of q is ``tq`` rows x the lanes of the query
  heads that share one key/value head. Nothing is transposed, repeated
  or copied around the call (a sequence that no tile divides is padded,
  as the einsum form pads it).
- **q's norm and rotation in the step** (both optional): a head's rows
  take their RMS norm and their rotate-half rotation in float32 where
  the block lies in VMEM, so q crosses HBM once, as its projection wrote
  it, where plain XLA makes float32 copies of it in two layouts.
- **A strip, not a triangle.** A tile of ``sub`` rows (the window
  rounded up to 128 lanes) meets the ``sub`` keys before it and its own
  ``sub`` keys: the query heads of the group stacked into the rows of
  ONE ``[heads x sub, hd] x [hd, sub]`` product a key tile (a key tile
  held in the MXU is paid for by the rows that stream past it), float32
  scores, the band cut out of both tiles by one ``[sub, sub]`` mask
  each, a direct softmax (every row sees itself: the maximum is finite,
  no running maximum), probabilities rounded to the values' dtype, one
  PV a key tile. Scores never leave VMEM.
- **The keys before a block** come as a second view of K (and V): block
  ``i x tq / sub - 1`` of ``sub``-row blocks, clamped at 0 and masked
  there. K and V are read twice at ``tq == sub``, q and o once.

Forward only: the caller (``models/exaone_moe.py``) differentiates
through the einsum form. ``pallas_call(name="window_fwd")``: a device
trace shows the kernel under that name, which the pattern ``flash_fwd``
does not match.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._platform import interpret_default
from .pallas_flash import NEG_INF, _NN, _NT, _dot, _fold_scale, _times

__all__ = ["band_attention", "band_tiles", "signed_sin"]

LANES = 128
# Rows of one product: the heads of a group a step x ``sub``.
STACK_ROWS = 1024
# Rows of a q block (grid step). Read on a v5e at 64 / 8 heads of 128, S
# 8,192 (PERF.md section 6, PR 48): 1.245 / 1.111 / 1.044 ms at 128 / 256 /
# 512 rows with q's norm and rotation, 0.698 / 0.584 / 0.505 without: a
# quarter of the grid steps (~0.35 us each) and tiles whose softmax
# overlaps the next one's products.
BLOCK_ROWS = 512
# What a step may hold by `_band_bytes`' reckoning: the default scoped
# limit (16 MiB) less room for Mosaic's own temporaries. The cell's
# shape (64 / 8 heads of 128, bfloat16, 512 rows) reckons 8.3 MB.
VMEM_BYTES = 12 << 20


def _band_bytes(tq, sub, heads, hd, itemsize):
    """VMEM a step holds, roughly: q and o blocks and the four K / V
    views double-buffered, one tile's stacked q, four float32 score /
    probability tiles, their two rounded copies and the float32 output."""
    rows = heads * sub
    io = 2 * (2 * tq * heads * hd + 2 * (tq + sub) * hd) * itemsize
    return io + rows * hd * (itemsize + 4) + rows * sub * (16 + 2 * itemsize)


def band_tiles(S: int, nh: int, nkv: int, window: int, hd: int,
               itemsize: int, block_rows: int = BLOCK_ROWS):
    """(sub, tq, heads a step, padded S) of a band over ``S`` positions:
    ``sub`` the window rounded up to whole lanes; as many of a group's
    heads a step as keep the stacked product at ``STACK_ROWS`` rows or
    under; ``tq`` the largest multiple of ``sub`` up to ``block_rows``
    that divides the padded sequence and keeps the step within
    ``VMEM_BYTES`` by :func:`_band_bytes`' reckoning (one tile if none
    does)."""
    sub = -(-int(window) // LANES) * LANES
    tiles = -(-S // sub)
    g = nh // nkv
    heads = max(d for d in range(1, g + 1)
                if g % d == 0 and d * sub <= max(STACK_ROWS, sub))
    per = max(d for d in range(1, tiles + 1) if d == 1 or (
        tiles % d == 0 and d * sub <= block_rows and _band_bytes(
            d * sub, sub, heads, hd, itemsize) <= VMEM_BYTES))
    return sub, per * sub, heads, tiles * sub


def signed_sin(sin):
    """``sin [..., hd]`` with its first half negated: ``rotate_half(x)
    sin`` is ``roll(x, hd / 2)`` times this."""
    half = sin.shape[-1] // 2
    return jnp.concatenate([-sin[..., :half], sin[..., half:]], -1)


def _band_kernel(*refs, scale, window, sub, heads, hd, eps, normed, rotated):
    """One (batch row, q block, group of heads): the block's tiles of
    ``sub`` rows walked in the step (unrolled: a tile's softmax overlaps
    the next one's products), each against the tile of keys before it
    (``before``: of the block's own K view, or for the first tile the
    view one tile back) and its own (``under``)."""
    q_ref, kb_ref, k_ref, vb_ref, v_ref = refs[:5]
    extra = list(refs[5:-1])
    o_ref = refs[-1]
    gain = extra.pop(0)[...].astype(jnp.float32) if normed else None
    cos_ref, sin_ref = extra if rotated else (None, None)
    first = pl.program_id(1) == 0
    q_scale, s_scale = _fold_scale(scale)
    ago = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0) \
        - jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    under = (ago >= 0) & (ago < window)
    before = ago + sub < window

    def head(rows, j):
        """Head j's rows of the block, normed and rotated in float32."""
        x = q_ref[0, rows, j * hd:(j + 1) * hd]
        if not (normed or rotated):
            return x
        h = x.astype(jnp.float32)
        if normed:
            h = h * jax.lax.rsqrt(
                jnp.mean(h * h, axis=1, keepdims=True) + eps) * gain
        if rotated:
            h = h * cos_ref[rows] + pltpu.roll(h, hd // 2, 1) * sin_ref[rows]
        return h.astype(x.dtype)

    def scores(q, k, keep):
        s = _times(_dot(q, k, _NT), s_scale).reshape(heads, sub, sub)
        return jnp.where(keep[None], s, NEG_INF).reshape(heads * sub, sub)

    for t in range(q_ref.shape[1] // sub):
        rows = slice(t * sub, (t + 1) * sub)
        q = _times(jnp.concatenate(
            [head(rows, j) for j in range(heads)], axis=0), q_scale)
        if t:
            back = slice((t - 1) * sub, t * sub)
            k_b, v_b, keep_b = k_ref[0, back], v_ref[0, back], before
        else:       # the sequence's first tile has no keys before it
            k_b, v_b = kb_ref[0], vb_ref[0]
            keep_b = before & jnp.logical_not(first)
        v_u = v_ref[0, rows]
        s_b = scores(q, k_b, keep_b)
        s_u = scores(q, k_ref[0, rows], under)
        m = jnp.max(jnp.maximum(s_b, s_u), axis=1, keepdims=True)
        p_b, p_u = jnp.exp(s_b - m), jnp.exp(s_u - m)   # masked: exp(-inf)
        l = jnp.sum(p_b + p_u, axis=1, keepdims=True)
        o = (_dot(p_b.astype(v_b.dtype), v_b, _NN)
             + _dot(p_u.astype(v_u.dtype), v_u, _NN)) / l
        for j in range(heads):
            o_ref[0, rows, j * hd:(j + 1) * hd] = o[
                j * sub:(j + 1) * sub].astype(o_ref.dtype)


def band_attention(q, k, v, window: int, head_dim: int, q_gain=None,
                   eps: float = 0.0, rope=None, interpret=None,
                   block_rows: int = None):
    """q ``[B, S, nh x hd]``, k, v ``[B, S, nkv x hd]`` -> ``[B, S, nh x
    hd]``: causal attention in which position i sees ``i - (window - 1)
    .. i``, scores and softmax in float32 scaled by ``1 / sqrt(hd)``,
    probabilities in the values' dtype. With ``q_gain [hd]`` every head
    of q first takes its RMS norm (``eps``) times the gain, with ``rope =
    (cos, signed_sin(sin))``, ``[S, hd]`` float32 each, its rotate-half
    rotation, both in float32 and rounded to q's dtype once; k comes as
    it is kept. On the chip ``hd`` must be whole lanes (a head is sliced
    out of a block by lanes)."""
    if interpret is None:
        interpret = interpret_default()
    return _band(q, k, v, q_gain, rope, window=int(window), hd=int(head_dim),
                 eps=float(eps), interpret=bool(interpret),
                 block_rows=int(block_rows or BLOCK_ROWS))


# jitted: a program's sliding layers are the same call, so they share ONE
# trace and ONE lowering of the kernel's body (a body a layer, 4 layers x
# 3 buckets, was 5 s of a serving run's warm-up on the chip's host: my
# chip runs, PR 48)
@functools.partial(jax.jit, static_argnames=("window", "hd", "eps",
                                             "interpret", "block_rows"))
def _band(q, k, v, q_gain, rope, *, window, hd, eps, interpret, block_rows):
    B, S, width = q.shape
    nh, nkv = width // hd, k.shape[2] // hd
    sub, tq, heads, padded = band_tiles(
        S, nh, nkv, window, hd, q.dtype.itemsize, block_rows)
    tables = list(rope or ())
    if padded != S:
        # padded queries see themselves and are cut off again; padded
        # keys lie after every real query
        q, k, v = (jnp.pad(x, ((0, 0), (0, padded - S), (0, 0)))
                   for x in (q, k, v))
        tables = [jnp.pad(x, ((0, padded - S), (0, 0))) for x in tables]
    chunks = nh // nkv // heads        # steps a key/value head is held
    per = tq // sub
    q_spec = pl.BlockSpec((1, tq, heads * hd), lambda b, i, h: (b, i, h))
    kv_spec = pl.BlockSpec((1, tq, hd), lambda b, i, h: (b, i, h // chunks))
    back_spec = pl.BlockSpec(
        (1, sub, hd),
        lambda b, i, h: (b, jnp.maximum(i * per - 1, 0), h // chunks))
    extra, extra_specs = [], []
    if q_gain is not None:
        extra.append(q_gain.reshape(1, hd))
        extra_specs.append(pl.BlockSpec((1, hd), lambda b, i, h: (0, 0)))
    extra += tables
    extra_specs += [pl.BlockSpec((tq, hd), lambda b, i, h: (i, 0))
                    for _ in tables]
    out = pl.pallas_call(
        functools.partial(_band_kernel, scale=hd ** -0.5, window=window,
                          sub=sub, heads=heads, hd=hd, eps=eps,
                          normed=q_gain is not None, rotated=bool(tables)),
        grid=(B, padded // tq, nh // heads),
        in_specs=[q_spec, back_spec, kv_spec, back_spec, kv_spec]
        + extra_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # no `vmem_limit_bytes`: `band_tiles` keeps a step within the
        # default scoped limit
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        name="window_fwd",
        interpret=interpret,
    )(q, k, k, v, v, *extra)
    return out[:, :S]
