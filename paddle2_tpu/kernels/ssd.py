"""The Mamba-2 (state-space duality) recurrence, per head with a scalar
decay a head::

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t      [P, N]
    y_t = H_t C_t + D x_t

``x`` is a head's ``P`` lanes, ``B`` / ``C`` its group's ``N`` state
lanes, ``dt`` the step AFTER its softplus and ``A`` negative. Decays,
sums and the state are float32 throughout. Three forms:

* :func:`ssm_recurrence` — the definition, a ``lax.scan`` over tokens
  (what the tests hold the other two to);
* :func:`ssd_chunk_scan` — the chunked form for a whole sequence
  (prefill): matrix products inside a chunk of ``Q`` tokens, a scan of
  ``T / Q`` steps across chunks. Plain XLA; a ``dt`` of 0 leaves the
  state where it stands, which is how a padded tail is passed over;
* :func:`ssm_state_step` — one step for a batch of rows against a pool
  of per-sequence states (decode): a Pallas kernel named
  ``ssm_state_step`` that takes the rows' slot ids by scalar prefetch
  and updates the pool IN PLACE (the pool is aliased input -> output),
  a row's state streamed through VMEM in blocks of heads
  (:func:`state_step_plan`: as many whole groups as a byte budget
  holds): each state byte is read once and written once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._platform import interpret_default

__all__ = ["ssm_recurrence", "ssd_chunk_scan", "ssm_state_step",
           "ssm_state_step_xla", "state_step_plan", "state_step_vmem_bytes",
           "state_step_counts"]

_HI = jax.lax.Precision.HIGHEST


def _by_head(g, heads: int):
    """``[..., G, N]`` of the groups -> ``[..., heads, N]``: head h
    reads group ``h // (heads / G)``."""
    return jnp.repeat(g, heads // g.shape[-2], axis=-2)


def ssm_recurrence(x, dt, A, B, C, D, h0=None):
    """The recurrence as its definition, token by token. x ``[T, nh,
    P]``, dt ``[T, nh]`` (softplus applied), A, D ``[nh]``, B, C ``[T,
    G, N]`` -> (y ``[T, nh, P]`` f32, H ``[nh, P, N]`` f32 after the
    last token)."""
    nh, P = x.shape[1:]
    f32 = jnp.float32
    x, dt, B, C = (a.astype(f32) for a in (x, dt, B, C))
    A, D = A.astype(f32), D.astype(f32)
    if h0 is None:
        h0 = jnp.zeros((nh, P, B.shape[-1]), f32)

    def step(H, t):
        xt, dtt, Bt, Ct = t
        H = jnp.exp(dtt * A)[:, None, None] * H \
            + (dtt[:, None] * xt)[:, :, None] * _by_head(Bt, nh)[:, None, :]
        y = jnp.sum(H * _by_head(Ct, nh)[:, None, :], -1) + D[:, None] * xt
        return H, y

    H, y = jax.lax.scan(step, h0.astype(f32), (x, dt, B, C))
    return y, H


def ssd_chunk_scan(x, dt, A, B, C, D, chunk: int, h0=None):
    """The same numbers in the chunked form. Shapes as
    :func:`ssm_recurrence`; ``T`` is padded to a multiple of ``chunk``
    with steps of ``dt = 0`` (the state stands still under them, and
    their outputs are cut off). With ``a_t = dt_t A`` and ``s_t`` its
    running sum inside a chunk::

        y_t   = sum_{r<=t} exp(s_t - s_r) (C_t . B_r) dt_r x_r
                + exp(s_t) (H_in C_t) + D x_t
        H_out = exp(s_Q) H_in + sum_r exp(s_Q - s_r) dt_r x_r (outer) B_r

    Only exponentials of non-positive numbers are taken. The products
    that carry a decay run at full float32 precision."""
    T, nh, P = x.shape
    G, N = B.shape[1:]
    Q = int(chunk)
    f32 = jnp.float32
    pad = -T % Q
    x32, dt, B32, C32 = (jnp.pad(a.astype(f32),
                                 ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                         for a in (x, dt, B, C))
    A, D = A.astype(f32), D.astype(f32)
    nc = (T + pad) // Q
    xc = x32.reshape(nc, Q, nh, P)
    dtc = dt.reshape(nc, Q, nh)
    Bc = B32.reshape(nc, Q, G, N)
    Cc = C32.reshape(nc, Q, G, N)
    s = jnp.cumsum(dtc * A, axis=1)                          # [c, Q, nh]
    dtx = dtc[..., None] * xc                                # [c, Q, nh, P]
    with jax.named_scope("intra"):
        # decay from r to t inside a chunk, r <= t (else 0)
        diff = s[:, :, None, :] - s[:, None, :, :]           # [c, t, r, nh]
        causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
        decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
        cb = jnp.einsum("ctgn,crgn->ctrg", Cc, Bc, precision=_HI)
        w = decay * jnp.repeat(cb, nh // G, axis=-1)         # [c, t, r, nh]
        y = jnp.einsum("ctrh,crhp->cthp", w, dtx, precision=_HI)
    with jax.named_scope("states"):
        # what each chunk adds to the state, and its whole decay
        to_end = jnp.exp(s[:, -1:, :] - s)                   # [c, Q, nh]
        add = jnp.einsum("crh,crhp,crhn->chpn", to_end, dtx,
                         _by_head(Bc, nh), precision=_HI)
        whole = jnp.exp(s[:, -1, :])                         # [c, nh]
        if h0 is None:
            h0 = jnp.zeros((nh, P, N), f32)

        def join(H, t):
            dec, a = t
            return dec[:, None, None] * H + a, H             # emits H_in

        H, h_in = jax.lax.scan(join, h0.astype(f32), (whole, add))
    with jax.named_scope("inter"):
        y = y + jnp.exp(s)[..., None] * jnp.einsum(
            "chpn,cthn->cthp", h_in, _by_head(Cc, nh), precision=_HI)
    y = y + D[:, None] * xc
    return y.reshape(nc * Q, nh, P)[:T], H


# ------------------------------------------------------------ the state step
def ssm_state_step_xla(pool, layer, slots, x, B, C, dt, A, D):
    """:func:`ssm_state_step` as the ``jnp`` formula: the rows' states
    gathered, stepped and scattered back (what the kernel is held to)."""
    nh = x.shape[1]
    f32 = jnp.float32
    H = pool[layer, slots]                                   # [R, nh, P, N]
    dt = dt.astype(f32)
    xf = x.astype(f32)
    H = jnp.exp(dt * A.astype(f32))[:, :, None, None] * H \
        + (dt[:, :, None] * xf)[..., None] \
        * _by_head(B.astype(f32), nh)[:, :, None, :]
    y = jnp.sum(H * _by_head(C.astype(f32), nh)[:, :, None, :], -1) \
        + D.astype(f32)[None, :, None] * xf
    return pool.at[layer, slots].set(H), y


# The most float32 state one grid step holds. On the chip (PERF.md
# section 6, PR 42) a call's time falls with the block up to the chip's
# own ceiling for a stream read and written (658 GB/s), reached at 1 MB
# by a [128, 256] head and at 2 MB by a [64, 128] one; in and out, each
# double-buffered, 2 MB is half of the default scoped VMEM.
STATE_BLOCK_BYTES = 2 << 20
_LANES = 128


def state_step_plan(nh: int, G: int, P: int, N: int):
    """(heads a grid step holds, grid steps a row) of :func:`ssm_state_step`
    at ``nh`` heads of ``[P, N]`` in ``G`` groups: the LARGEST divisor
    of ``nh`` that is a whole number of groups or a divisor of one
    group, with its float32 state within ``STATE_BLOCK_BYTES`` (one head
    where none is). Static shapes only."""
    per_group = nh // G
    fits = [hb for hb in range(1, nh + 1)
            if nh % hb == 0 and (hb % per_group == 0 or per_group % hb == 0)
            and hb * P * N * 4 <= STATE_BLOCK_BYTES]
    hb = max(fits, default=1)
    return hb, nh // hb


def _heads_per_chunk(hb: int) -> int:
    # heads whose dt x share one 128-lane tile: three bf16 a head
    return max(c for c in range(1, _LANES // 3 + 1) if hb % c == 0)


def state_step_vmem_bytes(nh: int, G: int, P: int, N: int) -> int:
    """Scoped VMEM a call asks for under the plan: every block twice
    (the pipeline's two buffers) — the state in and out, the decays on
    every lane, dt x in three bf16 a head, B, C and y."""
    hb, _ = state_step_plan(nh, G, P, N)
    chunks = hb // _heads_per_chunk(hb)
    return 2 * (2 * hb * P * N * 4 + hb * N * 4 + P * chunks * _LANES * 2
                + 2 * G * N * 4 + P * hb * 4)


def state_step_counts(rows: int, state_shape, G: int) -> dict:
    """What ``decode.dispatch`` says of a step's calls over a bucket of
    ``rows`` against a state kind of ``state_shape`` ``(layers, nh, P,
    N)`` in ``G`` groups: the state a grid step holds, and the grid
    steps of all its layers."""
    layers, nh, P, N = state_shape
    hb, steps = state_step_plan(nh, G, P, N)
    return dict(ssm_block_bytes=hb * P * N * 4,
                ssm_grid_steps=rows * layers * steps)


def _split3(x):
    """float32 -> three bfloat16 that sum to it exactly: each part is
    the leading 8 bits of what the parts before left, CUT off as
    integers. (Rounding through ``astype`` and back is what XLA's
    ``xla_allow_excess_precision`` removes on the chip: the parts after
    the first then come out zero, PERF.md section 6, PR 42.)"""
    def leading(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32)

    hi = leading(x)
    mid = leading(x - hi)
    lo = x - hi - mid
    return tuple(part.astype(jnp.bfloat16) for part in (hi, mid, lo))


def _step_kernel(slots_ref, layer_ref, h_ref, da_ref, d3_ref, b_ref, c_ref,
                 h_out, y_ref, *, heads_per_group, steps):
    # one row's block of heads, whole groups or part of one: h_ref [hb,
    # P, N]; da_ref [hb, N] (the head's decay on every lane); d3_ref [P,
    # chunks x 128] bf16 (a chunk of heads a 128-lane tile: dt x in
    # three bf16 parts, hi | mid | lo | zeros, a head a lane of each);
    # b_ref, c_ref [G, N]; y_ref [P, hb] (a head a lane)
    del slots_ref, layer_ref            # the index maps read them
    hb, _, N = h_ref.shape
    per_chunk = _heads_per_chunk(hb)
    lanes = min(N, _LANES)
    first = pl.program_id(1) * hb if steps > 1 else 0
    g0 = first // heads_per_group
    ones = jnp.ones((_LANES, lanes), jnp.bfloat16)
    for i in range(hb):
        if i % heads_per_group == 0:
            g = g0 + i // heads_per_group
            b_row = b_ref[pl.ds(g, 1), :]                        # [1, N]
            c_row = c_ref[pl.ds(g, 1), :]
        chunk, lane = divmod(i, per_chunk)
        if lane == 0:
            # two bf16 rows a 32-bit word: masked as integers
            d3 = pltpu.bitcast(
                d3_ref[:, chunk * _LANES:(chunk + 1) * _LANES], jnp.int32)
            lane_of = jax.lax.broadcasted_iota(jnp.int32, d3.shape, 1) \
                % per_chunk
        # dt x of head i on every state lane: its three parts summed by
        # the idle MXU against ones (bf16 x 1 into float32: exact) — the
        # lane broadcast of a column would be one XLU permute a vreg,
        # beside the lane reduction of y
        own = pltpu.bitcast(jnp.where(lane_of == lane, d3, 0), jnp.bfloat16)
        col = jnp.dot(own, ones, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.DEFAULT)       # [P, lanes]
        new = h_ref[i] * da_ref[i:i + 1, :] \
            + jnp.concatenate([col] * (N // lanes), axis=1) * b_row
        h_out[i] = new
        y_ref[:, i:i + 1] = jnp.sum(new * c_row, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _state_step(pool, layer, slots, da, dtx, B, C, *, interpret):
    # da [R, nh] the heads' decays, dtx [R, nh, P] = dt x; handed to
    # the kernel a block of hb heads at a time: da on every state lane
    # [R, J, hb, N], dtx as _step_kernel reads it [R, J, P, chunks x
    # 128], y comes back a head a lane [R, J, P, hb]
    L, S, nh, P, N = pool.shape
    R = slots.shape[0]
    G = B.shape[1]
    hb, J = state_step_plan(nh, G, P, N)
    per_chunk = _heads_per_chunk(hb)
    chunks = hb // per_chunk
    da_rows = jnp.broadcast_to(da.reshape(R, J, hb, 1), (R, J, hb, N))
    d3 = jnp.stack(_split3(dtx.reshape(R, J, chunks, per_chunk, P)), 3)
    d3 = jnp.pad(d3.reshape(R, J, chunks, 3 * per_chunk, P),
                 ((0, 0),) * 3 + ((0, _LANES - 3 * per_chunk), (0, 0)))
    d3 = jnp.transpose(d3, (0, 1, 4, 2, 3)).reshape(R, J, P, chunks * _LANES)

    def state_block():
        return pl.BlockSpec(
            (None, None, hb, P, N),
            lambda r, j, slots, layer: (layer[0], slots[r], j, 0, 0))

    def row_block(shape):
        return pl.BlockSpec((None,) + shape,
                            lambda r, j, slots, layer: (r, 0, 0))

    def head_block(shape):
        return pl.BlockSpec((None, None) + shape,
                            lambda r, j, slots, layer: (r, j, 0, 0))

    pool, y = pl.pallas_call(
        functools.partial(_step_kernel, heads_per_group=nh // G, steps=J),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, J),
            in_specs=[state_block(), head_block((hb, N)),
                      head_block((P, chunks * _LANES)), row_block((G, N)),
                      row_block((G, N))],
            out_specs=[state_block(), head_block((P, hb))]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((R, J, P, hb), jnp.float32)],
        # the pool is updated where it lies: operand 2 (behind the two
        # prefetched scalars) is output 0
        input_output_aliases={2: 0},
        # rows in order: the padded rows all land in slot 0
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_state_step",
    )(slots, layer.reshape(1), pool, da_rows, d3, B, C)
    return pool, jnp.swapaxes(y, 2, 3).reshape(R, nh, P)


def ssm_state_step(pool, layer, slots, x, B, C, dt, A, D, interpret=None):
    """One recurrence step of ``R`` rows against their slots of ``pool``
    ``[layers, slots + 1, nh, P, N]`` float32, in place: (pool with
    ``pool[layer, slots[r]]`` stepped, y ``[R, nh, P]`` f32). x ``[R,
    nh, P]``, B, C ``[R, G, N]``, dt ``[R, nh]`` (softplus applied), A,
    D ``[nh]``. Rows share no slot, except the padded rows, which all
    land in the garbage slot 0."""
    if interpret is None:
        interpret = interpret_default()
    f32 = jnp.float32
    dt = dt.astype(f32)
    xf = x.astype(f32)
    pool, y = _state_step(
        pool, jnp.asarray(layer, jnp.int32), slots.astype(jnp.int32),
        jnp.exp(dt * A.astype(f32)), dt[:, :, None] * xf, B.astype(f32),
        C.astype(f32), interpret=interpret)
    return pool, y + D.astype(f32)[None, :, None] * xf
