"""Grouped matmul for dropless experts: ``out[rows of group g] =
lhs[rows of group g] @ rhs[g]`` with the rows sorted by group.

One Pallas kernel, ``moe_gmm``, after ``jax.experimental.pallas.ops.
tpu.megablox`` (whose tile bookkeeping, :func:`make_group_metadata`, is
used as it stands): the grid walks ``(n tile, visit)`` where a visit is
one (row tile, group) pair that share rows — a row tile that several
groups share is visited once by each, consecutively, and every visit
stores only its own group's rows. The whole contraction rides one step
(``K`` 768 to 5120 here), so there is no accumulator.

What a visit costs (PERF.md section 6, PR 40: 135 readings of the
kernel alone on a v5e, fitted to 4 %): the larger of its copies — the
group's ``[K, tn]`` weight tile if the group is new, the ``[tm, K]``
row tile in and the ``[tm, tn]`` tile out if the row tile is new; a
block whose index did not change is not fetched again, so a group that
straddles row tiles does NOT re-stream its weights — and the product of
the WHOLE row tile (``tm x K x tn x 2`` operations at ~0.9 of the MXU's
peak, whatever share of the rows is the group's), plus ~0.35 us a grid
step. The tiles follow from that (:func:`_row_tile`,
:func:`_col_tile`): rows enough that a step is not all overhead, few
enough that a visit's product hides under its weight tile's copy, and
columns as wide as the fast memory allows where the rows are many,
since every column tile reads all the rows again. At decode (a few rows a group) the kernel
then moves each hit expert's weights once and stands at 83-91 % of
their bytes' time; a prefill of thousands of rows (a hundred or two a
group) pays the weights' copy AND the rows' products, which overlap
only within a visit: 45-55 %.

``group_sizes`` may count more groups than ``rhs`` holds: ``first`` is
the group ``rhs[0]`` belongs to and only ``rhs.shape[0]`` groups from
there are computed — the rows of the others come back as zeros. That is
how an expert layer that holds a share of the experts uses it; a
trailing group that nobody holds is where rows to skip are parked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from ..ops.linalg import _mxu_precision
from ._platform import interpret_default

__all__ = ["moe_gmm", "gmm_plan", "plan_tile_rows", "gmm_reference"]


def _kernel(offsets_ref, gids_ref, mtiles_ref, first_ref, lhs_ref, rhs_ref,
            out_ref, *, tm):
    del first_ref
    v = pl.program_id(1)
    acc = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
        precision=_mxu_precision(lhs_ref, rhs_ref),
        preferred_element_type=jnp.float32)
    g = gids_ref[v]
    rows = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0) \
        + mtiles_ref[v] * tm
    mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
    # the tile stays in VMEM over its consecutive visits: rows of the
    # other groups are theirs to write
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


def _row_tile(m: int, groups: int) -> int:
    """Rows of one tile, from the rows a group gets (``m`` over the
    ``groups`` the rows are sorted into): every visit multiplies the
    whole tile, so the tile is the largest whose product still hides
    under its weight tile's copy — ~96 rows of bf16 by a v5e's peaks
    whatever ``K`` and ``tn`` are, and 128 reads faster than 64 on the
    chip (fewer grid steps) — and the tall 256 only where a group's rows
    fill it and the products bound the call anyway. Halved until it
    divides the rows."""
    tm = 256 if m >= 256 * groups else 128
    while m % tm:
        tm //= 2
    return tm


def _col_tile(n: int, k: int, itemsize: int, m: int) -> int:
    """Columns of one weight tile, among the multiples of 128 that
    divide ``n`` with ``[K, tn]`` at most 3 MB (``[2048, 768]``,
    ``[768, 2048]``, ``[5120, 256]``: two of them and two row tiles stay
    inside the scoped VMEM; the narrowest where none is that small, all
    of ``n`` where 128 does not divide it): every column tile reads the
    ``m`` rows again, and the first weight tile's copy hides under
    nothing, so the tile is the one with the fewest such bytes —
    ``n / tn x m + tn`` rows of ``K`` — and the narrower of two that tie
    (a decode step's few hundred rows keep ``[K, 512]``, thousands of
    rows take the widest)."""
    tiles = [tn for tn in range(128, n + 1, 128) if n % tn == 0] or [n]
    fit = [tn for tn in tiles if k * tn * itemsize <= 3 << 20] or tiles[:1]
    return min(fit, key=lambda tn: (n // tn * m + tn, tn))


def gmm_plan(group_sizes, m: int, first=0, held: int = None):
    """The visit list of one routing, shared by every projection that
    multiplies the same sorted rows (an expert layer's three): which
    (row tile, group) pairs share rows, for the ``held`` groups from
    ``first`` on. A few hundred small ops, so it is computed once a
    layer and not once a matmul."""
    group_sizes = jnp.asarray(group_sizes, jnp.int32)
    first = jnp.asarray(first, jnp.int32)
    if held is None:
        held = group_sizes.shape[0]
    tm = _row_tile(m, group_sizes.shape[0])
    (offsets, gids, mtiles), visits = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=first,
        num_nonzero_groups=held, visit_empty_groups=False)
    return offsets, gids, mtiles, visits, first.reshape(1)


def _plan_row_tile(plan, m: int) -> int:
    """The row tile :func:`gmm_plan` made ``plan`` (of ``m`` rows) for:
    its offsets count the groups."""
    return _row_tile(m, plan[0].shape[0] - 1)


@functools.partial(jax.jit, static_argnames=("interpret", "zero_rest"))
def _gmm(lhs, rhs, plan, *, interpret, zero_rest=True):
    m, k = lhs.shape
    held, _, n = rhs.shape
    offsets, gids, mtiles, visits, first = plan
    tm, tn = _plan_row_tile(plan, m), _col_tile(n, k, rhs.dtype.itemsize, m)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, visits),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, v, off, gid, mt, f: (mt[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, off, gid, mt, f:
                             (gid[v] - f[0], 0, j)),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, off, gid, mt, f: (mt[v], j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_gmm",
    )(offsets, gids, mtiles, first, lhs, rhs)
    if not zero_rest:
        return out
    # rows of groups not held were never visited
    row = jnp.arange(m)
    mine = (row >= offsets[first[0]]) & (row < offsets[first[0] + held])
    return jnp.where(mine[:, None], out, jnp.zeros((), out.dtype))


def moe_gmm(lhs, rhs, group_sizes=None, first=0, interpret=None,
            plan=None, zero_rest: bool = True):
    """lhs ``[m, k]`` (rows sorted by group), rhs ``[held, k, n]``,
    group_sizes int32 ``[groups]`` adding up to ``m`` -> ``[m, n]`` in
    lhs's dtype (f32 accumulation). ``m`` is a multiple of 8. Pass the
    ``plan`` (:func:`gmm_plan` of the same sizes, rows, ``first`` and
    ``held``) where several products share one routing. The rows of
    groups not held come back as zeros; ``zero_rest=False`` leaves them
    as the kernel's output buffer has them, UNWRITTEN (anything, NaN
    too), for a caller that reads the held groups' rows only and need
    not pay a pass over ``[m, n]``."""
    if interpret is None:
        interpret = interpret_default()
    if plan is None:
        plan = gmm_plan(group_sizes, lhs.shape[0], first, rhs.shape[0])
    return _gmm(lhs, rhs, plan, interpret=bool(interpret),
                zero_rest=bool(zero_rest))


def plan_tile_rows(plan, m: int):
    """Rows the MXU multiplies a column tile under ``plan`` (of ``m``
    rows): its visits times the row tile, against the rows the held
    groups own."""
    return plan[3] * _plan_row_tile(plan, m)


def gmm_reference(lhs, rhs, group_sizes, first=0):
    """The same product as a plain loop over the held groups (tests)."""
    m = lhs.shape[0]
    ends = jnp.cumsum(jnp.asarray(group_sizes))
    starts = ends - jnp.asarray(group_sizes)
    row = jnp.arange(m)[:, None]
    out = jnp.zeros((m, rhs.shape[2]), jnp.float32)
    for h in range(rhs.shape[0]):
        mine = (row >= starts[first + h]) & (row < ends[first + h])
        out = out + jnp.where(mine, jnp.dot(
            lhs, rhs[h], precision=_mxu_precision(lhs, rhs),
            preferred_element_type=jnp.float32), 0.0)
    return out.astype(lhs.dtype)
