"""Grouped matmul for dropless experts: ``out[rows of group g] =
lhs[rows of group g] @ rhs[g]`` with the rows sorted by group.

One Pallas kernel, ``moe_gmm``, after ``jax.experimental.pallas.ops.
tpu.megablox`` (whose tile bookkeeping, :func:`make_group_metadata`, is
used as it stands): the grid walks ``(n tile, visit)`` where a visit is
one (row tile, group) pair that share rows — a row tile that several
groups share is visited once by each, consecutively, and every visit
stores only its own group's rows. The whole contraction rides one step
(``K`` 768 to 5120 here), so there is no accumulator and a group's
``[K, tn]`` weight tile is streamed once per row tile it touches: at
decode (a few rows a group) the kernel moves each hit expert's weights
once and is bound by their bytes; a prefill of a few thousand tokens
(under ~240 rows a group) is still mostly bound by them, longer ones by
the MXU.

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

__all__ = ["moe_gmm", "gmm_plan", "gmm_reference"]


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


def _row_tile(m: int) -> int:
    """Rows of one tile: 128 (256 for thousands of rows), halved until
    it divides the rows."""
    tm = 256 if m >= 2048 else 128
    while m % tm:
        tm //= 2
    return tm


def _col_tile(n: int, k: int = 2048, itemsize: int = 2) -> int:
    """Columns of one weight tile: ``[K, 512]`` is about 2 MB at the
    ``K`` of 2048 and under, so two of them and two row tiles stay well
    inside the scoped VMEM; a longer contraction (``K`` 5120: 5 MB a
    tile, and 2.6 MB a row tile) halves the columns until the tile is
    under 3 MB again."""
    tn = 512
    while n % tn or (k * tn * itemsize > 3 << 20 and tn > 128):
        tn //= 2
    return tn


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
    tm = _row_tile(m)
    (offsets, gids, mtiles), visits = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=first,
        num_nonzero_groups=held, visit_empty_groups=False)
    return offsets, gids, mtiles, visits, first.reshape(1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gmm(lhs, rhs, plan, *, interpret):
    m, k = lhs.shape
    held, _, n = rhs.shape
    tm, tn = _row_tile(m), _col_tile(n, k, rhs.dtype.itemsize)
    offsets, gids, mtiles, visits, first = plan
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
    # rows of groups not held were never visited
    row = jnp.arange(m)
    mine = (row >= offsets[first[0]]) & (row < offsets[first[0] + held])
    return jnp.where(mine[:, None], out, jnp.zeros((), out.dtype))


def moe_gmm(lhs, rhs, group_sizes=None, first=0, interpret=None,
            plan=None):
    """lhs ``[m, k]`` (rows sorted by group), rhs ``[held, k, n]``,
    group_sizes int32 ``[groups]`` adding up to ``m`` -> ``[m, n]`` in
    lhs's dtype (f32 accumulation). ``m`` is a multiple of 8. Pass the
    ``plan`` (:func:`gmm_plan` of the same sizes, rows, ``first`` and
    ``held``) where several products share one routing."""
    if interpret is None:
        interpret = interpret_default()
    if plan is None:
        plan = gmm_plan(group_sizes, lhs.shape[0], first, rhs.shape[0])
    return _gmm(lhs, rhs, plan, interpret=bool(interpret))


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
