"""Eager collective communication API (python/paddle/distributed/communication/).

TPU-native redesign of ProcessGroupNCCL (process_group_nccl.cc:860): every
collective is a jitted ``shard_map`` program over the global mesh, so the
"communicator" is an XLA HLO collective riding ICI — there is no eager NCCL
call to wrap. The single-controller SPMD view replaces per-rank processes:

    A distributed tensor is RANK-MAJOR — ``x[i]`` is rank i's local tensor,
    i.e. the global array of the SPMD program, sharded over the mesh. Each
    collective consumes/produces that global view and mutates the input
    Tensor in place like the reference API.

Groups are mesh axes (see mesh.py): the world group spans every axis; a
sub-group (e.g. the 'mp' ring inside a dp×mp mesh) reduces over its axis
only, which is exactly how XLA lowers grouped collectives.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec

from ..framework.tensor import Tensor
from . import mesh as mesh_mod
from .watchdog import CollectiveTimeout  # re-export: raised by timeouts
# flight recorder: every dispatched collective records enter/exit with a
# per-rank sequence number — the key the post-mortem doctor joins ranks
# on. One attribute load per collective when recording is off.
from .fault_tolerance import flight_recorder as _flight
# chaos: flip_bits:collective corrupts the victim rank's payload at
# dispatch (silent-data-corruption drills); same one-attribute-load
# clean-path contract as the flight hook.
from .fault_tolerance import chaos as _chaos
# metrics plane: every dispatched collective accrues wall time to the
# step window's "collective" component and bumps the bytes/count
# counters the cost model and perf_doctor read (one _metered() site
# rule for every dispatch path). One attribute load per collective
# when the plane is off.
from contextlib import contextmanager as _contextmanager
from contextlib import nullcontext

from ..observability import metrics as _metrics

P = PartitionSpec

__all__ = ["ReduceOp", "Group", "new_group", "get_group", "all_reduce",
           "fused_all_reduce",
           "all_gather", "all_gather_object", "reduce_scatter", "broadcast",
           "reduce", "scatter", "all_to_all", "alltoall", "send", "recv",
           "isend", "irecv", "barrier", "ppermute", "wait",
           "batch_isend_irecv", "P2POp", "is_initialized",
           "destroy_process_group", "gather", "alltoall_single",
           "broadcast_object_list", "scatter_object_list",
           "CollectiveTimeout"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class _Task:
    """Return object of async collectives (reference ProcessGroup::Task);
    XLA dispatch is already async, wait() blocks on the result buffer."""

    def __init__(self, tensor: Optional[Tensor] = None):
        self._tensor = tensor

    def wait(self):
        if self._tensor is not None:
            jax.block_until_ready(self._tensor._data)

    def is_completed(self):
        return True


class Group:
    """A communication group = a (tuple of) mesh axis(es)."""

    _next_id = 0

    def __init__(self, axes: Tuple[str, ...], ranks: Optional[List[int]] = None):
        self.axes = tuple(axes)
        mesh = mesh_mod.get_mesh()
        self.nranks = int(np.prod([mesh.shape[a] for a in self.axes]))
        self.ranks = ranks if ranks is not None else list(range(self.nranks))
        self.id = Group._next_id
        Group._next_id += 1
        self._p2p_queue: List[Tuple[Tensor, int]] = []

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank: int) -> int:
        return self.ranks.index(rank) if rank in self.ranks else -1

    @property
    def process_ids(self):
        return self.ranks


_world_cache: Dict[int, Group] = {}


def _world_group() -> Group:
    mesh = mesh_mod.get_mesh()
    g = _world_cache.get(id(mesh))
    if g is None:
        g = Group(tuple(mesh.axis_names))
        _world_cache[id(mesh)] = g
    return g


_groups: Dict[int, Group] = {}


def is_initialized() -> bool:
    return mesh_mod.mesh_initialized()


def destroy_process_group(group: Optional[Group] = None) -> None:
    _groups.clear()


def get_group(gid: int = 0) -> Optional[Group]:
    return _groups.get(gid)


def new_group(ranks: Optional[List[int]] = None, backend: Optional[str] = None,
              timeout=None) -> Group:
    """Create a group. Groups must be axis-aligned sub-meshes — on TPU a
    communication group IS a mesh axis (XLA grouped collectives); arbitrary
    rank subsets have no efficient ICI mapping (reference new_group
    collective.py:194 builds NCCL sub-rings instead)."""
    mesh = mesh_mod.get_mesh()
    world = int(np.prod(list(mesh.shape.values())))
    if ranks is None or sorted(ranks) == list(range(world)):
        g = _world_group()
    else:
        axis = _find_axis_for_ranks(mesh, sorted(ranks))
        if axis is None:
            raise NotImplementedError(
                f"new_group({ranks}): only axis-aligned groups are supported "
                f"on the TPU mesh {dict(mesh.shape)}; reshape the mesh so the "
                "group lies along one axis")
        g = Group((axis,), list(sorted(ranks)))
    _groups[g.id] = g
    return g


def _find_axis_for_ranks(mesh, ranks: List[int]) -> Optional[str]:
    """If `ranks` is one of the sub-groups obtained by varying a single mesh
    axis (others fixed), return that axis name."""
    sizes = [mesh.shape[a] for a in mesh.axis_names]
    grid = np.arange(int(np.prod(sizes))).reshape(sizes)
    for i, name in enumerate(mesh.axis_names):
        rolled = np.moveaxis(grid, i, -1).reshape(-1, sizes[i])
        for row in rolled:
            if row.tolist() == ranks:
                return name
    return None


# --------------------------------------------------------------------------
# collective kernels: jit(shard_map(...)) cached per (kind, axes, aval, extra)
# --------------------------------------------------------------------------

_kernel_cache: Dict[Any, Any] = {}


def _rank_spec(mesh) -> P:
    """Rank-major leading dim: sharded over ALL mesh axes in order."""
    return P(tuple(mesh.axis_names))


def _gather_cat_over(x, axes):
    """Concat of the group's blocks along dim0 (paddle all_gather layout)."""
    out = x
    for a in axes[::-1]:
        out = jax.lax.all_gather(out, a, axis=0, tiled=True)
    return out


def _gather_stack_over(x, axes):
    """Stack of the group's blocks on a NEW leading dim [G, *S]."""
    return _gather_cat_over(x[None], axes)


def _butterfly_prod(x, axes, mesh):
    """All-reduce product via a log2(G) recursive-doubling butterfly of
    collective-permutes — O(1) memory per step (the gather-then-prod
    fallback materializes [G, *S]). Non-power-of-two groups fall back."""
    ax = axes if len(axes) > 1 else axes[0]
    g = int(np.prod([mesh.shape[a] for a in axes]))
    if len(axes) > 1 or g & (g - 1):
        return jnp.prod(_gather_stack_over(x, axes), axis=0)
    shift = 1
    while shift < g:
        perm = [(i, i ^ shift) for i in range(g)]
        x = x * jax.lax.ppermute(x, ax, perm=perm)
        shift <<= 1
    return x


def _kernel(kind: str, axes: Tuple[str, ...], aval, extra=()) -> Any:
    mesh = mesh_mod.get_mesh()
    key = (kind, axes, id(mesh), aval.shape, str(aval.dtype), extra)
    fn = _kernel_cache.get(key)
    if fn is not None:
        return fn

    spec = _rank_spec(mesh)
    ax = axes if len(axes) > 1 else axes[0]

    def _psum(v):
        return jax.lax.psum(v, ax)

    def _group_size():
        return int(np.prod([mesh.shape[a] for a in axes]))

    def _gather_cat(v):
        return _gather_cat_over(v, axes)

    def _gather_stack(v):
        return _gather_stack_over(v, axes)

    if kind == "all_reduce_sum":
        body = lambda x: _psum(x)
    elif kind == "all_reduce_max":
        body = lambda x: jax.lax.pmax(x, ax)
    elif kind == "all_reduce_min":
        body = lambda x: jax.lax.pmin(x, ax)
    elif kind == "all_reduce_prod":
        def body(x):
            return _butterfly_prod(x, axes, mesh)
    elif kind == "all_reduce_avg":
        body = lambda x: _psum(x) / _group_size()
    elif kind == "all_gather":
        body = _gather_cat
    elif kind == "reduce_scatter":
        def body(x):
            return jax.lax.psum_scatter(x, ax, scatter_dimension=0, tiled=True)
    elif kind == "broadcast":
        src = extra[0]

        def body(x):
            # binomial-tree broadcast: ceil(log2 G) collective-permutes,
            # O(S) memory — no [G, *S] gather materialization
            # (reference: ncclBroadcast's tree algorithm)
            if len(axes) > 1:
                return _gather_stack(x)[src]  # multi-axis fallback
            g = _group_size()
            rel = (jax.lax.axis_index(ax) - src) % g
            shift = 1
            while shift < g:
                perm = [((src + r) % g, (src + r + shift) % g)
                        for r in range(shift) if r + shift < g]
                recv = jax.lax.ppermute(x, ax, perm=perm)
                x = jnp.where((rel >= shift) & (rel < 2 * shift), recv, x)
                shift <<= 1
            return x
    elif kind == "reduce":
        dst, op = extra

        def body(x):
            if op == ReduceOp.MAX:
                red = jax.lax.pmax(x, ax)
            elif op == ReduceOp.MIN:
                red = jax.lax.pmin(x, ax)
            elif op == ReduceOp.AVG:
                red = _psum(x) / _group_size()
            elif op == ReduceOp.PROD:
                red = _butterfly_prod(x, axes, mesh)
            else:
                red = _psum(x)
            idx = jax.lax.axis_index(ax)
            return jnp.where(idx == dst, red, x)
    elif kind == "scatter":
        src = extra[0]

        def body(x):
            # x: [G, *S] on every rank; only src's rows matter. One
            # all-to-all routes row j of every rank to rank j, so rank i
            # ends with [G, *S] whose row r is rank r's row i — row src
            # is the scatter payload. O(G*S) per rank, never [G, G, *S].
            if len(axes) > 1:
                g = _gather_stack(x)  # multi-axis fallback
                return g[src, jax.lax.axis_index(ax)]
            routed = jax.lax.all_to_all(x, ax, split_axis=0, concat_axis=0,
                                        tiled=True)
            return routed[src]
    elif kind == "all_to_all":
        def body(x):
            # x: [G, *S]; block j goes to rank j
            return jax.lax.all_to_all(x, ax, split_axis=0, concat_axis=0,
                                      tiled=True)
    elif kind == "ppermute":
        perm = extra[0]

        def body(x):
            return jax.lax.ppermute(x, ax, perm=list(perm))
    elif kind == "p2p":
        src, dst, = extra

        def body(sent, buf):
            moved = jax.lax.ppermute(sent, ax, perm=[(src, dst)])
            idx = jax.lax.axis_index(ax)
            return jnp.where(idx == dst, moved, buf)
    else:
        raise ValueError(kind)

    rank_first = _rank_spec(mesh)

    def wrap(single_body):
        def f(*xs):
            # each x: local block [1, *S] → op on [*S]
            outs = single_body(*[x[0] for x in xs])
            return outs[None]
        return f

    n_args = 2 if kind == "p2p" else 1
    fn = jax.jit(shard_map(wrap(body), mesh=mesh,
                           in_specs=tuple([rank_first] * n_args),
                           out_specs=rank_first))
    _kernel_cache[key] = fn
    return fn


def _axes(group: Optional[Group]) -> Tuple[str, ...]:
    g = group if group is not None else _world_group()
    return g.axes


def _check_rank_major(t: Tensor, group: Optional[Group]) -> None:
    w = mesh_mod.world_size()
    if not t.shape or t.shape[0] != w:
        raise ValueError(
            f"collective tensors are RANK-MAJOR: leading dim must equal the "
            f"mesh world size {w}, got shape {t.shape}")


def _multiprocess() -> bool:
    return jax.process_count() > 1


def _mp_group_guard(group: Optional["Group"]) -> None:
    """Multi-process collectives run over ALL processes; sub-groups would
    need coordination-service subgroup gathers (not implemented). Refuse
    loudly instead of silently widening the group."""
    if group is not None and group is not _world_group():
        raise NotImplementedError(
            "multi-process collectives support only the world group; "
            "axis-aligned sub-groups are a single-controller feature")


# Shared no-op span for dispatch sites whose body can't early-return
# (e.g. the all_gather list form): `with _NO_METER if off else
# _metered(...)` keeps the off path at one attribute load — the
# conditional never evaluates _metered's arguments, so no generator or
# axes-string is built.
_NO_METER = nullcontext()


@_contextmanager
def _metered(kind: str, t: Tensor, axes: str, rank_major: bool = False):
    """THE metering rule for every eager collective dispatch site:
    count the op, charge the PER-RANK payload bytes (controller-mode
    invariant — ``cost_model.wire_bytes`` multiplies the group effect
    back in), and accrue the span to the step window's "collective"
    component. ``rank_major`` payloads carry the mesh world size as
    dim 0 (``_check_rank_major``), so the per-rank slice divides by
    ``shape[0]`` — NOT the group size: a subgroup collective still
    moves a rank-major [W, ...] tensor."""
    pl = _metrics._ACTIVE
    if pl is None:
        yield
        return
    nbytes = float(getattr(t._data, "nbytes", 0))
    if rank_major:
        shape = getattr(t._data, "shape", None)
        if shape:
            nbytes /= max(int(shape[0]), 1)
    pl.inc("collectives_total", op=kind)
    pl.inc("collective_bytes_total", nbytes, op=kind, axes=axes)
    pl.phase_enter("collective")
    try:
        yield
    finally:
        pl.phase_exit()


def _run_process_level(kind: str, t: Tensor, extra=()) -> Tensor:
    if _metrics._ACTIVE is None:   # one attribute load on the off path
        return _run_process_level_impl(kind, t, extra=extra)
    with _metered(kind, t, "process"):
        return _run_process_level_impl(kind, t, extra=extra)


def _run_process_level_impl(kind: str, t: Tensor, extra=()) -> Tensor:
    """Multi-process (multi-controller) collectives: each PROCESS passes
    its own local tensor and the group ranks are processes — the
    reference's ProcessGroup semantics (process_group.h:48). Built on
    the coordination service's process_allgather, which is correct for
    ANY local-device count (a v4 host driving 4 chips is still one
    rank). This is the bootstrap/control-plane path; bulk data parallelism
    on pods should flow through jit+GSPMD shardings, not eager
    collectives (module docstring)."""
    from jax.experimental import multihost_utils as mhu
    local = np.asarray(t._data)
    if _chaos._ACTIVE is not None:
        # SDC drill: the victim PROCESS feeds corrupt bits into the
        # gather — exactly what a marginal host NIC/DMA would do
        local = np.asarray(
            _chaos.maybe_flip_bits_array("collective", local))
    cseq = -1
    if _flight._ACTIVE is not None:
        cseq = _flight.collective_enter(
            kind, f"processes={jax.process_count()}",
            shape=tuple(map(int, local.shape)), dtype=str(local.dtype))
    g = mhu.process_allgather(local)            # [P, *S] everywhere
    pid = jax.process_index()
    nproc = jax.process_count()
    if kind == "all_reduce_sum":
        out = g.sum(axis=0)
    elif kind == "all_reduce_max":
        out = g.max(axis=0)
    elif kind == "all_reduce_min":
        out = g.min(axis=0)
    elif kind == "all_reduce_prod":
        out = g.prod(axis=0)
    elif kind == "all_reduce_avg":
        out = g.mean(axis=0)
    elif kind == "broadcast":
        out = g[extra[0]]
    elif kind == "all_gather_cat":
        out = g.reshape((-1,) + g.shape[2:]) if g.ndim > 1 else g
    elif kind == "all_gather_stack":
        out = g
    elif kind == "reduce":
        dst, op = extra
        red = {ReduceOp.MAX: g.max(axis=0), ReduceOp.MIN: g.min(axis=0),
               ReduceOp.PROD: g.prod(axis=0),
               ReduceOp.AVG: g.mean(axis=0)}.get(op, g.sum(axis=0))
        out = red if pid == dst else local
    elif kind == "scatter":
        # local is [P, *S] on the src (a list stacked by the caller)
        out = g[extra[0]][pid]
    elif kind == "all_to_all":
        # local [P, *S]: block j of each process goes to process j
        out = g[:, pid]
    elif kind == "reduce_scatter":
        red = g.sum(axis=0)
        out = np.split(red, nproc, axis=0)[pid]
    else:
        raise NotImplementedError(
            f"collective '{kind}' has no multi-process path (send/recv "
            "p2p pairs inside one controller only; use ppermute-based "
            "patterns or the GSPMD path for cross-process p2p)")
    _flight.collective_exit(cseq, kind)
    t._replace_data(jnp.asarray(out))
    return t


def _to_mesh(arr: jax.Array) -> jax.Array:
    """Commit a rank-major array onto the mesh (dim0 split across devices)."""
    mesh = mesh_mod.get_mesh()
    from jax.sharding import NamedSharding
    spec = P(tuple(mesh.axis_names), *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


def _group_desc(group: Optional[Group]) -> str:
    g = group if group is not None else _world_group()
    return f"axes={g.axes} nranks={g.nranks}"


def _run(kind: str, t: Tensor, group: Optional[Group], extra=(),
         timeout: Optional[float] = None) -> Tensor:
    if _metrics._ACTIVE is None:   # one attribute load on the off path
        return _run_impl(kind, t, group, extra=extra, timeout=timeout)
    g = group if group is not None else _world_group()
    with _metered(kind, t, "x".join(g.axes), rank_major=True):
        return _run_impl(kind, t, group, extra=extra, timeout=timeout)


def _run_impl(kind: str, t: Tensor, group: Optional[Group], extra=(),
              timeout: Optional[float] = None) -> Tensor:
    _check_rank_major(t, group)
    arr = t._data
    if _chaos._ACTIVE is not None:
        # SDC drill, single-controller form: corrupt only the victim
        # LOGICAL rank's dim-0 row of the rank-major payload
        arr = _chaos.maybe_flip_bits_array("collective", arr,
                                           rank_axis=True)
    cseq = -1
    if _flight._ACTIVE is not None:
        cseq = _flight.collective_enter(
            kind, _group_desc(group), shape=tuple(map(int, arr.shape)),
            dtype=str(arr.dtype))
    # per-rank scalars ([W] global): lift to [W, 1] so axis-0 kernels work,
    # then drop the lifted dim (all_gather keeps it: its output IS the dim)
    lifted = arr.ndim == 1
    if lifted:
        arr = arr[:, None]
    fn = _kernel(kind, _axes(group),
                 jax.ShapeDtypeStruct(arr.shape, arr.dtype), extra)
    out = fn(_to_mesh(arr))
    if lifted and kind != "all_gather":
        out = out[..., 0]
    from .watchdog import watch as _watch
    _watch(kind, out)
    if timeout is not None:
        # deadline-aware: bound the wait on the dispatched result — a
        # hang raises CollectiveTimeout naming group/op/stragglers. A
        # timeout propagates with the enter event left un-exited: the
        # dump shows this op in flight at death.
        from .watchdog import wait_with_deadline
        wait_with_deadline(kind, out, float(timeout),
                           group_desc=_group_desc(group))
    _flight.collective_exit(cseq, kind)
    t._replace_data(out)
    return t


# --------------------------------------------------------------------------
# public API (communication/all_reduce.py etc. parity)
# --------------------------------------------------------------------------

def _deadline_process_level(kind: str, t: Tensor, extra=(),
                            timeout: Optional[float] = None) -> Tensor:
    """Multi-controller collectives block inside the coordination
    service, so the deadline wraps the WHOLE call on a helper thread.
    The thread dispatches into a SHADOW tensor and the caller commits
    only on an in-deadline return — an abandoned thread that wakes late
    can never mutate the live tensor under a retried step. Note the
    gang itself stays desynced after a timeout (this rank dispatched a
    collective its peers may still complete); pair deadlines with
    FLAGS_collective_abort_on_timeout for launcher-driven gang restart,
    exactly the reference AbortComm posture."""
    if timeout is None:
        return _run_process_level(kind, t, extra=extra)
    from .watchdog import run_with_deadline
    shadow = Tensor(t._data)

    def _dispatch():
        # UN-metered impl: this closure runs on the deadline helper
        # thread, and run_with_deadline requires late completion to be
        # side-effect-free — an abandoned thread's phase_exit would pop
        # whatever frame the caller opened since. Metering happens on
        # the caller thread, around the deadline wait, below.
        return _run_process_level_impl(kind, shadow, extra=extra)

    with (_NO_METER if _metrics._ACTIVE is None
          else _metered(kind, t, "process")):
        out = run_with_deadline(
            kind, _dispatch, float(timeout),
            group_desc=f"processes={jax.process_count()}")
    t._replace_data(out._data)
    return t


def all_reduce(tensor: Tensor, op: str = ReduceOp.SUM,
               group: Optional[Group] = None, sync_op: bool = True,
               timeout: Optional[float] = None):
    if _multiprocess():
        _mp_group_guard(group)
        _deadline_process_level(f"all_reduce_{op}", tensor,
                                timeout=timeout)
        return _Task(tensor)
    _run(f"all_reduce_{op}", tensor, group, timeout=timeout)
    return _Task(tensor)


def fused_all_reduce(tensors: List[Tensor], op: str = ReduceOp.SUM,
                     group: Optional[Group] = None,
                     bucket_bytes: Optional[float] = None,
                     timeout: Optional[float] = None,
                     plan=None) -> int:
    """All-reduce a LIST of tensors in fused, size-targeted buckets.

    The DDP-reducer dispatch primitive: instead of one collective per
    tensor (one kernel launch — or, multi-controller, one coordination-
    service RPC — each), tensors are packed into the deterministic
    ``distributed.bucket`` plan and each bucket ships as ONE flat
    fused payload, split back in place afterwards. Bitwise identical
    to per-tensor ``all_reduce`` (sum/mean are elementwise). Returns
    the number of collective dispatches issued. ``bucket_bytes``
    defaults to the bucket module's 25 MB; a caller that already built
    the :class:`~paddle2_tpu.distributed.bucket.BucketPlan` for these
    tensors passes it as ``plan`` (validated to cover exactly these
    tensors — a stale plan for a different grad set would silently
    leave some tensors un-reduced, a cross-rank desync)."""
    from .bucket import (DEFAULT_BUCKET_MB, BucketPlan, _concat_flat,
                         _split_back)
    if not tensors:
        return 0
    arrs = [t._data for t in tensors]
    # rank-major payloads carry the mesh world as dim 0 (the
    # single-controller contract); process-level payloads are local
    lead = 0 if _multiprocess() else 1
    if plan is None:
        if bucket_bytes is None:
            bucket_bytes = DEFAULT_BUCKET_MB * 1e6
        # plan over LOGICAL per-rank shapes: the leading world dim is
        # presentation, not payload — counting it would shrink every
        # bucket's logical content by a factor of W
        plan = BucketPlan([(tuple(a.shape[lead:]), a.dtype)
                           for a in arrs], float(bucket_bytes))
    else:
        idx = sorted(i for b in plan.buckets for i in b)
        if idx != list(range(len(arrs))):
            raise ValueError(
                "fused_all_reduce: supplied plan does not cover the "
                f"tensor list exactly ({len(idx)} plan slots for "
                f"{len(arrs)} tensors)")
        expect = [(tuple(a.shape[lead:]), str(np.dtype(a.dtype)))
                  for a in arrs]
        if list(plan.avals) != expect:
            raise ValueError(
                "fused_all_reduce: supplied plan was built for "
                "different tensor shapes/dtypes than the ones passed")
    n = 0
    for bucket in plan.buckets:
        chunk = [arrs[i] for i in bucket]
        fused = Tensor(_concat_flat(chunk, lead))
        all_reduce(fused, op=op, group=group, timeout=timeout)
        for i, piece in zip(bucket, _split_back(fused._data, chunk,
                                                lead)):
            tensors[i]._replace_data(piece)
        n += 1
    return n


def all_gather(tensor_or_list, tensor: Optional[Tensor] = None,
               group: Optional[Group] = None, sync_op: bool = True):
    """paddle signature: all_gather(tensor_list, tensor). Also accepts a
    single rank-major tensor, returning the gathered rank-major result
    ([W, G*S0, ...])."""
    if isinstance(tensor_or_list, list):
        out_list, t = tensor_or_list, tensor
        if _multiprocess():
            _mp_group_guard(group)
            with (_NO_METER if _metrics._ACTIVE is None
                  else _metered("all_gather", t, "process")):
                from jax.experimental import multihost_utils as mhu
                g = mhu.process_allgather(np.asarray(t._data))
            out_list.extend(Tensor(jnp.asarray(row)) for row in g)
            return _Task()
        _check_rank_major(t, group)
        g = group if group is not None else _world_group()
        with (_NO_METER if _metrics._ACTIVE is None
              else _metered("all_gather", t, "x".join(g.axes),
                            rank_major=True)):
            arr = t._data
            scalar_per_rank = arr.ndim == 1
            if scalar_per_rank:
                arr = arr[:, None]
            fn = _kernel("all_gather", _axes(group),
                         jax.ShapeDtypeStruct(arr.shape, arr.dtype))
            out = fn(_to_mesh(arr))  # [W, G*S0, ...]
            from .watchdog import watch as _watch
            _watch("all_gather", out)
        s0 = arr.shape[1]
        for i in range(g.nranks):
            block = out[:, i * s0:(i + 1) * s0]
            if scalar_per_rank:
                block = block[:, 0]
            out_list.append(Tensor(block))
        return _Task()
    if _multiprocess():
        _mp_group_guard(group)
        return _run_process_level("all_gather_cat", tensor_or_list)
    return _run("all_gather", tensor_or_list, group)


def all_gather_object(object_list: list, obj, group: Optional[Group] = None):
    # single-controller: every "rank" holds the same object
    g = group if group is not None else _world_group()
    object_list.extend([obj] * g.nranks)


def reduce_scatter(tensor: Tensor, tensor_or_tensor_list=None,
                   op: str = ReduceOp.SUM, group: Optional[Group] = None,
                   sync_op: bool = True, timeout: Optional[float] = None):
    t = tensor_or_tensor_list if tensor_or_tensor_list is not None else tensor
    if isinstance(t, list):
        from ..ops.manipulation import concat
        # process-level layout: per-destination chunks concatenate on
        # axis 0 (the handler splits axis 0 per process); the
        # single-controller rank-major layout concatenates on axis 1
        t = concat(t, axis=0 if _multiprocess() else 1)
    if op != ReduceOp.SUM:
        raise NotImplementedError("reduce_scatter supports SUM on TPU")
    if _multiprocess():
        _mp_group_guard(group)
        out = _deadline_process_level("reduce_scatter", t, timeout=timeout)
        if t is not tensor:
            tensor._replace_data(out._data)
        return _Task(tensor)
    out = _run("reduce_scatter", t, group, timeout=timeout)
    if t is not tensor:
        tensor._replace_data(out._data)
    return _Task(tensor)


def broadcast(tensor: Tensor, src: int = 0, group: Optional[Group] = None,
              sync_op: bool = True, timeout: Optional[float] = None):
    g = group if group is not None else _world_group()
    rel = g.get_group_rank(src) if src in g.ranks else src
    if _multiprocess():
        _mp_group_guard(group)
        _deadline_process_level("broadcast", tensor, extra=(int(src),),
                                timeout=timeout)
        return _Task(tensor)
    _run("broadcast", tensor, group, extra=(int(rel),), timeout=timeout)
    return _Task(tensor)


def reduce(tensor: Tensor, dst: int = 0, op: str = ReduceOp.SUM,
           group: Optional[Group] = None, sync_op: bool = True,
           timeout: Optional[float] = None):
    g = group if group is not None else _world_group()
    rel = g.get_group_rank(dst) if dst in g.ranks else dst
    if _multiprocess():
        _mp_group_guard(group)
        _deadline_process_level("reduce", tensor, extra=(int(dst), op),
                                timeout=timeout)
        return _Task(tensor)
    _run("reduce", tensor, group, extra=(int(rel), op), timeout=timeout)
    return _Task(tensor)


def scatter(tensor: Tensor, tensor_list=None, src: int = 0,
            group: Optional[Group] = None, sync_op: bool = True):
    """Rank-major: tensor is [W, G, *S] (row src holds the payload);
    result [W, *S]. With tensor_list, the list is stacked first."""
    g = group if group is not None else _world_group()
    rel = g.get_group_rank(src) if src in g.ranks else src
    if _multiprocess():
        _mp_group_guard(group)
        nproc = jax.process_count()
        on_src = jax.process_index() == int(src)
        if on_src and tensor_list is not None:
            payload = Tensor(jnp.stack([x._data for x in tensor_list]))
        elif on_src:
            # single-tensor form: src's tensor IS the [P, *S] payload
            payload = tensor
        else:
            out_shape = tuple(tensor.shape)
            payload = Tensor(jnp.zeros((nproc,) + out_shape,
                                       tensor._data.dtype))
        out = _run_process_level("scatter", payload, extra=(int(src),))
        tensor._replace_data(out._data)
        return _Task(tensor)
    if tensor_list is not None:
        from ..ops.manipulation import stack
        payload = stack(tensor_list, axis=1)
    else:
        payload = tensor
    out = _run("scatter", payload, group, extra=(int(rel),))
    if payload is not tensor:
        tensor._replace_data(out._data)
    return _Task(tensor)


def all_to_all(out_tensor_list, in_tensor_list=None,
               group: Optional[Group] = None, sync_op: bool = True):
    """paddle signature: (out_tensor_list, in_tensor_list). Also accepts a
    single rank-major [W, G, *S] tensor."""
    if isinstance(out_tensor_list, Tensor):
        if _multiprocess():
            _mp_group_guard(group)
            return _run_process_level("all_to_all", out_tensor_list)
        return _run("all_to_all", out_tensor_list, group)
    if _multiprocess():
        _mp_group_guard(group)
        t = Tensor(jnp.stack([x._data for x in in_tensor_list]))
        out = _run_process_level("all_to_all", t)
        out_tensor_list.extend(Tensor(out._data[i])
                               for i in range(out._data.shape[0]))
        return _Task()
    from ..ops.manipulation import stack
    t = stack(in_tensor_list, axis=1)  # [W, G, *S]
    out = _run("all_to_all", t, group)
    g = group if group is not None else _world_group()
    for i in range(g.nranks):
        out_tensor_list.append(Tensor(out._data[:, i]))
    return _Task()


alltoall = all_to_all


def gather(tensor: Tensor, gather_list=None, dst: int = 0,
           group: Optional[Group] = None, sync_op: bool = True):
    """communication/gather.py parity. Every rank contributes ``tensor``;
    ``gather_list`` receives the per-rank tensors. Single-controller SPMD
    has no rank-private host memory, so the gathered list materializes
    identically everywhere — a superset of the reference's dst-only
    guarantee (NCCL gather is allgather + discard off-dst anyway)."""
    if gather_list is None:
        raise ValueError("gather_list must be provided (the reference "
                         "requires it on the dst rank; every rank is dst "
                         "in single-controller mode)")
    all_gather(gather_list, tensor, group=group, sync_op=sync_op)
    return _Task()


def alltoall_single(out_tensor: Tensor, in_tensor: Tensor,
                    in_split_sizes=None, out_split_sizes=None,
                    group: Optional[Group] = None, sync_op: bool = True):
    """communication/all_to_all.py alltoall_single parity: dim0 of the
    rank-major payload splits evenly across ranks and blocks exchange.
    Unequal splits would need ragged all-to-all, which XLA lowers only
    for equal tiles — raise loudly rather than densify silently."""
    if in_split_sizes is not None or out_split_sizes is not None:
        raise NotImplementedError(
            "alltoall_single with unequal split sizes: XLA all-to-all "
            "exchanges equal tiles; pad to equal splits or use "
            "all_to_all with an explicit tensor list")
    # exchange a fresh wrapper: the single-tensor all_to_all path
    # replaces its argument's buffer, and the reference contract leaves
    # in_tensor untouched
    out = all_to_all(Tensor(in_tensor._data), group=group)
    out_tensor._replace_data(out._data)
    return _Task(out_tensor)


def ppermute(tensor: Tensor, perm: Sequence[Tuple[int, int]],
             group: Optional[Group] = None) -> Tensor:
    """Native collective-permute (no reference twin; the building block of
    pipeline p2p). perm = [(src, dst), ...]; un-targeted ranks get zeros."""
    return _run("ppermute", tensor, group, extra=(tuple(map(tuple, perm)),))


def send(tensor: Tensor, dst: int = 0, group: Optional[Group] = None,
         sync_op: bool = True):
    if _multiprocess():
        raise NotImplementedError(
            "cross-process send/recv is not supported: p2p pairs inside "
            "one controller only — use ppermute-based patterns or the "
            "GSPMD path for cross-process transfers")
    g = group if group is not None else _world_group()
    _groups.setdefault(g.id, g)
    g._p2p_queue.append((tensor, dst))
    return _Task()


def recv(tensor: Tensor, src: int = 0, group: Optional[Group] = None,
         sync_op: bool = True):
    if _multiprocess():
        raise NotImplementedError(
            "cross-process send/recv is not supported: p2p pairs inside "
            "one controller only — use ppermute-based patterns or the "
            "GSPMD path for cross-process transfers")
    g = group if group is not None else _world_group()
    # pair with the oldest pending send (single-controller executes both
    # sides of the reference's rank-to-rank handshake at once)
    if not g._p2p_queue:
        raise RuntimeError("recv() without a matching send() in this process")
    if len(g._p2p_queue) > 1:
        import warnings
        warnings.warn(
            "multiple sends queued: recv() pairs FIFO with the OLDEST send; "
            "issue send/recv in matching order or use batch_isend_irecv",
            RuntimeWarning, stacklevel=2)
    sent, dst = g._p2p_queue.pop(0)
    _check_rank_major(sent, group)
    _check_rank_major(tensor, group)
    cseq = -1
    if _flight._ACTIVE is not None:
        cseq = _flight.collective_enter(
            "p2p", _group_desc(group),
            shape=tuple(map(int, sent._data.shape)),
            dtype=str(sent._data.dtype))
    fn = _kernel("p2p", g.axes,
                 jax.ShapeDtypeStruct(sent._data.shape, sent._data.dtype),
                 extra=(int(src), int(dst)))
    out = fn(_to_mesh(sent._data), _to_mesh(tensor._data))
    from .watchdog import watch as _watch
    _watch("p2p", out)
    _flight.collective_exit(cseq, "p2p")
    tensor._replace_data(out)
    return _Task(tensor)


isend = send
irecv = recv


class P2POp:
    def __init__(self, op, tensor, peer, group=None):
        self.op, self.tensor, self.peer, self.group = op, tensor, peer, group


def _validate_p2p_batch(p2p_op_list: List[P2POp]) -> None:
    """Pre-dispatch validation: the batch must pair up — recvs match
    sends FIFO, shapes/dtypes agree, and nothing is left dangling.
    Catching this here turns a shape mismatch deep inside an XLA
    ppermute (or a deadlocked half-pair) into a descriptive error
    naming the offending list entries."""
    # per-group FIFO of pending sends: sends already queued on the group
    # (earlier bare send() calls) count too, labelled as such
    pending: Dict[int, List[Tuple[str, Tensor]]] = {}

    def _fifo(gr):
        g = gr if gr is not None else _world_group()
        if id(g) not in pending:
            pending[id(g)] = [("a send queued before this batch", t)
                              for t, _ in g._p2p_queue]
        return pending[id(g)]

    for i, op in enumerate(p2p_op_list):
        if not isinstance(op, P2POp):
            raise TypeError(
                f"batch_isend_irecv entry {i} is {type(op).__name__}, "
                f"expected P2POp")
        if op.op is send:
            _fifo(op.group).append((f"the send at entry {i}", op.tensor))
        elif op.op is recv:
            fifo = _fifo(op.group)
            if not fifo:
                raise ValueError(
                    f"batch_isend_irecv: recv at entry {i} has no "
                    f"matching earlier send in its group — sends pair "
                    f"FIFO with recvs; reorder the op list so every "
                    f"recv follows its send")
            label, sent = fifo.pop(0)
            if tuple(sent.shape) != tuple(op.tensor.shape):
                raise ValueError(
                    f"batch_isend_irecv: {label} (shape "
                    f"{tuple(sent.shape)}) pairs with recv at entry "
                    f"{i} (shape {tuple(op.tensor.shape)}) — buffer "
                    f"shapes must match")
            if str(sent._data.dtype) != str(op.tensor._data.dtype):
                raise ValueError(
                    f"batch_isend_irecv: {label} (dtype "
                    f"{sent._data.dtype}) pairs with recv at entry "
                    f"{i} (dtype {op.tensor._data.dtype}) — buffer "
                    f"dtypes must match")
        else:
            raise ValueError(
                f"batch_isend_irecv entry {i}: op must be isend/irecv, "
                f"got {getattr(op.op, '__name__', op.op)!r}")
    dangling = [lbl for fifo in pending.values()
                for lbl, _ in fifo if lbl.startswith("the send at")]
    if dangling:
        raise ValueError(
            f"batch_isend_irecv: {', '.join(dangling)} ha"
            f"{'s' if len(dangling) == 1 else 've'} no matching recv in "
            f"the batch — each send needs a recv or the pair deadlocks")


def batch_isend_irecv(p2p_op_list: List[P2POp]) -> List[_Task]:
    _validate_p2p_batch(p2p_op_list)
    tasks = []
    for op in p2p_op_list:
        tasks.append(op.op(op.tensor, op.peer, group=op.group))
    return tasks


def wait(tensor: Tensor, group: Optional[Group] = None, use_calc_stream=True):
    jax.block_until_ready(tensor._data)


def barrier(group: Optional[Group] = None,
            timeout: Optional[float] = None):
    """Block until every rank arrives. With ``timeout`` (seconds) the
    wait is DEADLINE-AWARE: a desynced gang raises CollectiveTimeout
    (naming group, op tag, and suspected straggler ranks) instead of
    blocking forever — the unattended-training contract."""
    if _multiprocess():
        from jax.experimental import multihost_utils as mhu

        def _sync():
            mhu.sync_global_devices("paddle2_tpu.distributed.barrier")

        if timeout is None:
            _sync()
            return _Task()
        from .watchdog import run_with_deadline
        run_with_deadline("barrier", _sync, float(timeout),
                          group_desc=f"processes={jax.process_count()}")
        return _Task()
    mesh = mesh_mod.get_mesh()
    w = mesh_mod.world_size()
    token = Tensor(jnp.zeros((w,), jnp.float32))
    _run("all_reduce_sum", token, group, timeout=timeout)
    token.numpy()
    return _Task()


def broadcast_object_list(object_list, src: int = 0,
                          group: Optional[Group] = None):
    """communication/broadcast.py broadcast_object_list: single-controller
    SPMD holds one copy of every host object already, so rank src's list
    IS the list (all_gather_object's dual)."""
    return _Task()


def scatter_object_list(out_object_list, in_object_list=None, src: int = 0,
                        group: Optional[Group] = None):
    """communication/scatter.py scatter_object_list: every logical rank
    receives its slot of src's list; single-controller materializes the
    whole per-rank view."""
    g = group if group is not None else _world_group()
    if in_object_list is None:
        raise ValueError("in_object_list must be provided on src")
    if len(in_object_list) != g.nranks:
        raise ValueError(
            f"in_object_list has {len(in_object_list)} entries for "
            f"{g.nranks} ranks")
    out_object_list.extend(in_object_list)
    return _Task()


# ------------------------------------------------------- hierarchical
# Traced ICI/DCN-hierarchical reductions (the 256-chip ladder's grad
# sync). A FLAT all-reduce over a group that crosses a DCN axis ships
# the whole 2(n-1)/n payload at DCN bandwidth; the hierarchical
# schedule keeps the heavy traffic on ICI and sends only the 1/ici_n
# partial shard across the slow wire:
#
#   1. in-slice REDUCE-SCATTER over the ICI axes (each in-slice rank
#      now owns the slice-partial sum of its 1/ici_n chunk),
#   2. cross-slice ALL-REDUCE of those partials over the DCN axes
#      (payload: 1/ici_n of the tensor),
#   3. in-slice ALL-GATHER to re-replicate the fully-reduced tensor.
#
# Value contract: the result equals the flat psum over (ici + dcn)
# EXACTLY as a sum over the same elements — hierarchical merely
# reassociates the additions (per-slice partial sums first). With
# exact-arithmetic payloads (integers, or any values whose sum is
# exactly representable) it is BITWISE equal to the flat collective;
# with arbitrary f32 payloads it agrees to reassociation rounding
# (~1 ulp), the same caveat every hierarchical/tree all-reduce in
# every framework carries. The bench gate pins both: bitwise on an
# integer-valued payload, 1-ulp allclose on random floats.


def _flatten_pad(v, n: int):
    """Flatten ``v`` and zero-pad to a multiple of ``n`` (zeros are
    sum-neutral, so padding never changes the reduced values)."""
    flat = v.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((pad,), flat.dtype)])
    return flat, pad


def hierarchical_psum(v, ici_axes, dcn_axes):
    """Traced hierarchical sum over ``ici_axes`` (in-slice) x
    ``dcn_axes`` (cross-slice) for shard_map/manual contexts; any
    shape, any dtype with an additive zero. ``ici_axes``/``dcn_axes``
    accept a name or a tuple of names; either may be empty (degrading
    to a plain psum over the other)."""
    ici = (ici_axes,) if isinstance(ici_axes, str) else tuple(ici_axes)
    dcn = (dcn_axes,) if isinstance(dcn_axes, str) else tuple(dcn_axes)
    if not ici and not dcn:
        return v
    if not ici:
        return jax.lax.psum(v, dcn)
    if not dcn:
        return jax.lax.psum(v, ici)
    # resolve from the axes BOUND IN THE TRACE, not the installed mesh
    # — a caller-constructed Mesh never routed through init_mesh would
    # otherwise silently degrade the pad/mean math
    n = 1
    for a in ici:
        n *= mesh_mod.traced_axis_size(a)
    flat, pad = _flatten_pad(v, n)
    # 1. in-slice reduce-scatter: each rank owns its 1/n partial chunk
    part = jax.lax.psum_scatter(flat, ici, scatter_dimension=0,
                                tiled=True)
    # 2. cross-slice all-reduce of the partial shard (the ONLY DCN hop)
    part = jax.lax.psum(part, dcn)
    # 3. in-slice all-gather re-replicates the fully-reduced tensor
    full = jax.lax.all_gather(part, ici, axis=0, tiled=True)
    if pad:
        full = full[:-pad]
    return full.reshape(v.shape)


def hierarchical_pmean(v, ici_axes, dcn_axes):
    """Hierarchical mean over the combined (ici x dcn) group: the
    :func:`hierarchical_psum` schedule divided by the group degree —
    the drop-in for ``jax.lax.pmean`` over both axes."""
    ici = (ici_axes,) if isinstance(ici_axes, str) else tuple(ici_axes)
    dcn = (dcn_axes,) if isinstance(dcn_axes, str) else tuple(dcn_axes)
    n = 1
    for a in ici + dcn:
        n *= mesh_mod.traced_axis_size(a)
    out = hierarchical_psum(v, ici, dcn)
    return out / n if n > 1 else out


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``shard_map`` with the varying-manual-axes check disabled — for
    programs whose results are replicated in VALUE but typed
    device-varying (hierarchical reductions, collective-matmul
    rings)."""
    return shard_map(f, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


__all__ += ["hierarchical_psum", "hierarchical_pmean",
            "shard_map_unchecked"]
