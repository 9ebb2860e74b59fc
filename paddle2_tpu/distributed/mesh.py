"""Global device mesh — the TPU-native ProcessGroup topology.

The reference builds rank topologies out of NCCL communicators
(``paddle/phi/core/distributed/collective/process_group.h:48``,
``fleet/base/topology.py:189`` HybridCommunicateGroup). On TPU the native
equivalent is a single ``jax.sharding.Mesh`` over all chips whose NAMED AXES
are the communication groups: collectives compile to XLA HLO over an axis
(ICI ring), sub-groups are sub-axes, and hybrid parallelism is an N-D mesh
with axes ordered [dp, pp, sharding, sep, mp] like the reference's
``topology.py:195-199`` axis order.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

# axis order mirrors HybridCommunicateGroup (topology.py:195-199)
HYBRID_AXES = ("dp", "pp", "sharding", "sep", "mp")

_state: Dict[str, Optional[Mesh]] = {"mesh": None}


def init_mesh(axes: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Create (and install) the global mesh.

    ``axes`` maps axis name -> degree in rank-major order; total must equal
    the device count. Default: one data-parallel axis over every device.
    """
    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {"dp": len(devices)}
    names = tuple(axes.keys())
    sizes = tuple(int(v) for v in axes.values())
    n = int(np.prod(sizes))
    if n != len(devices):
        raise ValueError(
            f"mesh {dict(axes)} needs {n} devices, have {len(devices)}")
    mesh = Mesh(np.array(devices).reshape(sizes), names)
    _state["mesh"] = mesh
    return mesh


def set_mesh(mesh: Mesh) -> None:
    _state["mesh"] = mesh


def get_mesh(auto_init: bool = True) -> Optional[Mesh]:
    if _state["mesh"] is None and auto_init:
        init_mesh()
    return _state["mesh"]


def mesh_initialized() -> bool:
    return _state["mesh"] is not None


def axis_size(name: str) -> int:
    mesh = get_mesh()
    return int(mesh.shape[name])


def axis_degrees() -> Dict[str, int]:
    """Axis name -> degree of the installed mesh, in rank-major order
    (outermost first — the DCN-tolerant end; see spec_layout)."""
    return {k: int(v) for k, v in get_mesh().shape.items()}


def traced_axis_size(name: str) -> int:
    """Degree of mesh axis ``name`` as seen INSIDE a traced
    shard_map/pmap body: prefers ``jax.lax.axis_size`` (the axis bound
    in the trace — correct even for a caller-constructed Mesh that was
    never installed via :func:`init_mesh`). The ONE axis-size
    resolution shared by the hierarchical collectives, the compiled
    pipelines, and the collective-matmul kernels."""
    import jax
    return int(jax.lax.axis_size(name))


def group_size(axes: Sequence[str]) -> int:
    """Number of ranks in the communication group spanned by ``axes``
    (the group-size input to wire-traffic accounting)."""
    mesh = get_mesh()
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n


def dcn_axes() -> set:
    """Mesh axes mapped onto the data-center network, per the cost
    model's :class:`~paddle2_tpu.observability.cost_model.LinkModel`
    convention (ONE owner of the rule): the ``PADDLE_DCN_AXES`` env
    list, any installed axis whose name contains ``"dcn"``, and the
    dcn axes of the :func:`~paddle2_tpu.distributed.spec_layout.\
hybrid_mesh`-installed layout — the same set its link model prices
    traffic with."""
    from ..observability.cost_model import LinkModel
    link = LinkModel()
    named = set(link.dcn_axes)
    mesh = get_mesh(auto_init=False)
    if mesh is not None:
        named |= {a for a in mesh.axis_names if link.is_dcn(a)}
    from .spec_layout import installed_layout
    layout = installed_layout()
    if layout is not None:
        declared = set(layout.dcn_axes)
        if mesh is not None:
            # a later init_mesh may have replaced the hybrid mesh with
            # different axes — only honor declarations that still name
            # an installed axis
            declared &= set(mesh.axis_names)
        named |= declared
    return named


def world_size() -> int:
    return int(np.prod(list(get_mesh().shape.values())))


def replicated(x: jax.Array) -> jax.Array:
    """Commit an array as fully replicated over the mesh."""
    return jax.device_put(x, NamedSharding(get_mesh(), P()))


def constrain(x: jax.Array, spec: PartitionSpec) -> jax.Array:
    """Sharding annotation that works both eagerly and under tracing.

    Eager: a real device_put (resharding collective). Traced: a GSPMD
    sharding constraint, the pjit idiom.
    """
    sharding = NamedSharding(get_mesh(), spec)
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, sharding)
    return jax.device_put(x, sharding)
