"""init_parallel_env + DataParallel (python/paddle/distributed/parallel.py:978,219).

TPU-native data parallelism: instead of an EagerReducer bucketing gradients
into NCCL all-reduces (reducer.cc), parameters are committed REPLICATED over
the mesh and the input batch is SHARDED over the 'dp' axis. Every eager op
then executes as an SPMD program; XLA inserts the gradient all-reduce itself
when the weight-grad contraction crosses the sharded batch dim — the GSPMD
equivalent of bucketed allreduce, fused and async-scheduled by the compiler.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..framework.tensor import Tensor
from ..nn.layer.layers import Layer
from . import mesh as mesh_mod
from .env import ParallelEnv, get_rank, get_world_size
from .collective import Group, _world_group

P = PartitionSpec

__all__ = ["init_parallel_env", "DataParallel", "ParallelEnv", "get_rank",
           "ParallelMode", "get_backend", "is_available", "gloo_barrier",
           "gloo_init_parallel_env", "gloo_release",
           "get_world_size"]

_initialized = {"flag": False}


class _AliasTensor(Tensor):
    """Placement-changed view of an input tensor: leaf gradient accumulation
    routes back to the user's tensor (x.grad must populate, parallel.py:219
    DataParallel contract)."""

    __slots__ = ("_origin",)

    def _accumulate_grad(self, g):
        self._origin._accumulate_grad(g)


def _maybe_init_jax_distributed() -> bool:
    """Multi-host bootstrap (the reference's TCPStore rendezvous,
    parallel.py:1134 / tcp_store.h:121): when the launcher exported a
    coordinator address, join JAX's coordination service so every
    process's local chips form ONE global device set. Idempotent."""
    import os
    addr = (os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("PADDLE_MASTER"))
    n = int(os.environ.get("JAX_NUM_PROCESSES")
            or os.environ.get("PADDLE_TRAINERS_NUM") or 1)
    if not addr or n <= 1:
        return False
    if jax.distributed.is_initialized():
        return True
    pid = int(os.environ.get("JAX_PROCESS_ID")
              or os.environ.get("PADDLE_TRAINER_ID") or 0)
    jax.distributed.initialize(coordinator_address=addr,
                               num_processes=n, process_id=pid)
    return True


def init_parallel_env(mesh_axes: Optional[dict] = None) -> ParallelEnv:
    """Bring up the parallel environment (parallel.py:978 parity).

    Multi-host: when the launcher exported PADDLE_MASTER /
    JAX_COORDINATOR_ADDRESS, this first joins the JAX coordination
    service (`jax.distributed.initialize` — the TCPStore-rendezvous
    analog), after which jax.devices() spans every host and the global
    mesh covers the whole job. Single-host: the PJRT client already
    knows every chip, so this just installs the global mesh (all chips
    on one 'dp' axis unless ``mesh_axes`` says otherwise).
    """
    _maybe_init_jax_distributed()
    if mesh_axes is not None or not mesh_mod.mesh_initialized():
        mesh_mod.init_mesh(mesh_axes)
    _initialized["flag"] = True
    return ParallelEnv()


def parallel_initialized() -> bool:
    return _initialized["flag"]


class DataParallel(Layer):
    """paddle.DataParallel parity (parallel.py:219).

    Wraps a Layer: parameters/buffers are replicated over the mesh, Tensor
    inputs get their batch dim sharded over the dp axis. Gradient sync is
    performed by XLA (see module docstring) — loss and gradients match the
    single-device run up to reduction order.
    """

    def __init__(self, layers: Layer, strategy=None, comm_buffer_size: int = 25,
                 last_comm_buffer_size: int = 1,
                 find_unused_parameters: bool = False,
                 group: Optional[Group] = None):
        super().__init__()
        if not mesh_mod.mesh_initialized():
            init_parallel_env()
        self._layers = layers
        self._group = group if group is not None else _world_group()
        self._axis = self._group.axes[0]
        self._mesh = mesh_mod.get_mesh()
        self._replicate_state()

    def _replicate_state(self):
        repl = NamedSharding(self._mesh, P())
        for p in self._layers.parameters():
            p._replace_data(jax.device_put(p._data, repl))
        for b in self._layers.buffers():
            if b is not None:
                b._replace_data(jax.device_put(b._data, repl))

    def _shard_batch(self, t: Tensor) -> Tensor:
        n = self._mesh.shape[self._axis]
        if t.ndim == 0 or t.shape[0] % n != 0:
            return t
        spec = P(self._axis, *([None] * (t.ndim - 1)))
        out = _AliasTensor.__new__(_AliasTensor)
        Tensor.__init__(out,
                        jax.device_put(t._data,
                                       NamedSharding(self._mesh, spec)),
                        stop_gradient=t.stop_gradient)
        out._grad_node = t._grad_node
        out._output_index = t._output_index
        out._hooks = t._hooks
        out._origin = t
        return out

    def forward(self, *args, **kwargs):
        args = jax.tree_util.tree_map(
            lambda x: self._shard_batch(x) if isinstance(x, Tensor) else x,
            args, is_leaf=lambda x: isinstance(x, Tensor))
        kwargs = jax.tree_util.tree_map(
            lambda x: self._shard_batch(x) if isinstance(x, Tensor) else x,
            kwargs, is_leaf=lambda x: isinstance(x, Tensor))
        return self._layers(*args, **kwargs)

    def scale_loss(self, loss):
        # grads come out globally averaged already (mean over global batch)
        return loss

    @contextlib.contextmanager
    def no_sync(self):
        # GSPMD fuses grad sync into the backward program; there is no
        # separate allreduce to skip. Accumulate on the sharded grads instead.
        yield

    # -- passthrough ------------------------------------------------------
    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_state_dict(self, state_dict, *args, **kwargs):
        out = self._layers.set_state_dict(state_dict, *args, **kwargs)
        self._replicate_state()
        return out

    set_dict = set_state_dict


class ParallelMode:
    """fleet/base/topology.py ParallelMode constants."""
    DATA_PARALLEL = 0
    TENSOR_PARALLEL = 1
    PIPELINE_PARALLEL = 2
    SHARDING_PARALLEL = 3
    SEGMENT_PARALLEL = 4


def get_backend() -> str:
    """parallel.py get_backend: the comm backend name. All collectives
    compile to XLA HLO over ICI/DCN here."""
    return "xla"


def is_available() -> bool:
    """distributed.is_available (reference parallel.py)."""
    return True


def gloo_init_parallel_env(rank_id: int, rank_num: int,
                           server_endpoint: str) -> None:
    """Reference gloo bootstrap (CPU barrier service). The coordination
    service behind init_parallel_env covers it; kept callable."""
    init_parallel_env()


def gloo_barrier() -> None:
    from .collective import barrier
    barrier()


def gloo_release() -> None:
    """No gloo store to tear down (coordination service owns lifetime)."""
