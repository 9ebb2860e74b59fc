"""Framework core: dtypes, places, device selection, global modes.

TPU-native equivalent of the reference's place/dtype machinery
(``paddle/phi/common/place.h``, ``python/paddle/device/__init__.py:281``
``set_device``). Devices are JAX/PJRT devices; ``TPUPlace`` maps to a PJRT TPU
device, ``CPUPlace`` to host. There are no streams/events to manage — PJRT's
async dispatch plays that role (SURVEY.md §5.8).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

# float32 matmuls must be true fp32 (reference parity). bf16 training — the
# TPU-fast path — passes real bf16 operands, which hit the MXU natively and
# are unaffected by this setting.
jax.config.update("jax_default_matmul_precision", "highest")

# ---------------------------------------------------------------------------
# dtypes — exposed paddle-style (paddle.float32 is a usable dtype object)
# ---------------------------------------------------------------------------

# TPU has no native 64-bit arithmetic (XLA emulates int64 as int32 pairs and
# has no f64 path worth using); the framework runs x32 like JAX's default and
# treats 64-bit dtype requests as their 32-bit equivalents. This is a
# deliberate, documented policy — `paddle.int64` IS int32 here — so dtype
# equality checks in ported code keep working instead of silently diverging.
bool_ = jnp.bool_
uint8 = jnp.uint8
int8 = jnp.int8
int16 = jnp.int16
int32 = jnp.int32
int64 = jnp.int32
float16 = jnp.float16
bfloat16 = jnp.bfloat16
float32 = jnp.float32
float64 = jnp.float32
complex64 = jnp.complex64
complex128 = jnp.complex64

_DTYPE_ALIASES = {
    "bool": bool_, "uint8": uint8, "int8": int8, "int16": int16,
    "int32": int32, "int64": int64, "uint32": jnp.uint32, "uint64": jnp.uint32,
    "float16": float16, "bfloat16": bfloat16,
    "float32": float32, "float64": float64, "complex64": complex64,
    "complex128": complex128,
}


def convert_dtype(dtype: Any) -> Any:
    """Normalize a user-supplied dtype (str / np / jnp) to a jnp dtype,
    applying the x32 policy (64-bit names map to 32-bit types)."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        if dtype in _DTYPE_ALIASES:
            return _DTYPE_ALIASES[dtype]
        dtype = jnp.dtype(dtype).type
    else:
        dtype = jnp.dtype(dtype).type
    name = jnp.dtype(dtype).name
    if name in _DTYPE_ALIASES:
        return _DTYPE_ALIASES[name]
    return dtype


_state = threading.local()


def _tls() -> threading.local:
    if not hasattr(_state, "default_dtype"):
        _state.default_dtype = float32
        _state.grad_enabled = True
        _state.amp_state = None  # set by paddle2_tpu.amp
    return _state


def set_default_dtype(dtype: Any) -> None:
    _tls().default_dtype = convert_dtype(dtype)


def get_default_dtype() -> Any:
    return _tls().default_dtype


def is_grad_enabled() -> bool:
    return _tls().grad_enabled


def set_grad_enabled(mode: bool):
    """Context/shorthand matching paddle.set_grad_enabled."""
    return _GradModeGuard(bool(mode))


class _GradModeGuard(contextlib.ContextDecorator):
    def __init__(self, mode: bool):
        self._mode = mode
        tls = _tls()
        self._prev = tls.grad_enabled
        tls.grad_enabled = mode  # effective immediately, like paddle

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        _tls().grad_enabled = self._prev
        return False


def no_grad(func=None):
    """Disable autograd tape recording (decorator or context manager)."""
    if func is not None:
        def wrapper(*args, **kwargs):
            with _GradModeGuard(False):
                return func(*args, **kwargs)
        return wrapper
    return _GradModeGuard(False)


def enable_grad():
    return _GradModeGuard(True)


# ---------------------------------------------------------------------------
# Places / devices
# ---------------------------------------------------------------------------

class Place:
    """Base place. Wraps a JAX device (or denotes a device class)."""

    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        # LOCAL devices only: in a multi-process job jax.devices() is the
        # global list, and device_put onto another process's chip would
        # make the array unreadable here (reference semantics: a Place is
        # always a local device, device_context.h:37)
        if self.device_type == "cpu":
            devs = jax.local_devices(backend="cpu")
        else:
            devs = [d for d in jax.local_devices()
                    if _platform_matches(d, self.device_type)]
        if not devs:
            raise RuntimeError(
                f"{self!r}: this process has no {self.device_type!r} "
                f"device (JAX platform "
                f"{jax.local_devices()[0].platform!r}); a place is "
                "never served by another kind of device")
        return devs[min(self.device_id, len(devs) - 1)]


def _platform_matches(dev, device_type: str) -> bool:
    # the accelerator this framework targets is the TPU: the
    # CUDA/GPU-named places of the reference API denote it
    plat = dev.platform.lower()
    return plat == ("tpu" if device_type == "gpu" else device_type)


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


class CUDAPlace(Place):  # accepted for API parity; maps to the accelerator
    device_type = "gpu"


class CustomPlace(Place):
    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


_device_lock = threading.Lock()
_current_place: Optional[Place] = None


def _default_place() -> Place:
    if jax.devices()[0].platform.lower() == "tpu":
        return TPUPlace(0)
    return CPUPlace(0)


def set_device(device: str) -> Place:
    """paddle.device.set_device parity: 'tpu', 'tpu:0', 'cpu', 'gpu:0'."""
    global _current_place
    name, _, idx = device.partition(":")
    device_id = int(idx) if idx else 0
    if name in ("cpu",):
        place: Place = CPUPlace(device_id)
    elif name in ("tpu", "gpu", "cuda", "xpu"):
        place = TPUPlace(device_id)
    else:
        place = CustomPlace(name, device_id)
    with _device_lock:
        _current_place = place
    return place


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def current_place() -> Place:
    global _current_place
    with _device_lock:
        if _current_place is None:
            _current_place = _default_place()
        return _current_place


def device_count(device_type: str = "tpu") -> int:
    return len([d for d in jax.devices()
                if _platform_matches(d, device_type)])


def is_compiled_with_cuda() -> bool:  # API parity
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform.lower() != "cpu" for d in jax.devices())


def synchronize(device=None) -> None:
    """Block until all dispatched work completes (stream-sync parity)."""
    (jnp.zeros(()) + 0).block_until_ready()


# ---------------------------------------------------------------------------
# Helpers used across the framework
# ---------------------------------------------------------------------------

def to_jax_array(data: Any, dtype: Any = None, place: Optional[Place] = None):
    """Convert host data to a jax.Array on the current (or given) place."""
    dtype = convert_dtype(dtype)
    if isinstance(data, (bool, int, float, complex)):
        if dtype is None:
            if isinstance(data, bool):
                dtype = bool_
            elif isinstance(data, int):
                dtype = int64
            elif isinstance(data, float):
                dtype = get_default_dtype()
            else:
                dtype = complex64
        arr = np.asarray(data, dtype=dtype)
    else:
        arr = np.asarray(data)
        if dtype is not None:
            arr = arr.astype(dtype)
        elif arr.dtype == np.float64:
            arr = arr.astype(get_default_dtype())
    dev = (place or current_place()).jax_device()
    return jax.device_put(arr, dev)
