"""paddle.device (reference python/paddle/device/__init__.py + cuda/).

TPU-native semantics: XLA dispatch is already async on a single ordered
device stream per chip, so Stream/Event are thin synchronization handles
over PJRT's completion model — record() snapshots the tail of the async
dispatch queue (a zero-copy token), wait()/synchronize() block on it.
Memory stats come from PJRT's live-buffer accounting.
"""

from __future__ import annotations

import time
from typing import Optional

__all__ = ["set_device", "get_device", "get_all_custom_device_type",
           "is_compiled_with_cuda", "is_compiled_with_rocm",
           "is_compiled_with_xpu", "is_compiled_with_custom_device",
           "Stream", "Event", "synchronize", "current_stream",
           "device_count", "get_available_device",
           "get_available_custom_device", "cuda", "max_memory_allocated",
           "max_memory_reserved", "memory_allocated", "memory_reserved"]


def _core():
    from ..framework import core
    return core


def set_device(device: str):
    return _core().set_device(device)


def get_device() -> str:
    return _core().get_device()


def device_count() -> int:
    import jax
    return jax.device_count()


def get_available_device():
    import jax
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return get_available_device()


def get_all_custom_device_type():
    import jax
    return sorted({d.platform for d in jax.devices()
                   if d.platform not in ("cpu", "gpu")})


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str = None) -> bool:
    # the TPU backend registers as a PJRT plugin — the reference's
    # CustomDevice plugin ABI analog (SURVEY §1 L0)
    import jax
    try:
        return any(d.platform not in ("cpu", "gpu")
                   for d in jax.devices())
    except Exception:
        return False


def _device_of(device=None):
    import jax
    devs = jax.devices()
    if device is None:
        return devs[0]
    if isinstance(device, int):
        return devs[device]
    if isinstance(device, str):  # "tpu:1" / "cpu:3" / "1"
        tail = device.rsplit(":", 1)[-1]
        idx = int(tail) if tail.isdigit() else 0
        return devs[idx]
    return device


class Event:
    """device/cuda Event parity. record() captures a completion token for
    everything dispatched so far; synchronize() blocks on it."""

    def __init__(self, device=None, enable_timing: bool = False,
                 blocking: bool = False, interprocess: bool = False):
        self._token = None
        self._t_done: Optional[float] = None
        self.enable_timing = enable_timing

    def record(self, stream: Optional["Stream"] = None):
        import jax
        import jax.numpy as jnp
        # a tiny device computation ordered AFTER everything already queued
        # on the (single, in-order) device stream — its readiness is the
        # event (PJRT has no explicit event object to wrap)
        self._token = jnp.zeros((), jnp.int32) + 0
        self._t_done = None

    def query(self) -> bool:
        """Non-blocking completion poll (CUDA event query contract)."""
        if self._token is None:
            return True
        try:
            return bool(self._token.is_ready())
        except AttributeError:  # older jax: fall back to blocking check
            self._token.block_until_ready()
            return True

    def synchronize(self):
        if self._token is not None:
            self._token.block_until_ready()
            if self._t_done is None:
                # completion time of everything queued before record() —
                # the first synchronize observes it (host clock)
                self._t_done = time.perf_counter()

    def elapsed_time(self, end: "Event") -> float:
        """Milliseconds between the COMPLETION of the work preceding each
        record() (device-sync'd host clock): work queued between two
        events shows up as their elapsed time, CUDA-event style. Query
        events promptly — a late first synchronize() inflates the
        measurement."""
        self.synchronize()
        end.synchronize()
        if self._t_done is None or end._t_done is None:
            return 0.0
        return (end._t_done - self._t_done) * 1e3


class Stream:
    """device/cuda Stream parity. One chip exposes one in-order XLA
    execution stream; extra Stream objects are synchronization views (the
    multi-stream overlap the reference hand-schedules is performed by
    XLA's async scheduler instead)."""

    def __init__(self, device=None, priority: int = 2):
        self.device = _device_of(device)
        self.priority = priority

    def record_event(self, event: Optional[Event] = None) -> Event:
        event = event or Event()
        event.record(self)
        return event

    def wait_event(self, event: Event):
        event.synchronize()

    def wait_stream(self, stream: "Stream"):
        synchronize()

    def query(self) -> bool:
        return True

    def synchronize(self):
        synchronize(self.device)


_current_stream = {}


def current_stream(device=None) -> Stream:
    d = _device_of(device)
    s = _current_stream.get(id(d))
    if s is None:
        s = Stream(d)
        _current_stream[id(d)] = s
    return s


def synchronize(device=None):
    return _core().synchronize()


# ----------------------------------------------------------- memory stats

def _mem_stats(device=None) -> dict:
    # the CPU backend reports None (no allocator stats); anything a
    # device raises propagates
    return _device_of(device).memory_stats() or {}


def memory_allocated(device=None) -> int:
    return int(_mem_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    return int(_mem_stats(device).get("peak_bytes_in_use",
                                      memory_allocated(device)))


def memory_reserved(device=None) -> int:
    s = _mem_stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    return int(_mem_stats(device).get("peak_bytes_in_use",
                                      memory_reserved(device)))


class cuda:
    """paddle.device.cuda namespace parity (maps onto the TPU runtime)."""
    Stream = Stream
    Event = Event

    @staticmethod
    def synchronize(device=None):
        return synchronize(device)

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def current_stream(device=None):
        return current_stream(device)

    @staticmethod
    def stream_guard(stream):
        import contextlib

        @contextlib.contextmanager
        def guard():
            yield
        return guard()

    max_memory_allocated = staticmethod(max_memory_allocated)
    max_memory_reserved = staticmethod(max_memory_reserved)
    memory_allocated = staticmethod(memory_allocated)
    memory_reserved = staticmethod(memory_reserved)

    @staticmethod
    def empty_cache():
        import gc
        gc.collect()


class XPUPlace:
    """Vendor-accelerator place: on this stack the accelerator is TPU;
    constructing an XPUPlace raises with the migration pointer."""

    def __init__(self, dev_id=0):
        raise NotImplementedError(
            "XPU is another vendor's accelerator; this framework targets "
            "TPU (set_device('tpu')).")


class IPUPlace:
    def __init__(self, dev_id=0):
        raise NotImplementedError(
            "IPU has no lowering here; this framework targets TPU "
            "(set_device('tpu')).")


def get_all_device_type():
    import jax
    return sorted({d.platform for d in jax.devices()})


def get_cudnn_version():
    """No cuDNN on the TPU stack (reference returns None when absent)."""
    return None


def is_compiled_with_cinn() -> bool:
    return False   # XLA is the compiler; CINN has no analog


def is_compiled_with_distribute() -> bool:
    return True    # jax.distributed / collectives are always built in


def is_compiled_with_ipu() -> bool:
    return False


def set_stream(stream=None):
    """device.set_stream: XLA owns stream assignment; accepted for
    source compatibility, returns the current (only) stream object."""
    return stream


class stream_guard:
    """device.stream_guard context: stream scheduling is the XLA
    compiler's decision on TPU; the guard is a no-op scope."""

    def __init__(self, stream=None):
        self.stream = stream

    def __enter__(self):
        return self.stream

    def __exit__(self, *exc):
        return False


__all__ += ["XPUPlace", "IPUPlace", "get_all_device_type",
            "get_cudnn_version", "is_compiled_with_cinn",
            "is_compiled_with_distribute", "is_compiled_with_ipu",
            "set_stream", "stream_guard"]
