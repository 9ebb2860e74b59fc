"""The one place that asks JAX which device the kernels run on.

Two platforms exist for this package: ``cpu`` (tests — Pallas kernels
are interpreted, the XLA reference paths serve attention) and ``tpu``
(Pallas kernels are compiled by Mosaic). Nothing here catches: an
exception from ``jax.devices()`` or an unknown platform propagates, so
a process that cannot reach its chip fails instead of quietly
interpreting a kernel or swapping in a reference path.
"""

from __future__ import annotations

import jax

__all__ = ["device_platform", "on_tpu", "interpret_default"]


def device_platform() -> str:
    """``"cpu"`` or ``"tpu"`` — the platform of ``jax.devices()[0]``."""
    platform = jax.devices()[0].platform.lower()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(
            f"unsupported JAX platform {platform!r}: paddle2_tpu runs on "
            "'tpu' (compiled Pallas kernels) or 'cpu' (interpreted)")
    return platform


def on_tpu() -> bool:
    return device_platform() == "tpu"


def interpret_default() -> bool:
    """Pallas ``interpret=`` default: interpret on the CPU, compile on
    the TPU."""
    return device_platform() == "cpu"
