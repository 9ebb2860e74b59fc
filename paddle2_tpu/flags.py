"""Global flag registry.

TPU-native analog of the reference's gflags-style global flag system
(``paddle/common/flags.cc`` — 184 ``PHI_DEFINE_EXPORTED_*`` entries, readable and
writable from Python via ``paddle.set_flags``/``get_flags``,
``python/paddle/base/framework.py:132``). Flags are env-overridable with the
``FLAGS_`` prefix, typed, and registered at import time by the subsystems that
consume them.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Iterable, List, Optional, Union


class _Flag:
    __slots__ = ("name", "value", "default", "type", "help", "on_change")

    def __init__(self, name: str, default: Any, help_str: str,
                 type_: type, on_change: Optional[Callable[[Any], None]] = None):
        self.name = name
        self.default = default
        self.type = type_
        self.help = help_str
        self.on_change = on_change
        self.value = default


_REGISTRY: Dict[str, _Flag] = {}
_LOCK = threading.RLock()


def _coerce(flag: _Flag, value: Any) -> Any:
    if flag.type is bool and isinstance(value, str):
        return value.lower() in ("1", "true", "yes", "on")
    return flag.type(value)


def define_flag(name: str, default: Any, help_str: str = "",
                on_change: Optional[Callable[[Any], None]] = None) -> None:
    """Register a flag. Environment ``FLAGS_<name>`` overrides the default."""
    with _LOCK:
        if name in _REGISTRY:
            return
        flag = _Flag(name, default, help_str, type(default), on_change)
        env = os.environ.get("FLAGS_" + name)
        if env is not None:
            flag.value = _coerce(flag, env)
        _REGISTRY[name] = flag


def set_flags(flags: Dict[str, Any]) -> None:
    """Set one or more registered flags (``paddle.set_flags`` parity)."""
    with _LOCK:
        for name, value in flags.items():
            key = name[6:] if name.startswith("FLAGS_") else name
            if key not in _REGISTRY:
                raise ValueError(f"unknown flag {name!r}")
            flag = _REGISTRY[key]
            flag.value = _coerce(flag, value)
            if flag.on_change is not None:
                flag.on_change(flag.value)


def get_flags(flags: Union[str, Iterable[str], None] = None) -> Dict[str, Any]:
    """Read registered flags (``paddle.get_flags`` parity)."""
    with _LOCK:
        if flags is None:
            names: List[str] = list(_REGISTRY)
        elif isinstance(flags, str):
            names = [flags]
        else:
            names = list(flags)
        out = {}
        for name in names:
            key = name[6:] if name.startswith("FLAGS_") else name
            if key not in _REGISTRY:
                raise ValueError(f"unknown flag {name!r}")
            out["FLAGS_" + key] = _REGISTRY[key].value
        return out


def flag_value(name: str) -> Any:
    """Fast internal read of a single flag value."""
    return _REGISTRY[name].value


# Core flags (subsystem-specific flags are defined where they are used).
define_flag("check_nan_inf", False,
            "Per-op nan/inf checking in eager mode (nan_inf_utils parity).")
define_flag("eager_vjp_cache", True,
            "Cache per-op linearized VJP computations keyed on shapes/dtypes.")
define_flag("log_level", 0, "Framework verbosity (VLOG-style).")
# -- persistent compilation cache --------------------------------------
# One rule, so the cache can be placed from outside and never moves:
#   1. JAX_COMPILATION_CACHE_DIR set -> JAX reads it itself and this
#      package never touches jax_compilation_cache_dir;
#   2. else FLAGS_compilation_cache_dir / PADDLE2_TPU_CACHE_DIR (the
#      launcher's --compile_cache_dir), '' = off;
#   3. else ONE fixed path inside the checkout, on by default. The path
#      is part of every cache key, so it is never built from a temporary
#      directory, a pid, a job id or the time. (tests/conftest.py turns
#      the default off for the test run, explicitly, with rule 2.)
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the persistent XLA cache uses ('' = off)."""
    return os.environ.get(COMPILE_CACHE_ENV) \
        or str(flag_value("compilation_cache_dir") or "")


def _apply_compilation_cache(path: str) -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    if not os.environ.get(COMPILE_CACHE_ENV):
        # empty REALLY disables (clears a previously-set directory)
        jax.config.update("jax_compilation_cache_dir", path or None)
    # min compile time gates what is worth persisting; the elastic
    # restart path (and tests) override via env — a respawned worker
    # wants EVERY train-step executable cached, since each one is pure
    # MTTR on the next recovery
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ.get("PADDLE2_TPU_CACHE_MIN_COMPILE_S", "1.0")))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the in-process cache singleton latches its configuration on first
    # compile: without a reset, changing the directory AFTER anything
    # has compiled would silently leave the old setting in force
    compilation_cache.reset_cache()


define_flag("compilation_cache_dir", os.environ.get(
    "PADDLE2_TPU_CACHE_DIR", DEFAULT_COMPILE_CACHE_DIR),
    "Persistent XLA compilation cache directory: repeat runs skip the "
    "first-compile of large programs. Defaults to the fixed "
    "<checkout>/.jax_cache; empty disables. Yields to "
    "JAX_COMPILATION_CACHE_DIR, which JAX reads itself.",
    on_change=_apply_compilation_cache)
_apply_compilation_cache(_REGISTRY["compilation_cache_dir"].value)


define_flag("conv_prefer_channels_last", False,
            "Run NCHW conv2d internally in NHWC. Measured on v5e: +26% "
            "on an isolated 3x3 conv but only +0.8% on ResNet-50 "
            "end-to-end (XLA's layout assignment already optimizes the "
            "NCHW graph) — off by default; a knob for conv-heavy models "
            "where it measures better.")
define_flag("max_program_cache_size", 32,
            "Guard-miss budget per to_static function: beyond this many "
            "compiled variants the function falls back to eager "
            "execution (SOT graph-break analog) instead of retracing "
            "per distinct value.")
define_flag("donate_optimizer_buffers", True,
            "Donate parameter/optimizer-state buffers to the fused update "
            "executable (XLA in-place aliasing; saves ~3x model size of HBM "
            "traffic per step). Disable if you hold aliases of parameter "
            "arrays across optimizer steps.")
define_flag("fused_optimizer_step", False,
            "Route AdamW/Momentum updates through the one-pass Pallas "
            "step kernels (kernels/pallas_fused.py fused_*_step): one "
            "HBM pass over (param, grad, moments) with in-place output "
            "aliases instead of XLA's multi-op chain and its staging "
            "copies. Bitwise-identical to the generic update on f32 "
            "state (bench --single-chip-speed gates it); per-optimizer "
            "fused= ctor kwarg overrides the flag either way.")


# -- XLA comm/compute-overlap knobs (multichip) -----------------------------
# The latency-hiding scheduler and async collectives are what turn the
# bucketed grad reduces and ZeRO-3 prefetch gathers from SERIAL wire
# time into overlapped wire time. They are options of the TPU runtime,
# which reads them from LIBTPU_INIT_ARGS when it loads — NOT from
# XLA_FLAGS: jaxlib's own parser (which also runs on a TPU host, for the
# CPU client) knows none of them and aborts the process on an unknown
# XLA_FLAGS token. Every token below is accepted by the installed
# libtpu (0.0.34) from LIBTPU_INIT_ARGS. They are process-wide, so they
# are NEVER applied implicitly: only apply_multichip_xla_env() (called
# by launchers / hybrid_mesh for multichip TPU runs) mutates the
# environment, and only before backend init — a single-chip CPU test
# compile never sees them.
define_flag("xla_latency_hiding_scheduler", True,
            "Schedule XLA collectives with the latency-hiding scheduler "
            "so in-flight collectives overlap independent compute "
            "(bucketed grad reduces under backward, ZeRO-3 prefetch "
            "gathers under the previous layer). Takes effect only via "
            "apply_multichip_xla_env() before backend init; no-op on "
            "CPU.")
define_flag("xla_async_collectives", True,
            "Lower all-gather / all-reduce / collective-permute as "
            "async start/done pairs so the scheduler can move compute "
            "between them. Takes effect only via "
            "apply_multichip_xla_env() before backend init; no-op on "
            "CPU.")

# flag name -> LIBTPU_INIT_ARGS tokens it expands to (tokens carry
# explicit ={true|false} so disabling a knob can OVERRIDE an operator
# default)
_XLA_PERF_FLAG_TOKENS = {
    "xla_latency_hiding_scheduler": (
        "--xla_tpu_enable_latency_hiding_scheduler={v}",
        "--xla_tpu_overlap_compute_collective_tc={v}",
    ),
    "xla_async_collectives": (
        "--xla_enable_async_all_gather={v}",
        "--xla_enable_async_collective_permute={v}",
        "--xla_tpu_enable_async_collective_fusion={v}",
        "--xla_tpu_enable_async_collective_fusion_fuse_all_gather={v}",
    ),
}


def multichip_xla_flag_tokens() -> List[str]:
    """The LIBTPU_INIT_ARGS tokens the current knob values expand
    to."""
    out: List[str] = []
    for name, tokens in _XLA_PERF_FLAG_TOKENS.items():
        v = "true" if flag_value(name) else "false"
        out.extend(t.format(v=v) for t in tokens)
    return out


def _probe_tpu_devices() -> bool:
    """Host exposes TPU device nodes: ``/dev/accel*`` (the TPU driver's
    char devices), or a VFIO group backed by a Google (PCI vendor
    0x1ae0) accelerator (v5e+ attach via vfio). Bare ``/dev/vfio/*`` is
    NOT sufficient — GPU-passthrough VMs expose those too, and the
    TPU-only XLA flags abort XLA startup on non-TPU backends."""
    import glob
    if glob.glob("/dev/accel*"):
        return True
    if glob.glob("/dev/vfio/*"):
        for vf in glob.glob("/sys/bus/pci/devices/*/vendor"):
            try:
                with open(vf) as f:
                    if f.read().strip().lower() == "0x1ae0":
                        return True
            except OSError:
                continue
    return False


def _env_platform(env) -> str:
    """Best-effort target platform from the environment WITHOUT
    importing (or initializing) jax."""
    for key in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME", "PJRT_DEVICE"):
        val = str(env.get(key, "")).strip().lower()
        if val:
            return val.split(",")[0]
    # no explicit platform: a Cloud TPU VM typically sets NONE of the
    # above (jax autodetects the chips) — probe the accelerator device
    # files directly so the overlap flags are not silently skipped on
    # the very hosts they exist for. Only consulted when env is the
    # real process environment (a caller-supplied env dict describes a
    # DIFFERENT process whose host we cannot see).
    if env is os.environ and _probe_tpu_devices():
        return "tpu"
    return ""


TPU_RUNTIME_ARGS_ENV = "LIBTPU_INIT_ARGS"


def apply_multichip_xla_env(env=None, platform: Optional[str] = None
                            ) -> str:
    """Append the overlap-scheduling flags to
    ``env['LIBTPU_INIT_ARGS']`` (where the TPU runtime reads them) and
    return the resulting string. ``XLA_FLAGS`` is never touched.

    Guard rails, because the variable is process-wide: (a) NO-OP unless
    the target platform is TPU — ``platform`` explicit, else detected
    from env vars without touching jax, so a CPU test process is never
    mutated; (b) idempotent — a token already present (from the
    operator or a previous call) is never duplicated, and the
    operator's existing value WINS over the knob default."""
    env = os.environ if env is None else env
    plat = (platform or _env_platform(env) or "").lower()
    existing = env.get(TPU_RUNTIME_ARGS_ENV, "")
    if not plat.startswith("tpu"):
        return existing
    have = {t.split("=", 1)[0] for t in existing.split() if t}
    added = [t for t in multichip_xla_flag_tokens()
             if t.split("=", 1)[0] not in have]
    if added:
        env[TPU_RUNTIME_ARGS_ENV] = " ".join(
            ([existing] if existing else []) + added)
    return env.get(TPU_RUNTIME_ARGS_ENV, "")
