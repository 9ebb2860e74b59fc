"""What the harness and both drivers share: files found by name, host
spans, the compile tally, device facts, small statistics."""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def resolve(dotted: str):
    """``package.module.attr`` -> the attribute."""
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def load_module(package: str, name: str):
    """``benchmark/<package>/<name>.py`` as a module, found by name."""
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    return importlib.import_module(f"{package}.{name.replace('-', '_')}")


REHEARSAL = False            # run.py sets it for --rehearse
_TIMED = re.compile(r"(^|_)(s|ms|seconds)($|_)|per_s|late|median")


def note(kind: str, **fields) -> None:
    """An earlier line: anything worth a number that is not a metric.
    A rehearsal (CPU) keeps the counts and drops every time and rate."""
    if REHEARSAL:
        fields = {k: v for k, v in fields.items() if not _TIMED.search(k)}
    print(json.dumps({"note": kind, **fields}, default=float), flush=True)


class Spans:
    """Host spans of the benchmark's own, around calls into the
    program. Durations are always kept; while a trace is being taken
    each span is also written into the profiler's trace, so spans and
    device ops share one clock."""

    def __init__(self):
        self.durations: dict = {}
        self.counters: dict = {}
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.durations.setdefault(name, []).append(
                time.perf_counter() - t0)
            if ann is not None:
                ann.__exit__(None, None, None)

    def count(self, name: str, value: float = 1.0) -> None:
        self.counters[name] = self.counters.get(name, 0.0) + value

    def reset(self) -> None:
        self.durations.clear()
        self.counters.clear()


class CompileTally:
    """Compilations and persistent-cache traffic, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {"compiles": self.compiles, "cache_hits": self.hits,
               "cache_misses": self.misses}
        self.compiles = self.hits = self.misses = 0
        return out


def device_record() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the runtime counts it."""
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    v = sorted(values)
    k = max(0, min(len(v) - 1, int(-(-q * len(v) // 100)) - 1))
    return float(v[k])


def median(values) -> float:
    v = sorted(values)
    n = len(v)
    return float(v[n // 2] if n % 2 else (v[n // 2 - 1] + v[n // 2]) / 2)


def start_trace(trace_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the host spans are TraceMe's
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop_trace() -> None:
    import jax
    jax.profiler.stop_trace()
