"""paddle.profiler (reference python/paddle/profiler/profiler.py:358
Profiler, :120 make_scheduler, utils.py RecordEvent, timer.py ips
benchmark).

TPU-native design: the heavyweight device timeline comes from jax.profiler
(xprof/TensorBoard trace of XLA execution — the counterpart of the
reference's CUPTI tracer), while host-side op records + RecordEvent spans
are collected in-process and exported as a chrome://tracing JSON, the same
artifact the reference's chrometracing_logger.cc writes.

The program's OWN host spans are written with one primitive,
:func:`span`: a ``jax.profiler.TraceAnnotation`` named ``"p2t:" +
name``. Whatever profiler session is active (``Profiler`` here, a bare
``jax.profiler.start_trace``) gets them in its ``.xplane.pb`` on the
device ops' clock; with none a span costs what an inactive TraceMe
costs, so call sites are unconditional. :func:`build` is the span
around the first use of a newly made ``jax.jit`` entry and also feeds
the always-on :func:`builds` log. :func:`launch` counts, per HLO module
name, the executions the host has enqueued: the span that enqueues one
carries its ordinal, which is what joins a host span to the device's
"XLA Modules" events of that name (FIFO a program).
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
from enum import Enum
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "SortedKeys", "SummaryView", "benchmark", "merge_traces",
           "span", "build", "builds", "launch", "launched"]

SPAN_PREFIX = "p2t:"


def span(name: str, **counts):
    """The program's one host span: a context manager that IS a
    ``jax.profiler.TraceAnnotation`` named ``"p2t:" + name``; ``counts``
    (host-known ints and short strings) become the event's stats, and a
    count known only at the end goes through ``set_metadata(**counts)``
    on the entered span. Rules for call sites: never inside a per-row
    or per-token loop, never a device read to compute a count, never
    inside a jitted function."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **counts)


# -- launch ordinals --------------------------------------------------------
# Executions enqueued so far in this process, by the HLO module's name
# (``jit_p2t_decode``). Advanced WHERE the jitted entry is called, after
# the call returned (an entry that raised enqueued nothing); a plain int
# in a dict under the GIL, always on.
_launched: Dict[str, int] = {}


def launch(program: str) -> int:
    """One more execution of the jitted entry whose HLO module is named
    ``program`` has been enqueued; returns its ordinal (0 for the
    process's first). The device runs a program's executions in the
    order of their ordinals."""
    n = _launched.get(program, 0)
    _launched[program] = n + 1
    return n


def launched(program: str) -> int:
    """Executions of ``program`` enqueued so far: the ordinal the next
    one will get. A span that enqueues reads it before (``launch``) and
    after (``launches`` = the difference)."""
    return _launched.get(program, 0)


# -- the build log ---------------------------------------------------------
# Programs are built tens of times per process and mostly before any
# profiler session, so every build also leaves one record here, filled
# from JAX's own monitoring events between the span's start and end.
_BUILD_PARTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
# the newest MAX_BUILDS records: a server that meets new shapes for weeks
# (one scatter program per distinct prompt length) must not grow it
MAX_BUILDS = 1024
_builds: collections.deque = collections.deque(maxlen=MAX_BUILDS)
# the open build of THIS thread: JAX's monitoring callbacks fire on the
# thread that compiles, so two threads building at once (engines beside
# a trainer) each fill their own record
_open = threading.local()
_listen_lock = threading.Lock()
_listening = False


def _on_duration(event: str, duration: float, **_) -> None:
    b = getattr(_open, "build", None)
    if b is None or b._in_cost:
        return
    part = _BUILD_PARTS.get(event)
    if part is not None:
        end = time.perf_counter()
        b._intervals[part].append((end - duration, end))
    elif event == _CACHE_READ:
        b._cache_read_s += duration


def _on_event(event: str, **_) -> None:
    b = getattr(_open, "build", None)
    if b is None or b._in_cost:
        return
    if event == _CACHE_HIT:
        b._hits += 1
    elif event == _CACHE_MISS:
        b._misses += 1


def _union_s(intervals) -> float:
    """Seconds covered by ``intervals``: a jit traced inside another
    reports its own duration too, and must not count twice."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


class build:
    """``with profiler.build(program, sig) as b:`` around the first use
    of a newly made ``jax.jit`` entry (the call that traces, lowers and
    compiles or reads the cache), ``with b.cost():`` around a
    ``cost_analysis`` lowering inside it. Writes the ``build`` /
    ``build.cost`` spans and appends ``{program, sig, trace_s, lower_s,
    compile_s, cache_hit, cache_read_s, cost_s, total_s}`` to
    :func:`builds`. ``compile_s`` is JAX's backend-compile event, which
    on a persistent-cache hit is the read (``cache_read_s`` of it);
    ``cache_hit`` is None when the cache was not consulted."""

    def __init__(self, program: str, sig: str = ""):
        self.record: Dict = {"program": program, "sig": sig}
        self._span = span("build", program=program, sig=sig)
        self._intervals: Dict[str, list] = {
            part: [] for part in _BUILD_PARTS.values()}
        self._hits = self._misses = 0
        self._cache_read_s = self._cost_s = 0.0
        self._in_cost = False

    def __enter__(self):
        global _listening
        with _listen_lock:
            if not _listening:
                import jax.monitoring as mon
                mon.register_event_duration_secs_listener(_on_duration)
                mon.register_event_listener(_on_event)
                _listening = True
        self._outer = getattr(_open, "build", None)
        _open.build = self
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        total = time.perf_counter() - self._t0
        self._span.__exit__(exc_type, exc, tb)
        _open.build = self._outer
        if exc_type is None:
            rec = self.record
            for part, intervals in self._intervals.items():
                rec[part] = _union_s(intervals)
            rec["cache_hit"] = (self._misses == 0
                                if self._hits or self._misses else None)
            rec["cache_read_s"] = self._cache_read_s
            rec["cost_s"] = self._cost_s
            rec["total_s"] = total
            _builds.append(rec)
        return False

    @contextlib.contextmanager
    def cost(self):
        t0 = time.perf_counter()
        self._in_cost = True
        try:
            with span("build.cost"):
                yield
        finally:
            self._in_cost = False
            self._cost_s += time.perf_counter() - t0


def builds() -> List[Dict]:
    """The programs built in this process so far, oldest first (the
    newest ``MAX_BUILDS`` of them)."""
    return list(_builds)


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1       # accepted for API parity; maps to the TPU device stream
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SortedKeys(Enum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    GPUTotal = 3


class SummaryView(Enum):
    OverView = 0
    OpView = 1


def make_scheduler(closed: int = 0, ready: int = 0, record: int = 1,
                   repeat: int = 0, skip_first: int = 0):
    """profiler.py:120 parity: step -> ProfilerState machine."""
    cycle = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        if repeat and step >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = step % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


class _Collector:
    """In-process event sink (host spans + op records)."""

    def __init__(self):
        self.events: List[Dict] = []
        self.lock = threading.Lock()
        self.enabled = False
        self.t0 = time.perf_counter()

    def add(self, name: str, cat: str, start: float, dur: float,
            args: Optional[dict] = None):
        if not self.enabled:
            return
        with self.lock:
            self.events.append({
                "name": name, "cat": cat, "ph": "X",
                "ts": (start - self.t0) * 1e6, "dur": dur * 1e6,
                "pid": os.getpid(), "tid": threading.get_ident(),
                "args": args or {}})


_collector = _Collector()


class RecordEvent:
    """User-annotated span (reference utils.py RecordEvent / the
    nvtx-range analog). Usable as context manager or begin()/end().

    One annotation, three correlated timelines:

    * the host chrome trace (always, when a Profiler is recording);
    * the device timeline — the mark opens :func:`span`, so ANY active
      ``jax.profiler`` session (this module's ``Profiler`` or a bare
      ``jax.profiler.start_trace``) gets it as ``p2t:<name>`` beside
      the XLA execution rows;
    * the flight-recorder ring — ``user_span`` events carry the name
      and duration into crash dumps, so a post-mortem can say WHICH
      phase of the step the gang died in.
    """

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._start: Optional[float] = None
        self._span = None

    def begin(self):
        self._span = span(self.name)
        self._span.__enter__()
        from ..distributed.fault_tolerance import flight_recorder
        flight_recorder.record("user_span_begin", name=self.name)
        self._start = time.perf_counter()

    def end(self):
        if self._start is not None:
            dur = time.perf_counter() - self._start
            _collector.add(self.name, "user", self._start, dur)
            self._start = None
            self._span.__exit__(None, None, None)
            self._span = None
            from ..distributed.fault_tolerance import flight_recorder
            flight_recorder.record("user_span_end", name=self.name,
                                   dur_s=round(dur, 6))

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """Returns an on_trace_ready callback writing chrome://tracing JSON
    (chrometracing_logger.cc artifact parity)."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_time_{int(time.time())}"
                                      ".paddle_trace.json")
        prof._export_path = path
        with open(path, "w") as f:
            json.dump({"traceEvents": prof._events,
                       "displayTimeUnit": "ms"}, f)

    return handler


def load_profiler_result(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def merge_traces(dir_name: str, output_path: Optional[str] = None,
                 align: bool = True) -> dict:
    """Merge the per-process ``*.paddle_trace.json`` files under
    ``dir_name`` into ONE chrome://tracing timeline with a process lane
    per rank (reference ``tools/CrossStackProfiler/`` multi-node trace
    merger). Worker/rank identity comes from the filename prefix the
    per-rank ``export_chrome_tracing(worker_name=...)`` wrote.

    ``align=True`` shifts each rank's events so its earliest timestamp
    is 0 — per-process monotonic clocks share no epoch, so lanes are
    comparable in DURATION and STRUCTURE, not absolute offset (noted in
    the merged metadata). Returns the merged trace dict and writes it to
    ``output_path`` (default ``dir_name/merged.paddle_trace.json``)."""
    files = sorted(f for f in os.listdir(dir_name)
                   if f.endswith(".paddle_trace.json")
                   and not f.startswith("merged"))
    if not files:
        raise ValueError(f"no *.paddle_trace.json traces in {dir_name!r}")
    merged: List[Dict] = []
    for lane, fname in enumerate(files):
        worker = fname.split("_time_")[0] if "_time_" in fname \
            else fname.rsplit(".paddle_trace.json", 1)[0]
        with open(os.path.join(dir_name, fname)) as f:
            events = json.load(f).get("traceEvents", [])
        spans = [e for e in events if e.get("ph") != "M"]
        t0 = min((e["ts"] for e in spans if "ts" in e), default=0.0) \
            if align else 0.0
        merged.append({"name": "process_name", "ph": "M", "pid": lane,
                       "args": {"name": worker}})
        merged.append({"name": "process_sort_index", "ph": "M",
                       "pid": lane, "args": {"sort_index": lane}})
        for e in spans:
            e = dict(e)
            e["pid"] = lane
            if align and "ts" in e:
                e["ts"] = e["ts"] - t0
            merged.append(e)
    out = {"traceEvents": merged, "displayTimeUnit": "ms",
           "metadata": {"merged_from": files,
                        "aligned_per_rank": bool(align),
                        "note": "per-rank monotonic clocks share no "
                                "epoch; lanes are start-aligned"}}
    path = output_path or os.path.join(dir_name,
                                       "merged.paddle_trace.json")
    with open(path, "w") as f:
        json.dump(out, f)
    return out


class Profiler:
    """profiler.py:358 parity: scheduler-driven start/stop/step with
    summary and chrome-trace export; device timeline via jax.profiler."""

    def __init__(self, targets: Optional[Sequence] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 record_shapes: bool = False, profile_memory: bool = False,
                 timer_only: bool = False, emit_nvtx: bool = False,
                 custom_device_types=None, with_flops: bool = False):
        if callable(scheduler):
            self._scheduler = scheduler
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            lo, hi = scheduler
            self._scheduler = make_scheduler(closed=lo, ready=0,
                                             record=hi - lo, repeat=1)
        else:
            self._scheduler = lambda step: ProfilerState.RECORD
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self.step_num = 0
        self._state = ProfilerState.CLOSED
        self._events: List[Dict] = []
        self._step_starts: List[float] = []
        self._export_path: Optional[str] = None
        self._jax_trace_dir: Optional[str] = None

    # -- lifecycle -------------------------------------------------------
    def start(self):
        self._state = self._scheduler(self.step_num)
        _collector.enabled = self._state in (ProfilerState.RECORD,
                                             ProfilerState.RECORD_AND_RETURN)
        _collector.events = []
        self._step_starts = [time.perf_counter()]
        self._sync_device_trace()
        return self

    def _recording(self) -> bool:
        return self._state in (ProfilerState.RECORD,
                               ProfilerState.RECORD_AND_RETURN)

    def _sync_device_trace(self):
        """xprof tracing follows the scheduler: device capture runs only
        inside RECORD windows (skip_first/closed steps stay untraced)."""
        if self._timer_only:
            return
        want = self._recording()
        have = self._jax_trace_dir is not None
        if want and not have:
            try:
                self._jax_trace_dir = os.environ.get(
                    "PADDLE2_TPU_XPROF_DIR", "/tmp/paddle2_tpu_xprof")
                jax.profiler.start_trace(self._jax_trace_dir)
            except Exception:
                self._jax_trace_dir = None
        elif not want and have:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_trace_dir = None

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._step_starts:
            _collector.add(f"ProfileStep#{self.step_num}", "step",
                           self._step_starts[-1], now - self._step_starts[-1],
                           {"num_samples": num_samples})
        self._step_starts.append(now)
        self.step_num += 1
        self._state = self._scheduler(self.step_num)
        _collector.enabled = self._state in (ProfilerState.RECORD,
                                             ProfilerState.RECORD_AND_RETURN)
        self._sync_device_trace()

    def stop(self):
        if self._jax_trace_dir is not None:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_trace_dir = None
        self._events = list(_collector.events)
        _collector.enabled = False
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)
        return self

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- reporting -------------------------------------------------------
    def summary(self, sorted_by=SortedKeys.CPUTotal, op_detail: bool = True,
                thread_sep: bool = False, time_unit: str = "ms"):
        """Aggregated per-name table (reference profiler summary).
        ``sorted_by`` picks the ordering column (``SortedKeys.CPUTotal``
        / ``CPUAvg`` / ``CPUMax``; ``GPUTotal`` aliases to total — the
        device stream is the TPU timeline here, same mapping as
        ``ProfilerTarget.GPU``) and ``time_unit`` scales the duration
        columns (``"s" | "ms" | "us" | "ns"``, reflected in the row
        keys: ``total_ms`` / ``avg_ms`` / ``max_ms`` for the default)."""
        try:
            scale = {"s": 1e6, "ms": 1e3, "us": 1.0,
                     "ns": 1e-3}[time_unit]          # events store us
        except KeyError:
            raise ValueError(
                f"time_unit must be one of 's', 'ms', 'us', 'ns'; got "
                f"{time_unit!r}")
        ndigits = {"s": 6, "ms": 3, "us": 1, "ns": 0}[time_unit]
        agg: Dict[str, List[float]] = {}
        for e in self._events:
            agg.setdefault(e["name"], []).append(e["dur"] / scale)
        sort_col = {SortedKeys.CPUTotal: sum,
                    SortedKeys.GPUTotal: sum,
                    SortedKeys.CPUAvg: lambda d: sum(d) / len(d),
                    SortedKeys.CPUMax: max}.get(sorted_by, sum)
        rows = []
        for name, durs in sorted(agg.items(),
                                 key=lambda kv: -sort_col(kv[1])):
            rows.append({"name": name, "calls": len(durs),
                         f"total_{time_unit}": round(sum(durs), ndigits),
                         f"avg_{time_unit}": round(sum(durs) / len(durs),
                                                   ndigits),
                         f"max_{time_unit}": round(max(durs), ndigits)})
        return rows

    @property
    def events(self):
        return self._events


class benchmark:
    """timer.py ips benchmark parity: throughput meter (samples/s)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._t0 = None
        self._steps = 0
        self._samples = 0

    def begin(self):
        self.reset()
        self._t0 = time.perf_counter()

    def step(self, num_samples: int = 1):
        if self._t0 is None:
            self.begin()
        self._steps += 1
        self._samples += num_samples

    def end(self) -> dict:
        dt = time.perf_counter() - (self._t0 or time.perf_counter())
        return {"steps": self._steps, "elapsed_s": round(dt, 4),
                "ips": round(self._samples / dt, 2) if dt > 0 else 0.0,
                "step_per_sec": round(self._steps / dt, 2) if dt > 0
                else 0.0}


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    """profiler/profiler.py export_protobuf: scheduler callback writing
    the collected trace. The reference's .pb feeds VisualDL; the
    portable binary container here is a length-prefixed pickle of the
    same event records (chrome-trace JSON remains the interchange
    format — export_chrome_tracing)."""
    import os
    import pickle

    def handle(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        events = getattr(prof, "_events", [])
        payload = pickle.dumps({"version": 1, "events": [
            dict(e) if isinstance(e, dict) else e for e in events]})
        with open(os.path.join(dir_name, name + ".pb"), "wb") as f:
            f.write(len(payload).to_bytes(8, "little"))
            f.write(payload)
    return handle


__all__.append("export_protobuf")
