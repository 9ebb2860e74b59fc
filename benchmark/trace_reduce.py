"""From a profiler trace (``.xplane.pb``) to numbers.

One reduction for every PR: device busy time as the union of device-op
intervals, time per kernel by name pattern, collective time with no
compute beside it, and idle gaps labelled by the benchmark span that was
open on the host. Host spans are the ``jax.profiler.TraceAnnotation``s
the drivers write (names starting with ``SPAN_PREFIX``), so they sit on
the trace's own clock. All times are nanoseconds until the final
results, which are seconds."""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


@dataclass
class Trace:
    """``devices``: {plane name: [(op name, start_ns, end_ns, the op's
    whole HLO line)]}, sorted by start.
    ``host``: the benchmark's spans [(name, start_ns, end_ns)].
    ``window``: (start_ns, end_ns) of the traced window."""
    devices: dict = field(default_factory=dict)
    host: list = field(default_factory=list)
    window: tuple = (0.0, 0.0)
    lines: dict = field(default_factory=dict)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def short_name(name: str) -> str:
    """The trace names a device op by its whole HLO line; keep what
    comes before `` = `` (``%fusion.12``) and the opcode with the
    kernel's own name where the line gives one."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    head = head.strip().lstrip("%")
    m = re.search(r'kernel_name[\\"=: ]+([A-Za-z0-9_.\-]+)', rest)
    if m:
        return f"{head}:{m.group(1)}"
    m = re.search(r"\b([a-z][a-z0-9\-]*)\(", rest)
    return f"{head}:{m.group(1)}" if m else head


CONTAINER = re.compile(r":(while|conditional|call)$")


def from_profile(pd) -> Trace:
    tr = Trace()
    texts = {}
    for plane in pd.planes:
        tr.lines[plane.name] = [ln.name for ln in plane.lines]
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        text = texts.setdefault(e.name, e.name)
                        ops.append((short_name(text), e.start_ns,
                                    e.start_ns + e.duration_ns, text))
            tr.devices[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX)]
    tr.host.sort(key=lambda e: e[1])
    win = [s for s in tr.host if s[0] == WINDOW_SPAN]
    if win:
        tr.window = (win[0][1], win[0][2])
    else:
        evs = [e for ops in tr.devices.values() for e in ops]
        if evs:
            tr.window = (min(e[1] for e in evs), max(e[2] for e in evs))
    return tr


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def subtract(a_union, b_union):
    """The parts of merged intervals ``a`` that no interval of merged
    ``b`` covers."""
    out, j = [], 0
    for a0, a1 in a_union:
        cur = a0
        while j < len(b_union) and b_union[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_union) and b_union[k][0] < a1:
            if b_union[k][0] > cur:
                out.append((cur, b_union[k][0]))
            cur = max(cur, b_union[k][1])
            k += 1
        if cur < a1:
            out.append((cur, a1))
    return out


def device_busy(tr: Trace, within=None) -> dict:
    """{device: busy ns inside the window (or inside ``within``, a list
    of intervals)}."""
    lo, hi = tr.window
    frames = union(_clip(within, lo, hi)) if within else [(lo, hi)]
    out = {}
    for dev, ops in tr.devices.items():
        busy = union(_clip([(e[1], e[2]) for e in ops], lo, hi))
        out[dev] = length(busy) - length(subtract(busy, frames))
    return out


def busy_and_window_s(tr: Trace, within=None) -> tuple:
    """(busy seconds averaged over the devices, window seconds)."""
    lo, hi = tr.window
    win = length(union(_clip(within, lo, hi))) if within else hi - lo
    busy = device_busy(tr, within)
    mean = sum(busy.values()) / len(busy) if busy else 0.0
    return mean / 1e9, win / 1e9


def pattern_time(tr: Trace, pattern: str) -> dict:
    """{device: (ns, number of events)} of ops whose whole HLO line
    matches ``pattern`` (kernels carry no stable names yet, so a cell's
    file tells them apart by their signature). Ops that hold other ops
    (while, conditional, call) never count."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    out = {}
    for dev, ops in tr.devices.items():
        verdicts = {}
        hit = []
        for n, a, b, text in ops:
            ok = verdicts.get(text)
            if ok is None:
                ok = verdicts[text] = bool(
                    not CONTAINER.search(n) and rx.search(text))
            if ok:
                hit.append((a, b))
        hit = _clip(hit, lo, hi)
        out[dev] = (length(hit), len(hit))
    return out


def exposed_collective(tr: Trace) -> dict:
    """{device: ns in which a collective op runs and no other op does}."""
    lo, hi = tr.window
    out = {}
    for dev, ops in tr.devices.items():
        coll = union(_clip([(e[1], e[2]) for e in ops
                            if COLLECTIVE.search(e[0])], lo, hi))
        rest = union(_clip([(e[1], e[2]) for e in ops
                            if not COLLECTIVE.search(e[0])
                            and not CONTAINER.search(e[0])], lo, hi))
        out[dev] = length(subtract(coll, rest))
    return out


def self_times(ops, lo, hi) -> dict:
    """{op name: ns} with each instant given to the innermost op open
    at it: a ``while`` that holds a scan's steps keeps only what none
    of its children covers."""
    total = {}
    stack = []          # [name, end, covered-from]
    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, since = stack.pop()
            total[name] = total.get(name, 0.0) + max(0.0, end - since)
            if stack:
                stack[-1][2] = max(stack[-1][2], end)
    for name, a, b in sorted(_clip3([e[:3] for e in ops], lo, hi),
                             key=lambda e: (e[1], -e[2])):
        close(a)
        if stack:
            top = stack[-1]
            total[top[0]] = total.get(top[0], 0.0) + max(0.0, a - top[2])
            top[2] = max(top[2], a)
        stack.append([name, b, a])
    close(float("inf"))
    return total


def _clip3(ops, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in ops
            if min(b, hi) > max(a, lo)]


def top_ops(tr: Trace, n: int = 10) -> list:
    """[[op name, seconds]]: the ops with most device SELF time,
    averaged over the devices; ops that differ only in their number
    (``fusion.12``, ``fusion.13``) are kept apart."""
    lo, hi = tr.window
    total = {}
    for ops in tr.devices.values():
        for name, ns in self_times(ops, lo, hi).items():
            total[name] = total.get(name, 0.0) + ns
    k = max(1, len(tr.devices))
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in rows]


def span_at(tr: Trace, t: float) -> str:
    """The innermost benchmark span open on the host at ``t``."""
    best = None
    for name, a, b in tr.host:
        if a <= t < b and name != WINDOW_SPAN:
            if best is None or (b - a) < (best[2] - best[1]):
                best = (name, a, b)
    return best[0][len(SPAN_PREFIX):] if best else "none"


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """[[host span, seconds]]: idle time of the busiest-idle device,
    added up by the benchmark span open on the host at the middle of
    each gap, largest first."""
    lo, hi = tr.window
    worst = None
    for ops in tr.devices.values():
        busy = union(_clip([(e[1], e[2]) for e in ops], lo, hi))
        gaps = subtract([(lo, hi)], busy)
        if worst is None or length(gaps) > length(worst):
            worst = gaps
    by = {}
    for a, b in worst or []:
        label = span_at(tr, (a + b) / 2)
        by[label] = by.get(label, 0.0) + (b - a)
    rows = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


def spans_named(tr: Trace, name: str) -> list:
    return [(a, b) for n, a, b in tr.host if n == SPAN_PREFIX + name]


def summary(tr: Trace) -> dict:
    """What a builder wants to see of a first trace."""
    return {
        "devices": {d: len(ops) for d, ops in tr.devices.items()},
        "lines": {p: ls for p, ls in tr.lines.items()
                  if not p.startswith("/host:CPU")},
        "window_s": (tr.window[1] - tr.window[0]) / 1e9,
        "host_spans": sorted({n for n, _, _ in tr.host}),
        "top_ops": top_ops(tr, 25),
    }
