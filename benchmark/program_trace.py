"""What the PROGRAM wrote into a profiler trace, read back.

``trace_reduce.py`` keeps the benchmark's own ``bench:`` spans and names
device ops by their HLO line. Since PR 23 the program names things
itself, and this helper reads those names off the same ``.xplane.pb``:

* host spans ``p2t:<name>`` with their counts (``paddle2_tpu.profiler.
  span``; PERF.md section 3 lists every name and count);
* the scope path of each device op (JAX's ``op_name``: the program's
  ``jax.named_scope``s — embed, attn, mlp, norm, head_ce, optimizer,
  kv_write, sample — wrapped by ``jvp(...)`` / ``transpose(...)`` /
  ``rematted_computation`` as the transformations left them);
* device time per HLO module (line "XLA Modules"): the program's jitted
  entries are ``jit_p2t_train_step``, ``jit_p2t_prefill``,
  ``jit_p2t_decode``, ``jit_p2t_kv_scatter_prefill``.

A trace of a program older than that has none of them: every reader
then returns ``None`` and the harness leaves the metric out. Times are
nanoseconds until the final results.

**The empty-cache rule.** JAX's compile-cache key holds kernel and
module names but NO scope names (debug info is stripped from it), so
after a scope is added, moved or renamed a warm cache still serves the
old executable and its old paths. A scope reader therefore returns
``None`` when its scope is in no op's path (never a confident 0.0),
``unscoped_device_pct.train`` returns ``None`` when one of
``TRAIN_SCOPES`` is missing, and the ``program_builds`` note prints
which scopes were found and their sum against the busy time. Whoever
changes a scope takes the traced run on an EMPTY cache directory
(``JAX_COMPILATION_CACHE_DIR=$(mktemp -d)``)."""

from __future__ import annotations

import bisect
import re
import sys
from dataclasses import dataclass, field

import trace_reduce

SPAN_PREFIX = "p2t:"
MODULES_LINE = "XLA Modules"
SCOPES = ("embed", "attn", "mlp", "norm", "head_ce", "optimizer",
          "kv_write", "sample")
# the scan over the block stack: its own plumbing (slicing the stacked
# leaves, stacking the residuals) is what carries this scope ALONE
STACK_SCOPE = "blocks"
# the scopes the training cell's metrics read: one of them in no op's
# path means the trace is of a stale executable (the empty-cache rule)
TRAIN_SCOPES = ("attn", "mlp", "head_ce", "optimizer", STACK_SCOPE)
# a kernel the program names itself (``pl.pallas_call(name=...)``)
NAMED_KERNEL = re.compile(
    r"^%?(flash_|fused_|rmsnorm_|rope\b|paged_decode|int[48]_)")
_SCOPE_TOKEN = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?=[/)]|$)")
_STACK_TOKEN = re.compile(r"(?:^|[/(])" + STACK_SCOPE + r"(?=[/)]|$)")
# the stat of a device op's event METADATA that holds JAX's op_name
PATH_STAT = "tf_op"


@dataclass
class ProgramTrace:
    """``spans``: [(name, start_ns, end_ns, counts)] of the program's
    host spans (prefix dropped), sorted by start, outer before inner.
    ``ops``: {device: [(short name, start_ns, end_ns, scope path)]}.
    ``modules``: {device: [(module name, start_ns, end_ns)]}."""
    spans: list = field(default_factory=list)
    ops: dict = field(default_factory=dict)
    modules: dict = field(default_factory=dict)
    scope_times: dict = field(default_factory=dict)     # by window
    scopes: frozenset = None            # filled by scopes_found()


# -- the scope paths, off the raw XSpace ------------------------------------
# ``jax.profiler.ProfileData`` shows an event's own stats only. On the
# chip a device op's ``tf_op`` (JAX's op_name, "<name stack>:") is a
# stat of its event METADATA (PR 23's first chip trace, PERF.md section
# 6), so those few fields are read off the serialized proto here:
# XSpace.planes=1; XPlane.name=2 .event_metadata=4 .stat_metadata=5 (maps:
# key=1 value=2); XEventMetadata.name=2 .stats=5; XStatMetadata.name=2;
# XStat.metadata_id=1 .str_value=5 .ref_value=7.
def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf):
    """(field number, wire type, value) of one serialized message:
    ints for varints, memoryviews for the other fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an XSpace")
            value = buf[i:i + size]
            i += size
        yield number, wire, value


def _map_value(entry):
    return next((v for n, _, v in _fields(entry) if n == 2), b"")


def op_paths(raw: bytes) -> dict:
    """{device plane: {event name (the op's HLO line): scope path}}."""
    out = {}
    for number, _, plane in _fields(memoryview(raw)):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for n, _, v in _fields(plane):
            if n == 2:
                name = bytes(v).decode()
            elif n == 4:
                events.append(_map_value(v))
            elif n == 5:
                meta = {k: x for k, _, x in _fields(_map_value(v))}
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        paths = out[name] = {}
        for ev in events:
            ev_name, path = "", ""
            for n, _, v in _fields(ev):
                if n == 2:
                    ev_name = bytes(v).decode()
                elif n == 5:
                    stat = {k: x for k, _, x in _fields(v)}
                    if stat_names.get(stat.get(1)) != PATH_STAT:
                        continue
                    if 5 in stat:
                        path = bytes(stat[5]).decode()
                    elif 7 in stat:
                        path = stat_names.get(stat[7], "")
            paths[ev_name] = path.rstrip(":")
    return out


def from_serialized(raw: bytes) -> ProgramTrace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(raw)
    pt = ProgramTrace()
    paths = op_paths(raw)
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            path_of = paths.get(plane.name, {})
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops += [(trace_reduce.short_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns,
                             path_of.get(e.name, "")) for e in line.events]
                elif line.name == MODULES_LINE:
                    mods += [(e.name.split("(")[0], e.start_ns,
                              e.start_ns + e.duration_ns)
                             for e in line.events]
            pt.ops[plane.name] = sorted(ops, key=lambda e: (e[1], -e[2]))
            pt.modules[plane.name] = sorted(mods, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                pt.spans += [(e.name[len(SPAN_PREFIX):], e.start_ns,
                              e.start_ns + e.duration_ns, dict(e.stats))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX)]
    pt.spans.sort(key=lambda s: (s[1], -s[2]))
    return pt


def load(path: str) -> ProgramTrace:
    with open(path, "rb") as f:
        return from_serialized(f.read())


_LOADED = {}


def of(ctx) -> ProgramTrace:
    """The traced run's program trace, loaded once per process (the
    first reader to ask also prints the ``note`` lines)."""
    path = trace_reduce.find_xplane(ctx["cell"]["trace_dir"])
    if path not in _LOADED:
        _LOADED[path] = load(path)
        print_notes(ctx, _LOADED[path])
    return _LOADED[path]


# -- host spans -------------------------------------------------------------
def spans_named(pt: ProgramTrace, name: str, window=None) -> list:
    """The spans called ``name`` that start inside ``window``."""
    lo, hi = window or (float("-inf"), float("inf"))
    return [s for s in pt.spans if s[0] == name and lo <= s[1] < hi]


def children(pt: ProgramTrace, parent, name: str) -> list:
    """The spans called ``name`` lying inside the span ``parent``."""
    _, a, b, _ = parent
    return [s for s in pt.spans if s[0] == name and a <= s[1] and s[2] <= b]


def decode_ticks(pt: ProgramTrace, window) -> list:
    """[(tick span, its ``decode.dispatch`` span)] of the ticks that
    ran a step."""
    out = []
    for tick in spans_named(pt, "decode", window):
        disp = children(pt, tick, "decode.dispatch")
        if disp:
            out.append((tick, disp[0]))
    return out


def first_token_ns(pt: ProgramTrace, window=None) -> list:
    """Per request submitted inside ``window``: ns from the start of
    its ``submit`` span to the end of the ``prefill.readback`` of its
    first ``prefill`` after that (joined on the ``req`` count) — the
    time to the first token on the trace's own clock."""
    prefills = spans_named(pt, "prefill")
    out = []
    for sub in spans_named(pt, "submit", window):
        req = sub[3].get("req")
        mine = next((p for p in prefills
                     if p[3].get("req") == req and p[1] >= sub[1]), None)
        back = children(pt, mine, "prefill.readback") if mine else []
        if back:
            out.append(back[0][2] - sub[1])
    return out


def prefill_pad_pct(pt: ProgramTrace, window=None):
    """Of the positions the prefill programs computed (``padded``), the
    share that was padding and not prompt (``tokens``)."""
    counts = [c for _, _, _, c in spans_named(pt, "prefill", window)]
    padded = sum(c.get("padded", 0) for c in counts)
    if not padded:
        return None
    return 100.0 * (padded - sum(c.get("tokens", 0) for c in counts)) / padded


def decode_by_bucket(pt: ProgramTrace, window=None) -> list:
    """The ticks that ran a step, by the decode program they called
    (``row_bucket`` x ``page_bucket``): how many, the median tick, the
    mean ``rows`` and ``ctx_tokens`` (the cached positions the paged
    kernel walked), the median tick time per thousand of those, and
    the sequences ``evicted`` to make room."""
    from common import median
    by = {}
    for tick, disp in decode_ticks(pt, window):
        c = disp[3]
        if "row_bucket" in c:
            by.setdefault((c["row_bucket"], c["page_bucket"]), []).append(
                ((tick[2] - tick[1]) / 1e6, c))
    out = []
    for (rows, pages), ticks in sorted(by.items()):
        per_ktok = [ms / (c["ctx_tokens"] / 1e3) for ms, c in ticks
                    if c.get("ctx_tokens")]
        out.append({
            "row_bucket": rows, "page_bucket": pages, "ticks": len(ticks),
            "tick_ms": median([ms for ms, _ in ticks]),
            "rows": sum(c.get("rows", 0) for _, c in ticks) / len(ticks),
            "ctx_tokens": sum(c.get("ctx_tokens", 0)
                              for _, c in ticks) / len(ticks),
            "ms_per_ctx_ktok": median(per_ktok) if per_ktok else None,
            "evicted": sum(c.get("evicted", 0) for _, c in ticks)})
    return out


def builds_in_trace(pt: ProgramTrace, window=None) -> list:
    """[[program, sig, ms]] of the ``build`` spans: WHICH program, at
    which shapes, was built while the profiler ran (inside a measured
    window there should be none: ``window_compiles`` only counts)."""
    return [[c.get("program"), c.get("sig"), (b - a) / 1e6]
            for _, a, b, c in spans_named(pt, "build", window)]


def steady_steps(pt: ProgramTrace, window=None) -> list:
    """The ``train.step`` spans that built nothing (``built`` 0)."""
    return [s for s in spans_named(pt, "train.step", window)
            if not s[3].get("built")]


def innermost_segments(spans) -> list:
    """The host's timeline cut into [(start, end, name)] pieces, each
    labelled with the INNERMOST span open during it; sorted, disjoint.
    Spans nest (one thread drives the program); one that straddles its
    neighbour's end is cut there."""
    out, stack = [], []          # stack of [name, end, covered-from]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, since = stack.pop()
            if end > since:
                out.append((since, end, name))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, a, b, _ in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(a)
        if stack:
            top = stack[-1]
            b = min(b, top[1])
            if a > top[2]:
                out.append((top[2], a, top[0]))
            top[2] = max(top[2], a)
        if b > a:
            stack.append([name, b, a])
    close(float("inf"))
    return sorted(out)


def idle_by_span(pt: ProgramTrace, tr, within=None) -> dict:
    """{innermost program span or None: idle ns} of the device with
    most idle time, inside the traced window (or inside ``within``, a
    list of intervals): every instant of a gap goes to the innermost
    span open on the host at it, None where no span is open."""
    lo, hi = tr.window
    frames = trace_reduce.union(trace_reduce._clip(within, lo, hi)) \
        if within else [(lo, hi)]
    worst = None
    for ops in tr.devices.values():
        busy = trace_reduce.union(
            trace_reduce._clip([(e[1], e[2]) for e in ops], lo, hi))
        gaps = trace_reduce.subtract(frames, busy)
        if worst is None or trace_reduce.length(gaps) > \
                trace_reduce.length(worst):
            worst = gaps
    segments = innermost_segments(pt.spans)
    starts = [s[0] for s in segments]
    by = {}
    for a, b in worst or []:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(segments) and segments[i][0] < b:
            s0, s1, label = segments[i]
            part = min(b, s1) - max(a, s0)
            if part > 0:
                by[label] = by.get(label, 0.0) + part
                covered += part
            i += 1
        if b - a > covered:
            by[None] = by.get(None, 0.0) + (b - a - covered)
    return by


# -- device ops by scope ----------------------------------------------------
def scope_of(path: str):
    """The OUTERMOST layer scope in an op's path (``attn/norm/mul``
    belongs to attn); ``blocks`` where the path holds the scan and no
    layer inside it; else None."""
    m = _SCOPE_TOKEN.search(path)
    if m:
        return m.group(1)
    return STACK_SCOPE if _STACK_TOKEN.search(path) else None


def pass_of(path: str) -> str:
    if "rematted_computation" in path:
        return "recomputed"
    return "bwd" if "transpose(" in path else "fwd"


def self_time_by(ops, lo, hi, key) -> dict:
    """{key(op): ns} with each instant given to the innermost op open
    at it (a ``while`` keeps only what none of its children covers)."""
    total, stack = {}, []        # stack of [key, end, covered-from]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            k, end, since = stack.pop()
            total[k] = total.get(k, 0.0) + max(0.0, end - since)
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for op in ops:
        a, b = max(op[1], lo), min(op[2], hi)
        if b <= a:
            continue
        close(a)
        if stack:
            top = stack[-1]
            total[top[0]] = total.get(top[0], 0.0) + max(0.0, a - top[2])
            top[2] = max(top[2], a)
        stack.append([key(op), b, a])
    close(float("inf"))
    return total


def scope_times(pt: ProgramTrace, window) -> dict:
    """{(scope or None, pass): ns}, device self time averaged over the
    devices. A named kernel outside every scope counts under its own
    name; None is what carries neither."""
    if window in pt.scope_times:
        return pt.scope_times[window]
    lo, hi = window
    total = {}

    def key(op):
        scope = scope_of(op[3])
        if scope is None and any(NAMED_KERNEL.match(part)
                                 for part in op[0].split(":")):
            scope = "kernel"
        return scope, pass_of(op[3])

    for ops in pt.ops.values():
        for k, ns in self_time_by(ops, lo, hi, key).items():
            total[k] = total.get(k, 0.0) + ns
    n = max(1, len(pt.ops))
    pt.scope_times[window] = {k: ns / n for k, ns in total.items()}
    return pt.scope_times[window]


def scopes_found(pt: ProgramTrace) -> frozenset:
    """The scopes that own at least one device op of the trace."""
    if pt.scopes is None:
        paths = {op[3] for ops in pt.ops.values() for op in ops}
        pt.scopes = frozenset(map(scope_of, paths)) - {None}
    return pt.scopes


def scope_ms_per_step(ctx, scope: str):
    """Device self time of the ops under ``scope`` (forward, backward
    and recomputed together), per traced step, in ms; None where no
    op's path holds ``scope`` (an older program, or a stale executable:
    the empty-cache rule)."""
    pt = of(ctx)
    if scope not in scopes_found(pt) or not ctx.get("steps"):
        return None
    ns = sum(v for (s, _), v in scope_times(pt, ctx["trace"].window).items()
             if s == scope)
    return ns / 1e6 / ctx["steps"]


def unscoped_pct(ctx):
    """Share of the busy time under no scope and in no named kernel;
    None unless every one of ``TRAIN_SCOPES`` is in the trace (with
    one missing its ops would be counted here)."""
    pt = of(ctx)
    if not scopes_found(pt).issuperset(TRAIN_SCOPES):
        return None
    times = scope_times(pt, ctx["trace"].window)
    busy = sum(times.values())
    if not busy:
        return None
    return 100.0 * sum(v for (s, _), v in times.items() if s is None) / busy


def module_time(pt: ProgramTrace, window, name: str) -> float:
    """Device ns inside ``window`` of the HLO module ``name``, averaged
    over the devices."""
    lo, hi = window
    total = 0.0
    for mods in pt.modules.values():
        total += trace_reduce.length(trace_reduce.union(trace_reduce._clip(
            [(a, b) for n, a, b in mods if n == name], lo, hi)))
    return total / max(1, len(pt.modules))


# -- the build log ------------------------------------------------------------
def builds():
    """``paddle2_tpu.profiler.builds()``, or None where the program has
    no build log."""
    try:
        from paddle2_tpu import profiler
        return profiler.builds()
    except (ImportError, AttributeError):
        return None


def program_build_s():
    log = builds()
    if not log:
        return None
    return float(sum(b["total_s"] for b in log))


# -- notes --------------------------------------------------------------------
def print_notes(ctx, pt: ProgramTrace) -> None:
    """Three earlier lines of a traced run: where the device's idle
    time lies by the program's own spans, what set-up built (with the
    scope split and its sum check), and what the serving spans' counts
    say (time to the first token by ``req``, ticks by decode program
    against the context they walked, prefill padding)."""
    from common import median, note, percentile
    tr = ctx["trace"]
    if pt.spans:
        by = idle_by_span(pt, tr)
        rows = sorted(by.items(), key=lambda kv: -kv[1])[:10]
        note("idle_by_program_span",
             idle_s=[[k or "none", ns / 1e9] for k, ns in rows],
             spans={n: sum(1 for s in pt.spans if s[0] == n)
                    for n in sorted({s[0] for s in pt.spans})})
    log = builds()
    if log is not None:
        parts = ("trace_s", "lower_s", "compile_s", "cache_read_s",
                 "cost_s", "total_s")
        by_program = {}
        for b in log:
            row = by_program.setdefault(
                b["program"], dict.fromkeys(parts, 0.0) | {
                    "builds": 0, "cache_hits": 0})
            row["builds"] += 1
            row["cache_hits"] += bool(b.get("cache_hit"))
            for p in parts:
                row[p] += b.get(p, 0.0)
        fields = {"builds_by_program_s": by_program,
                  "builds_s": [[b["program"], b.get("sig"), b["total_s"],
                              b.get("cache_hit")] for b in log]}
        if pt.spans:
            fields["builds_in_trace_ms"] = builds_in_trace(pt)
        if pt.ops and scopes_found(pt) and ctx.get("steps"):
            times = scope_times(pt, tr.window)
            per_step = {}
            for (scope, which), ns in times.items():
                per_step.setdefault(scope or "unscoped", {})[which] = \
                    ns / 1e6 / ctx["steps"]
            fields["scope_ms_per_step"] = per_step
            # the sum check: what the training cell's scope metrics and
            # unscoped_device_pct.train cover of the busy time
            busy = sum(times.values())
            fields["scopes_found"] = sorted(scopes_found(pt))
            fields["scopes_missing"] = sorted(
                set(TRAIN_SCOPES) - scopes_found(pt))
            fields["metric_scopes_pct_of_busy"] = 100.0 * sum(
                v for (s, _), v in times.items()
                if s is None or s in TRAIN_SCOPES) / busy if busy else None
        note("program_builds", **fields)
    waits = [ns / 1e6 for ns in first_token_ns(pt, tr.window)]
    counts = {
        "first_token_ms": {"requests": len(waits), "p50": median(waits),
                           "p95": percentile(waits, 95)} if waits else None,
        "decode_by_bucket_ms": decode_by_bucket(pt, tr.window) or None,
        "prefill_pad_pct": prefill_pad_pct(pt, tr.window)}
    counts = {k: v for k, v in counts.items() if v is not None}
    if counts:
        note("program_counts", **counts)


def describe(path: str, n: int = 3) -> None:
    """A first look at a trace by hand: planes and lines, then one
    device op, one module and one program span with every stat, then
    what the spans' counts say (builds, time to first token, prefill
    padding, ticks by decode program)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    paths = op_paths(raw)
    for plane in pd.planes:
        print("plane", plane.name, [ln.name for ln in plane.lines][:12])
        for line in plane.lines:
            device = trace_reduce.DEVICE_PLANE.match(plane.name)
            if not ((device and line.name in (trace_reduce.OPS_LINE,
                                              MODULES_LINE))
                    or plane.name.startswith("/host:")):
                continue
            shown = 0
            for e in line.events:
                if not device and not e.name.startswith(SPAN_PREFIX):
                    continue
                print(" line", line.name, "| event", e.name[:400])
                if device and line.name == trace_reduce.OPS_LINE:
                    print("     metadata stat", PATH_STAT, "=",
                          paths[plane.name].get(e.name))
                for k, v in e.stats:
                    print("     stat", k, "=", str(v)[:300])
                shown += 1
                if shown >= n:
                    break
    pt = from_serialized(raw)
    print("builds in the trace [program, sig, ms]:", builds_in_trace(pt))
    print("ms from submit to first token, by req:",
          [ns / 1e6 for ns in first_token_ns(pt)])
    print("prefill padding %:", prefill_pad_pct(pt))
    for row in decode_by_bucket(pt):
        print("decode program", row)


if __name__ == "__main__":
    describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 3)
