"""Which device execution belongs to which host span, and what ran
inside it.

The program's jitted entries are HLO modules (``jit_p2t_prefill``,
``jit_p2t_decode``, ``jit_p2t_kv_scatter_prefill``,
``jit_p2t_train_step``): every execution is one event of that name on
the device's "XLA Modules" line. The host span that enqueued it
(``prefill.dispatch``, ``decode.dispatch``, ``prefill.scatter``,
``train.dispatch``) carries ``program`` = the module's name, ``launch``
= the ordinal of the first execution it enqueued (``paddle2_tpu.
profiler.launch``, counted where the jitted entry is called) and
``launches`` = how many (1 where absent). Both lie in the same
``.xplane.pb`` on one clock. :func:`launches` joins them:

* a program's executions run in the order they were enqueued, so the
  module events of a name, sorted by start, are consecutive ordinals:
  event ``j`` is ordinal ``j + base``. One integer is unknown;
* **the anchor**: a device execution cannot start before the span that
  enqueued it began. The join takes the pairing that gives every span
  the EARLIEST event this allows (the smallest number of leading events
  left to executions enqueued before the trace began). One step to the
  earlier side a pair starts before its span; one step to the later
  side executions enqueued inside the trace would be given away;
* **the check**: where a span names the execution it read back
  (``read_launch`` on ``decode.dispatch``), that execution must have
  ended when the nested ``decode.readback`` ended. A pair that breaks
  either rule makes the whole program's join ``violated``;
* executions at the stretch's edges whose span or module event the
  trace does not hold stay unjoined, and are counted.

The runtime's own host-side launch events carry no id that the module
event carries too (PERF.md section 6, PR 37: the probe), so the join
is by order and anchor alone. The join is made on the first device
plane (every accepted cell has one chip).

A reader returns ``None``, never a number, when its program's join is
``violated`` or more than :data:`MAX_UNJOINED` of its executions inside
the stretch are unjoined; a program that writes no ``launch`` (an older
commit) therefore reads ``None`` everywhere.

    python3 benchmark/program_split.py <file.xplane.pb>

prints, for whoever has only the trace: per program and bucket
(``padded``; ``row_bucket x page_bucket``) the executions, median and
total device ms, ms and share by scope at both levels (``attn``,
``attn/expand``; unscoped last), the ten largest ops inside and the
lead; then how the programs add up to the busy time."""

from __future__ import annotations

import bisect
import re
import sys
from dataclasses import dataclass, field

import program_trace
import trace_reduce

PREFILL = "jit_p2t_prefill"
DECODE = "jit_p2t_decode"
SCATTER = "jit_p2t_kv_scatter_prefill"
TRAIN = "jit_p2t_train_step"
MAX_UNJOINED = 2
# every scope the programs write (PERF.md section 3): the trainer's and
# the GPT family's, then the routed, conv and block-diffusion families'
SCOPES = program_trace.SCOPES + ("moe", "conv", "unmask", "state_write")
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?=[/)]|$)")
# a kernel the program names itself, whatever scope it lies under
NAMED_KERNEL = re.compile(
    r"^%?(flash_|fused_|rmsnorm_|rope\b|paged_decode|paged_mla_decode|"
    r"moe_gmm|int[48]_)")
# path components that are the transformations' wrapping, not a scope
_WRAPPING = re.compile(r"\(|^(while|body|cond|closed_call|checkpoint|"
                       r"rematted_computation|branch_\d+_fun|pallas_call)$")
_NAME = re.compile(r"^[A-Za-z_]\w*$")
# a metric name that reads the busy share under a scope
_SCOPE_METRIC = re.compile(r"^(?:prefill_)?([a-z]+)_device_pct\.serve$")


@dataclass
class Execution:
    """One execution of ``program`` on the device: ``[start, end)`` ns;
    ``launch`` its ordinal, ``span`` the host span that enqueued it and
    ``counts`` that span's counts over those of the ``prefill`` span
    that holds it (all ``None`` / empty when unjoined); ``enqueued`` =
    the instant the enqueuing call had returned: the start of the
    span's nested ``decode.readback``, else the span's end."""
    program: str
    start: float
    end: float
    launch: int = None
    span: tuple = None
    counts: dict = field(default_factory=dict)
    enqueued: float = None

    @property
    def ns(self) -> float:
        return self.end - self.start

    def ns_within(self, window) -> float:
        """The part of its device time that lies inside ``window``."""
        return max(0.0, min(self.end, window[1]) - max(self.start, window[0]))

    @property
    def lead_ns(self):
        """How long it lay enqueued before the device took it."""
        return None if self.enqueued is None else self.start - self.enqueued


@dataclass
class Join:
    """A program's executions that run, wholly or in part, inside the
    stretch (what ``program_trace.module_time`` sums), joined ones with
    their span; ``unjoined`` counts those without one and the enqueued
    slots of the stretch's spans that have no module event."""
    program: str
    executions: list = field(default_factory=list)
    unjoined: int = 0
    violated: bool = False

    @property
    def joined(self) -> list:
        return [e for e in self.executions if e.span is not None]

    @property
    def sound(self) -> bool:
        return bool(self.joined) and not self.violated \
            and self.unjoined <= MAX_UNJOINED


def _slots(spans, program: str) -> dict:
    """{ordinal: enqueuing span} of ``program``."""
    out = {}
    for s in spans:
        c = s[3]
        if c.get("program") == program and "launch" in c:
            for i in range(int(c.get("launches", 1))):
                out[int(c["launch"]) + i] = s
    return out


def _anchor(events, slots):
    """``base`` such that event ``j`` is ordinal ``j + base``: the
    LARGEST one under which no event starts before its span began
    (larger still, and executions enqueued inside the trace would be
    given to spans that came after them). None when no pairing
    overlaps."""
    lo_ord, hi_ord = min(slots), max(slots)

    def fits(base):
        return all(events[o - base][1] >= s[1] for o, s in slots.items()
                   if 0 <= o - base < len(events))

    # event j <-> ordinal j + base; overlapping pairings only
    bases = range(hi_ord, lo_ord - len(events), -1)
    # `fits` is monotone: giving every span an earlier-enqueued ordinal's
    # LATER event keeps what fitted fitting
    lo, hi = 0, len(bases)          # first index in `bases` that fits
    while lo < hi:
        mid = (lo + hi) // 2
        if fits(bases[mid]):
            hi = mid
        else:
            lo = mid + 1
    return bases[lo] if lo < len(bases) else None


def _holder(holders, starts, span):
    """The ``prefill`` span (with ``tokens``) that holds ``span``."""
    i = bisect.bisect_right(starts, span[1]) - 1
    # (admissions do not nest: only the last one begun can hold it)
    if i >= 0 and span[2] <= holders[i][2]:
        return holders[i]
    return None


def _device(pt):
    """The device plane the join is made on: the first."""
    return min(pt.modules, default=None)


def join_program(pt, window, program: str) -> Join:
    lo, hi = window
    events = [m for m in pt.modules.get(_device(pt), ()) if m[0] == program]
    slots = _slots(pt.spans, program)
    out = Join(program)
    base = _anchor(events, slots) if events and slots else None
    by_event = {}
    if base is not None:
        by_event = {o - base: (o, s) for o, s in slots.items()
                    if 0 <= o - base < len(events)}
    admissions = [s for s in pt.spans if s[0] == "prefill"
                  and "tokens" in s[3]]
    starts = [s[1] for s in admissions]
    backs = program_trace.spans_named(pt, "decode.readback")
    back_starts = [s[1] for s in backs]

    def readback_in(span):
        """The ``decode.readback`` nested in ``span``, or None."""
        i = bisect.bisect_left(back_starts, span[1])
        return backs[i] if i < len(backs) and backs[i][2] <= span[2] \
            else None

    ends = {}                   # ordinal -> device end, for the check
    for j, (_, a, b) in enumerate(events):
        ordinal, span = by_event.get(j, (None, None))
        if ordinal is not None:
            ends[ordinal] = b
        if b <= lo or a >= hi:
            continue
        ex = Execution(program, a, b)
        if span is None:
            out.unjoined += 1
        else:
            holder = _holder(admissions, starts, span)
            ex.launch, ex.span = ordinal, span
            ex.counts = dict(holder[3] if holder else {}, **span[3])
            back = readback_in(span)
            ex.enqueued = back[1] if back else span[2]
        out.executions.append(ex)
    paired = {o for o, _ in by_event.values()}
    out.unjoined += sum(1 for o, s in slots.items()
                        if o not in paired and lo <= s[1] < hi)
    # the check: a step read back has ended when its read-back ended
    for s in program_trace.spans_named(pt, "decode.dispatch") \
            if program == DECODE else ():
        read, back = s[3].get("read_launch"), readback_in(s)
        if read in ends and back and ends[read] > back[2]:
            out.violated = True
    return out


def launches(pt, window) -> dict:
    """{program: :class:`Join`} of every program that a span of the
    trace names or that has a ``jit_p2t_`` module event on the device,
    cached on ``pt`` by window."""
    cache = pt.__dict__.setdefault("launches", {})
    if window not in cache:
        names = {s[3]["program"] for s in pt.spans if "program" in s[3]
                 and "launch" in s[3]}
        names |= {m[0] for m in pt.modules.get(_device(pt), ())
                  if m[0] in (PREFILL, DECODE, SCATTER, TRAIN)}
        cache[window] = {p: join_program(pt, window, p)
                         for p in sorted(names)}
    return cache[window]


# -- what ran inside an execution ------------------------------------------
def scope_levels(path: str) -> tuple:
    """(``attn``, ``attn/expand``) of an op's path: its outermost layer
    scope and that scope with the named scope directly under it (the
    first level again where the next component is the op's own
    primitive or a transformation's wrapping); the scan's own plumbing
    (``blocks`` and no layer inside it) is ``blocks`` at both levels, as
    ``program_trace.scope_of`` has it; (None, None) under no scope."""
    m = _SCOPE.search(path)
    if not m:
        stack = program_trace.scope_of(path)    # ``blocks`` or None
        return stack, stack
    one = m.group(1)
    rest = [p for p in path[m.end():].lstrip(")").split("/") if p]
    if len(rest) >= 2 and _NAME.match(rest[0]) \
            and not _WRAPPING.search(rest[0]):
        return one, one + "/" + rest[0]
    return one, one


def inside(pt, ex: Execution, key) -> dict:
    """{key(op): self ns} of the device ops inside one execution."""
    ops = pt.ops.get(_device(pt), [])
    starts = pt.__dict__.get("op_starts")
    if starts is None:
        starts = pt.__dict__["op_starts"] = [op[1] for op in ops]
    a = bisect.bisect_left(starts, ex.start)
    b = bisect.bisect_left(starts, ex.end)
    return program_trace.self_time_by(ops[a:b], ex.start, ex.end, key)


def is_kernel(op) -> bool:
    return any(NAMED_KERNEL.match(part) for part in op[0].split(":"))


def scope_ns(pt, executions, level: int = 0) -> dict:
    """{scope at ``level`` (0: ``attn``, 1: ``attn/expand``) or
    ``kernel`` (a named kernel under no scope) or None: self ns} over
    ``executions``."""
    total = {}

    def key(op):
        k = scope_levels(op[3])[level]
        return "kernel" if k is None and is_kernel(op) else k

    for ex in executions:
        for k, ns in inside(pt, ex, key).items():
            total[k] = total.get(k, 0.0) + ns
    return total


def bucket_of(ex: Execution) -> str:
    c = ex.counts
    if "row_bucket" in c:
        return f"{c['row_bucket']}x{c.get('page_bucket')}"
    return str(c["padded"]) if "padded" in c else ""


# -- the readers ---------------------------------------------------------------
def of(ctx) -> tuple:
    """(program trace, {program: Join}) of the traced run; the first
    reader to ask also prints the ``program_split`` note."""
    pt = program_trace.of(ctx)
    window = ctx["trace"].window
    first = window not in pt.__dict__.get("launches", {})
    joins = launches(pt, window)
    if first:
        from common import median, note
        rows = {}
        for p, j in joins.items():
            leads = [e.lead_ns / 1e6 for e in j.joined]
            rows[p] = {
                "executions": len(j.executions), "joined": len(j.joined),
                "unjoined": j.unjoined, "violated": j.violated,
                "joined_device_s": sum(e.ns_within(window)
                                       for e in j.joined) / 1e9,
                "module_time_s": program_trace.module_time(
                    pt, window, p) / 1e9,
                "lead_ms_median": median(leads) if leads else None}
        note("program_split", programs=rows)
    return pt, joins


def _sound(ctx, program: str):
    pt, joins = of(ctx)
    j = joins.get(program)
    return (pt, j) if j is not None and j.sound else (pt, None)


def prefill_device_ms_per_ktok(ctx):
    _, j = _sound(ctx, PREFILL)
    tokens = sum(e.counts.get("tokens", 0) for e in j.joined) if j else 0
    if not tokens:
        return None
    return sum(e.ns for e in j.joined) / 1e6 / (tokens / 1e3)


def prefill_device_pct(ctx):
    _, j = _sound(ctx, PREFILL)
    busy_s, _ = ctx["reduce"].busy_and_window_s(ctx["trace"])
    if j is None or not busy_s:
        return None
    window = ctx["trace"].window
    return 100.0 * sum(e.ns_within(window) for e in j.joined) / 1e9 / busy_s


def prefill_scope_pct(ctx, scope: str):
    """Share of the joined prefill executions' device time spent in ops
    under ``scope``; None where no op of them carries it (an older
    program, a stale executable: the empty-cache rule)."""
    pt, j = _sound(ctx, PREFILL)
    if j is None:
        return None
    by = scope_ns(pt, j.joined)
    total = sum(e.ns for e in j.joined)
    if scope not in by or not total:
        return None
    return 100.0 * by[scope] / total


def decode_device_ms(ctx):
    from common import median
    _, j = _sound(ctx, DECODE)
    return median([e.ns for e in j.joined]) / 1e6 if j else None


def period_gaps(j: Join) -> list:
    """ns between the device starts of executions with consecutive
    ordinals: whatever ran between two steps is inside."""
    at = {e.launch: e.start for e in j.joined}
    return [at[o + 1] - t for o, t in at.items() if o + 1 in at]


def decode_period_ms(ctx):
    from common import median
    _, j = _sound(ctx, DECODE)
    gaps = period_gaps(j) if j else []
    return median(gaps) / 1e6 if gaps else None


def dispatch_lead_ms(ctx):
    from common import median
    _, j = _sound(ctx, DECODE)
    return median([e.lead_ns for e in j.joined]) / 1e6 if j else None


def needed_scopes(cell: dict) -> set:
    """The scopes that the cell's scope-share metrics read (their names
    in the manifest say which); ``attn`` where there is no manifest."""
    out = {"attn"}
    for m in cell.get("manifest", {}).get("per_layer", ()):
        hit = _SCOPE_METRIC.match(m["name"])
        if hit and hit.group(1) in SCOPES \
                and cell["name"] in m.get("workloads", ()):
            out.add(hit.group(1))
    return out


def unscoped_pct(ctx):
    """Busy share of the ops under none of the program's scopes and in
    no kernel it names, inside the stretch; None when a scope that the
    cell's other readers need is in no op's path (its ops would be
    counted here: the empty-cache rule)."""
    pt = program_trace.of(ctx)
    lo, hi = ctx["trace"].window
    found = {scope_levels(p)[0]
             for p in {op[3] for ops in pt.ops.values() for op in ops}}
    if not needed_scopes(ctx["cell"]) <= found:
        return None
    under = total = 0.0
    for ops in pt.ops.values():
        by = program_trace.self_time_by(
            ops, lo, hi, lambda op: scope_levels(op[3])[0] is None
            and not is_kernel(op))
        under += by.get(True, 0.0)
        total += sum(by.values())
    return 100.0 * under / total if total else None


# -- the table -------------------------------------------------------------------
def _ms(ns) -> str:
    return f"{ns / 1e6:10.3f}"


def table(pt, window, out=print) -> None:
    from common import median
    joins = launches(pt, window)
    device = _device(pt)
    lo, hi = window
    busy = trace_reduce.length(trace_reduce.union(trace_reduce._clip(
        [(op[1], op[2]) for op in pt.ops.get(device, ())], lo, hi)))
    out(f"stretch {(hi - lo) / 1e6:.1f} ms, busy {busy / 1e6:.1f} ms on "
        f"{device}")
    covered = 0.0
    for program, j in joins.items():
        dev_ns = sum(e.ns_within(window) for e in j.executions)
        covered += dev_ns
        mod_ns = program_trace.module_time(pt, window, program)
        out(f"\n== {program}: {len(j.executions)} executions in the "
            f"stretch, {len(j.joined)} joined, {j.unjoined} unjoined"
            f"{', VIOLATED' if j.violated else ''}; device "
            f"{dev_ns / 1e6:.1f} ms = {100 * dev_ns / busy if busy else 0:.2f}"
            f" % of busy (module_time {mod_ns / 1e6:.1f} ms)")
        buckets = {}
        for e in j.executions:
            buckets.setdefault(
                bucket_of(e) if e.span else "unjoined", []).append(e)
        for name, execs in sorted(buckets.items(),
                                  key=lambda kv: (len(kv[0]), kv[0])):
            total = sum(e.ns for e in execs)
            leads = [e.lead_ns for e in execs if e.span]
            tokens = sum(e.counts.get("tokens", 0) for e in execs) \
                if program == PREFILL else 0
            out(f"  bucket {name or '-'}: {len(execs)} executions, median "
                f"{median([e.ns for e in execs]) / 1e6:.3f} ms, total "
                f"{total / 1e6:.1f} ms"
                + (f", lead median {median(leads) / 1e6:.3f} ms"
                   if leads else "")
                + (f", {tokens} tokens = {total / 1e3 / tokens:.3f} "
                   f"ms/ktok" if tokens else ""))
            for level in (0, 1):
                by = scope_ns(pt, execs, level)
                rows = sorted(by.items(),
                              key=lambda kv: (kv[0] is None, -kv[1]))
                out("    by scope" + (" (two levels)" if level else "")
                    + ", ms an execution and share:")
                for k, ns in rows:
                    out(f"      {k or 'unscoped':24s}"
                        f"{_ms(ns / len(execs))} {100 * ns / total:6.2f} %")
            ops = {}
            for e in execs:
                for k, ns in inside(
                        pt, e, lambda op: (op[0], scope_levels(op[3])[1])
                        ).items():
                    ops[k] = ops.get(k, 0.0) + ns
            out("    largest ops, ms an execution, share, scope:")
            for (op, scope), ns in sorted(ops.items(),
                                          key=lambda kv: -kv[1])[:10]:
                out(f"      {op[:44]:44s}{_ms(ns / len(execs))} "
                    f"{100 * ns / total:6.2f} %  {scope or 'unscoped'}")
    if busy:
        out(f"\nthe programs' executions cover {100 * covered / busy:.2f} % "
            f"of the busy time; the rest is the micro-programs "
            f"(jit_p2t_first_token, jit_p2t_state_write) and transfers")
    gaps = period_gaps(joins[DECODE]) if DECODE in joins else []
    if gaps:
        out(f"decode period (start to start, consecutive ordinals): "
            f"median {median(gaps) / 1e6:.3f} ms over {len(gaps)} gaps")


def main(path: str) -> None:
    pt = program_trace.load(path)
    table(pt, trace_reduce.load(path).window)


if __name__ == "__main__":
    main(sys.argv[1])
