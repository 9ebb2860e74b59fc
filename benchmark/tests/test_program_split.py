"""``program_split.py`` on a trace built by ``make_program_trace.py``'s
method (its ``Plane``): two programs interleaved on one device, two
prefill buckets, a K and a V scatter behind each prefill, a decode loop
one step ahead whose last step is still queued when the trace ends.
Times in ns from the window's start (the planes start at 1000 ns).

device, "XLA Modules" (and the ops inside, with their scope paths):
    D40 [40, 140)   paged_decode.1 40 attn, fusion.d 50 moe/router,
                    fusion.e 10 sample  (every decode step alike)
    P5  [140, 340)  fusion.a 60 attn/expand, flash_fwd.1 50 attn,
                    moe_gmm.1 60 moe/experts, copy.9 30 (no path)
    S10 [340, 360), S11 [360, 380)   copy.5 kv_write
    D41 [380, 480), D42 [480, 580)
    P6  [580, 980)  the same ops, twice as long (bucket 1024)
    S12 [980, 990), S13 [990, 1000)
host: decode.dispatch [10, 30) launch 40; prefill [50, 100) tokens 500
    padded 512 > prefill.dispatch [52, 70) launch 5, prefill.scatter
    [72, 98) launch 10 launches 2; decode.dispatch [110, 150) launch 41
    read_launch 40 > decode.readback [120, 150); decode.dispatch
    [160, 490) launch 42 read_launch 41 > decode.readback [170, 490);
    prefill [500, 560) tokens 1000 padded 1024 > prefill.dispatch
    [502, 520) launch 6, prefill.scatter [522, 558) launch 12 launches
    2; decode.dispatch [570, 600) launch 43 read_launch 42 >
    decode.readback [575, 600): its execution is not in the trace
busy [40, 1000) = 960; prefill 600 over 1,500 tokens; attn 110 + 220,
moe 60 + 120, unscoped 30 + 60; decode starts 40, 380, 480; enqueued
30 (no read-back: the span's end), 120, 170."""

import json
import os

import pytest

import program_split as S
import program_trace as P
import run
import trace_reduce
from make_program_trace import Plane

HERE = os.path.dirname(os.path.abspath(__file__))
SERVING = ["gpt2m-serve-longdoc-backlog", "lfm2moe-serve-doc3k-backlog",
           "sdar-serve-gen512-backlog", "dsv2-serve-doc5k-backlog"]
CELLS = {
    "prefill_device_ms_per_ktok.serve": SERVING,
    "prefill_device_pct.serve": SERVING,
    "prefill_attn_device_pct.serve": SERVING,
    "prefill_moe_device_pct.serve": SERVING[1:],
    "decode_device_ms.serve": SERVING,
    "decode_period_ms.serve": SERVING,
    "dispatch_lead_ms.serve": SERVING,
    "unscoped_device_pct.serve": SERVING,
}
HAND = {
    "prefill_device_ms_per_ktok.serve": 600e-6 / 1.5,
    "prefill_device_pct.serve": 100 * 600 / 960,
    "prefill_attn_device_pct.serve": 100 * 330 / 600,
    "prefill_moe_device_pct.serve": 100 * 180 / 600,
    "decode_device_ms.serve": 100e-6,
    "decode_period_ms.serve": (340 + 100) / 2 * 1e-6,
    "dispatch_lead_ms.serve": 260e-6,
    "unscoped_device_pct.serve": 100 * 90 / 960,
}
PRE, DEC, SCA = "jit(p2t_prefill)/", "jit(p2t_decode)/", \
    "jit(p2t_kv_scatter_prefill)/"
KERNEL = ' custom-call(%q), custom_call_target="tpu_custom_call"'


def decode_ops(s):
    return [("%paged_decode.1 = bf16[8]{0}" + KERNEL, s, 40,
             DEC + "attn/jit(paged)/paged_decode/pallas_call"),
            ("%fusion.d = bf16[8]{0} fusion(%p), kind=kLoop", s + 40, 50,
             DEC + "moe/router/dot_general"),
            ("%fusion.e = s32[8]{0} fusion(%p), kind=kLoop", s + 90, 10,
             DEC + "sample/argmax")]


def prefill_ops(s, k):
    return [("%fusion.a = bf16[8]{0} fusion(%p), kind=kOutput", s, 60 * k,
             PRE + "attn/expand/dot_general"),
            ("%flash_fwd.1 = bf16[8]{0}" + KERNEL, s + 60 * k, 50 * k,
             PRE + "attn/jit(flash_bshd)/flash_fwd/pallas_call"),
            ("%moe_gmm.1 = bf16[8]{0}" + KERNEL, s + 110 * k, 60 * k,
             PRE + "moe/experts/jit(moe_gmm)/moe_gmm/pallas_call"),
            ("%copy.9 = bf16[8]{0} copy(%p)", s + 170 * k, 30 * k, "")]


def scatter_op(s, n):
    return [("%copy.5 = bf16[8]{0} copy(%p)", s, n,
             SCA + "kv_write/scatter")]


OPS = (decode_ops(40) + prefill_ops(140, 1) + scatter_op(340, 20)
       + scatter_op(360, 20) + decode_ops(380) + decode_ops(480)
       + prefill_ops(580, 2) + scatter_op(980, 10) + scatter_op(990, 10))
MODULES = [("jit_p2t_decode(7)", 40, 100, ""),
           ("jit_p2t_prefill(5)", 140, 200, ""),
           ("jit_p2t_kv_scatter_prefill(9)", 340, 20, ""),
           ("jit_p2t_kv_scatter_prefill(9)", 360, 20, ""),
           ("jit_p2t_decode(7)", 380, 100, ""),
           ("jit_p2t_decode(7)", 480, 100, ""),
           ("jit_p2t_prefill(6)", 580, 400, ""),
           ("jit_p2t_kv_scatter_prefill(9)", 980, 10, ""),
           ("jit_p2t_kv_scatter_prefill(9)", 990, 10, "")]
STEP = {"rows": 2, "row_bucket": 4, "page_bucket": 8, "ctx_tokens": 900,
        "program": S.DECODE}


def host(launch=True):
    def enq(program, first, **more):
        return dict(program=program, launch=first, **more) if launch else {}

    def step(first, read=None):
        c = dict(STEP, launch=first) if launch else {
            k: v for k, v in STEP.items() if k != "program"}
        if read is not None and launch:
            c["read_launch"] = read
        return c

    return [
        ("bench:traced_window", 0, 1000, {}),
        ("p2t:decode.dispatch", 10, 20, step(40)),
        ("p2t:prefill", 50, 50, {"req": 0, "tokens": 500, "padded": 512,
                                 "ahead": 1}),
        ("p2t:prefill.dispatch", 52, 18, enq(S.PREFILL, 5, launches=1)),
        ("p2t:prefill.scatter", 72, 26, enq(S.SCATTER, 10, launches=2)),
        ("p2t:decode.dispatch", 110, 40, step(41, 40)),
        ("p2t:decode.readback", 120, 30, {}),
        ("p2t:decode.dispatch", 160, 330, step(42, 41)),
        ("p2t:decode.readback", 170, 320, {}),
        ("p2t:prefill", 500, 60, {"req": 1, "tokens": 1000, "padded": 1024,
                                  "ahead": 1}),
        ("p2t:prefill.dispatch", 502, 18, enq(S.PREFILL, 6, launches=1)),
        ("p2t:prefill.scatter", 522, 36, enq(S.SCATTER, 12, launches=2)),
        ("p2t:decode.dispatch", 570, 30, step(43, 42)),
        ("p2t:decode.readback", 575, 25, {}),
    ]


def serialized(launch=True) -> bytes:
    from jax.profiler import ProfileData
    dev = Plane(1, "/device:TPU:0")
    dev.line(1, "XLA Ops", OPS)
    dev.line(2, "XLA Modules", MODULES)
    plane = Plane(2, "/host:CPU")
    plane.line(1, "python", host(launch))
    return ProfileData.text_proto_to_serialized_xspace(
        dev.text() + plane.text())


def ctx_of(monkeypatch, raw: bytes, cell="dsv2-serve-doc5k-backlog"):
    from jax.profiler import ProfileData
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "a trace")
    monkeypatch.setattr(P, "load", lambda path: P.from_serialized(raw))
    monkeypatch.setattr(P, "builds", lambda: None)
    monkeypatch.setattr(P, "_LOADED", {})
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    trace = trace_reduce.from_profile(ProfileData.from_serialized_xspace(raw))
    return {"trace": trace, "reduce": trace_reduce,
            "cell": {"trace_dir": "unused", "name": cell,
                     "manifest": manifest}}


@pytest.fixture()
def ctx(monkeypatch):
    return ctx_of(monkeypatch, serialized())


@pytest.mark.parametrize("metric", sorted(HAND))
def test_reader_by_hand_arithmetic(ctx, metric):
    assert run.read_layer_metric(metric, ctx) == pytest.approx(HAND[metric])


def test_the_join_pairs_every_execution_with_its_span(ctx):
    pt, joins = S.of(ctx)
    assert sorted(joins) == [S.DECODE, S.SCATTER, S.PREFILL]
    assert [(e.launch, e.start - 1000) for e in joins[S.DECODE].joined] == \
        [(40, 40), (41, 380), (42, 480)]
    # launch 43 was enqueued inside the stretch and never ran in it
    assert joins[S.DECODE].unjoined == 1 and joins[S.DECODE].sound
    assert [(e.launch, S.bucket_of(e)) for e in joins[S.PREFILL].joined] \
        == [(5, "512"), (6, "1024")]
    assert [e.counts["tokens"] for e in joins[S.PREFILL].joined] == [500,
                                                                     1000]
    # two scatters a span, each holding its prefill's counts
    assert [(e.launch, e.counts["padded"])
            for e in joins[S.SCATTER].joined] == [
        (10, 512), (11, 512), (12, 1024), (13, 1024)]
    assert all(j.unjoined == 0 for p, j in joins.items() if p != S.DECODE)
    assert not any(j.violated for j in joins.values())
    for program, j in joins.items():
        # what the join gives a program is what the module line holds
        assert sum(e.ns for e in j.joined) == pytest.approx(
            P.module_time(pt, ctx["trace"].window, program))
        assert all(e.start >= e.span[1] for e in j.joined)
    assert [S.bucket_of(e) for e in joins[S.DECODE].joined] == ["4x8"] * 3


def test_scope_levels_and_the_split_inside_one_bucket(ctx):
    assert S.scope_levels(PRE + "attn/expand/dot_general") == (
        "attn", "attn/expand")
    assert S.scope_levels(PRE + "attn/dot_general") == ("attn", "attn")
    assert S.scope_levels(
        PRE + "attn/jit(flash_bshd)/flash_fwd/pallas_call") == ("attn",
                                                                "attn")
    assert S.scope_levels("jit(p2t_train_step)/transpose(jvp(mlp))/mul") \
        == ("mlp", "mlp")
    assert S.scope_levels("") == (None, None)
    # under the scan a layer's scope owns the op; the scan's plumbing
    # is what carries ``blocks`` alone
    scan = "jit(p2t_train_step)/transpose(jvp(blocks))/while/body/"
    assert S.scope_levels(scan + "closed_call/checkpoint/"
                          "rematted_computation/attn/norm/mul") == (
        "attn", "attn/norm")
    assert S.scope_levels(scan + "dynamic_slice") == ("blocks", "blocks")
    pt, joins = S.of(ctx)
    big = [e for e in joins[S.PREFILL].joined if S.bucket_of(e) == "1024"]
    assert S.scope_ns(pt, big, 0) == {"attn": 220.0, "moe": 120.0,
                                      None: 60.0}
    assert S.scope_ns(pt, big, 1) == {"attn/expand": 120.0, "attn": 100.0,
                                      "moe/experts": 120.0, None: 60.0}


def test_unscoped_says_nothing_without_a_scope_the_cell_reads(monkeypatch):
    """The SDAR cell reads ``unmask_device_pct.serve``: a trace with no
    op under ``unmask`` is of a stale executable (the empty-cache
    rule), and its unscoped share is not a number."""
    ctx = ctx_of(monkeypatch, serialized(), "sdar-serve-gen512-backlog")
    assert S.needed_scopes(ctx["cell"]) == {"attn", "moe", "unmask"}
    assert run.read_layer_metric("unscoped_device_pct.serve", ctx) is None
    ctx = ctx_of(monkeypatch, serialized(), "gpt2m-serve-longdoc-backlog")
    assert S.needed_scopes(ctx["cell"]) == {"attn"}
    assert run.read_layer_metric("unscoped_device_pct.serve", ctx) \
        == pytest.approx(HAND["unscoped_device_pct.serve"])


@pytest.mark.parametrize("metric", sorted(set(HAND)
                                          - {"unscoped_device_pct.serve"}))
def test_reader_says_nothing_of_a_program_without_launch_counts(
        monkeypatch, metric):
    """The parent's spans (no ``program`` / ``launch``): None, never a
    raise — the same readers run over the parent's traces."""
    old = ctx_of(monkeypatch, serialized(launch=False))
    assert run.read_layer_metric(metric, old) is None


@pytest.mark.parametrize("metric", sorted(HAND))
def test_reader_finds_nothing_in_a_trace_without_the_programs_names(
        monkeypatch, metric):
    with open(os.path.join(HERE, "tiny_trace.pbtxt")) as f:
        from jax.profiler import ProfileData
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    assert run.read_layer_metric(metric, ctx_of(monkeypatch, raw)) is None


def test_cli_prints_the_table(tmp_path, capsys):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(serialized())
    S.main(str(path))
    out = capsys.readouterr().out
    assert "== jit_p2t_prefill: 2 executions in the stretch, 2 joined, " \
        "0 unjoined" in out
    assert "== jit_p2t_decode: 3 executions in the stretch, 3 joined, " \
        "1 unjoined" in out
    assert "bucket 1024: 1 executions" in out and "bucket 512: 1" in out
    assert "bucket 4x8: 3 executions" in out
    assert "attn/expand" in out and "moe/experts" in out
    # unscoped comes last in a bucket's scope rows
    rows = out.split("== jit_p2t_prefill")[1].split("bucket 1024")[1] \
        .split("largest ops")[0].splitlines()
    scope_rows = [r.split()[0] for r in rows if r.startswith("      ")]
    assert scope_rows[:3] == ["attn", "moe", "unscoped"]
    assert "cover 100.00 % of the busy time" in out
    assert "decode period (start to start, consecutive ordinals): median " \
        "0.000 ms over 2 gaps" in out


def test_every_new_metric_is_in_the_manifest_with_its_cells():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, cells in CELLS.items():
        m = per_layer[name]
        assert m["workloads"] == cells
        assert m["moves"] == "serve_tokens_per_s"
        assert m["source"] == "device_trace"
        assert os.path.exists(os.path.join(
            run.HERE, "layer_metrics", name + ".py"))
