"""The readers of what the program wrote into a trace, on the
hand-built trace of ``make_program_trace.py`` (read its docstring for
the intervals): every new per-layer metric by hand arithmetic."""

import json
import os

import pytest

import program_trace as P
import run
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
PBTXT = os.path.join(HERE, "program_trace.pbtxt")
BUILDS = [
    {"program": "train_step", "sig": "8x1024", "trace_s": 1.0,
     "lower_s": 0.5, "compile_s": 2.0, "cache_hit": True,
     "cache_read_s": 1.5, "cost_s": 0.0, "total_s": 4.0},
    {"program": "decode", "sig": "64x64", "trace_s": 2.0, "lower_s": 1.0,
     "compile_s": 0.5, "cache_hit": False, "cache_read_s": 0.0,
     "cost_s": 1.25, "total_s": 5.0},
]


def _serialized(path=PBTXT) -> bytes:
    from jax.profiler import ProfileData
    with open(path) as f:
        return ProfileData.text_proto_to_serialized_xspace(f.read())


def _ctx(monkeypatch, raw: bytes, builds):
    """What ``run.py`` hands a reader after a traced run."""
    from jax.profiler import ProfileData
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "a trace")
    monkeypatch.setattr(P, "load", lambda path: P.from_serialized(raw))
    monkeypatch.setattr(P, "builds", builds)
    monkeypatch.setattr(P, "_LOADED", {})
    trace = trace_reduce.from_profile(ProfileData.from_serialized_xspace(raw))
    return {"trace": trace, "reduce": trace_reduce,
            "cell": {"trace_dir": "unused"}, "steps": 2}


@pytest.fixture()
def ctx(monkeypatch):
    return _ctx(monkeypatch, _serialized(), lambda: list(BUILDS))


def test_pbtxt_is_what_the_generator_writes(tmp_path):
    import make_program_trace as gen
    dev = gen.Plane(1, "/device:TPU:0")
    dev.line(1, "XLA Ops", gen.OPS)
    dev.line(2, "XLA Modules", gen.MODULES)
    host = gen.Plane(2, "/host:CPU")
    host.line(1, "python", gen.HOST)
    with open(PBTXT) as f:
        assert f.read() == dev.text() + host.text()


def test_loads_spans_paths_and_modules():
    pt = P.from_serialized(_serialized())
    assert [s[0] for s in pt.spans[:3]] == ["train.step", "train.step",
                                            "submit"]
    assert all(not s[0].startswith(("bench:", "$")) for s in pt.spans)
    dispatch, = P.spans_named(pt, "decode.dispatch")
    assert dispatch[3] == {"rows": 2, "row_bucket": 4, "page_bucket": 8,
                           "ctx_tokens": 900, "blocks_in_use": 3,
                           "blocks_total": 4, "evicted": 0}
    ops = pt.ops["/device:TPU:0"]
    assert ops[2][0] == "flash_fwd.1:custom-call"
    assert ops[2][3] == "attn/jit(flash_bshd)/flash_fwd/pallas_call"
    assert pt.modules["/device:TPU:0"] == [
        ("jit_p2t_train_step", 1000.0, 1200.0),
        ("jit_p2t_kv_scatter_prefill", 1220.0, 1250.0)]


@pytest.mark.parametrize("path,scope,which", [
    ("attn/norm/reduce_sum", "attn", "fwd"),
    ("jit(p2t_train_step)/jvp(head_ce)/dot_general", "head_ce", "fwd"),
    ("jit(p2t_train_step)/transpose(jvp(attn))/mul", "attn", "bwd"),
    ("checkpoint/rematted_computation/mlp/norm/mul", "mlp", "recomputed"),
    ("jit(p2t_train_step)/norm/add", "norm", "fwd"),
    ("jit(p2t_train_step)/optimizer/sqrt", "optimizer", "fwd"),
    ("jit(p2t_decode)/kv_write/scatter", "kv_write", "fwd"),
    ("jit(p2t_train_step)/jvp()/while/body/dynamic_slice", None, "fwd"),
    ("jit(p2t_train_step)/transpose(jvp(blocks))/while/body/squeeze",
     "blocks", "bwd"),
    ("jit(p2t_train_step)/jvp(blocks)/while/body/closed_call/attn/norm/mul",
     "attn", "fwd"),
    ("jit(normalize)/attnx/mul", None, "fwd"),
    ("", None, "fwd"),
])
def test_scope_and_pass_of_a_path(path, scope, which):
    assert P.scope_of(path) == scope
    assert P.pass_of(path) == which


def test_scope_paths_are_read_off_the_event_metadata():
    """``ProfileData`` shows an event's own stats only; the path is a
    stat of the event's METADATA, read off the serialized proto."""
    paths = P.op_paths(_serialized())["/device:TPU:0"]
    assert len(paths) == 10          # 8 ops + 2 modules
    flash = next(k for k in paths if k.startswith("%flash_fwd.1"))
    assert paths[flash] == "attn/jit(flash_bshd)/flash_fwd/pallas_call"
    assert paths[next(k for k in paths if k.startswith("%copy.7"))] == ""
    assert P.op_paths(_serialized(
        os.path.join(HERE, "tiny_trace.pbtxt")))["/device:TPU:1"] == {
            "%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop": "",
            "%all-reduce.1 = f32[4]{0} all-reduce(%g), replica_groups={}": ""}


def test_self_time_by_scope_adds_up_to_busy(ctx):
    times = P.scope_times(P.of(ctx), ctx["trace"].window)
    assert times == {("attn", "fwd"): 40.0, ("mlp", "recomputed"): 30.0,
                     ("head_ce", "bwd"): 30.0, ("optimizer", "fwd"): 30.0,
                     ("kernel", "fwd"): 20.0, ("blocks", "fwd"): 30.0,
                     (None, "fwd"): 30.0}
    busy = trace_reduce.device_busy(ctx["trace"])["/device:TPU:0"]
    assert sum(times.values()) == busy == 210.0


def test_innermost_segments_and_idle_by_span(ctx):
    pt = P.of(ctx)
    seg = P.innermost_segments(pt.spans)
    assert (1099.0, 1100.0, "train.step") in seg    # after submit closed
    assert (1110.0, 1150.0, "decode.dispatch") in seg
    assert all(a[1] <= b[0] for a, b in zip(seg, seg[1:]))
    assert P.idle_by_span(pt, ctx["trace"]) == {
        "decode.select": 7.0, "decode.build_batch": 5.0,
        "decode.dispatch": 10.0, "decode.emit": 20.0,
        "prefill.scatter": 5.0, None: 43.0}


# metric -> value by hand (see the generator's docstring); 2 steps
HAND = {
    "step_host_ms.train": 60 / 1e6,       # the step that built is out
    "program_build_s.train": 9.0,
    "program_build_s.serve": 9.0,
    "attn_device_ms.train": 40 / 1e6 / 2,
    "mlp_device_ms.train": 30 / 1e6 / 2,
    "head_ce_device_ms.train": 30 / 1e6 / 2,
    "optimizer_device_ms.train": 30 / 1e6 / 2,
    "blocks_device_ms.train": 30 / 1e6 / 2,
    "unscoped_device_pct.train": 100 * 30 / 210,
    "decode_tick_ms.serve": 120 / 1e6,            # the empty tick is out
    "tick_host_ms.serve": (5 + 5 + 20) / 1e6,
    "prefill_span_ms_per_ktok.serve": 28 / 1e6 / 0.5,
    "kv_pool_live_pct.serve": 75.0,
    "kv_scatter_device_pct.serve": 100 * 30 / 210,
    "idle_unattributed_pct.serve": 100 * 43 / 90,
}


@pytest.mark.parametrize("metric", sorted(HAND))
def test_reader_by_hand_arithmetic(ctx, metric):
    assert run.read_layer_metric(metric, ctx) == pytest.approx(HAND[metric])


def test_every_new_metric_is_in_the_manifest_with_its_one_cell():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in HAND:
        cell = {"train": "gpt2m-pretrain-1k",
                "serve": "gpt2m-serve-longdoc-backlog"}[name.rsplit(".")[-1]]
        assert per_layer[name]["workloads"] == [cell]
        assert os.path.exists(os.path.join(
            run.HERE, "layer_metrics", name + ".py"))


@pytest.mark.parametrize("metric", sorted(HAND))
def test_reader_finds_nothing_in_an_older_programs_trace(monkeypatch,
                                                         metric):
    """The parent's traces (PR 22's tiny trace: no ``p2t:`` span, no
    scope path, no module line) and no build log: None, never a raise."""
    old = _ctx(monkeypatch, _serialized(os.path.join(HERE, "tiny_trace.pbtxt")),
               lambda: None)
    assert run.read_layer_metric(metric, old) is None


def test_count_readers_by_hand_arithmetic(ctx):
    """Every count a span carries has its reader: ``req`` joins submit
    and prefill, ``tokens`` / ``padded`` give the padding, ``rows``,
    the buckets, ``ctx_tokens`` and ``evicted`` describe the decode
    programs' ticks, ``program`` / ``sig`` name a build in the trace,
    ``built`` takes the building step out of the median."""
    pt = P.of(ctx)
    assert P.first_token_ns(pt) == [248.0 - 98.0]
    assert P.first_token_ns(pt, (1100.0, 1300.0)) == []   # no submit there
    assert P.prefill_pad_pct(pt) == pytest.approx(100 * 12 / 512)
    assert P.decode_by_bucket(pt) == [{
        "row_bucket": 4, "page_bucket": 8, "ticks": 1,
        "tick_ms": pytest.approx(120 / 1e6), "rows": 2.0,
        "ctx_tokens": 900.0,
        "ms_per_ctx_ktok": pytest.approx(120 / 1e6 / 0.9), "evicted": 0}]
    assert P.builds_in_trace(pt) == [["prefill", "512",
                                     pytest.approx(6 / 1e6)]]
    assert [s[1:3] for s in P.steady_steps(pt)] == [(1040.0, 1100.0)]


STALE = ["blocks_device_ms.train", "unscoped_device_pct.train"]


@pytest.mark.parametrize("metric", sorted(
    m for m in HAND if m.endswith("_device_ms.train")) + STALE[1:])
def test_scope_missing_from_the_trace_reads_none(monkeypatch, metric):
    """A stale executable (the compile-cache key holds no scope names)
    lacks a scope that the program now has: its reader and the unscoped
    share give None, never a confident 0.0; the other scopes read."""
    with open(PBTXT) as f:
        text = f.read().replace("jvp(blocks)", "jvp()")
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(text)
    stale = _ctx(monkeypatch, raw, lambda: list(BUILDS))
    value = run.read_layer_metric(metric, stale)
    if metric in STALE:
        assert value is None
    else:
        assert value == pytest.approx(HAND[metric])


def _notes(ctx, capsys, monkeypatch):
    import common
    monkeypatch.setattr(common, "REHEARSAL", False)   # a chip run's lines
    P.of(ctx)
    P.of(ctx)                       # loaded once: the notes come once
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]


def test_notes_of_a_traced_run(ctx, capsys, monkeypatch):
    notes = _notes(ctx, capsys, monkeypatch)
    assert [n["note"] for n in notes] == [
        "idle_by_program_span", "program_builds", "program_counts"]
    idle, built, counts = notes
    assert idle["idle_s"][:3] == [["none", 43 / 1e9],
                                  ["decode.emit", 20 / 1e9],
                                  ["decode.dispatch", 10 / 1e9]]
    assert idle["spans"]["decode"] == 2 and idle["spans"]["prefill"] == 1
    by = built["builds_by_program_s"]
    assert by["decode"]["cost_s"] == 1.25 and by["decode"]["builds"] == 1
    assert by["train_step"]["cache_hits"] == 1
    assert built["builds_s"] == [["train_step", "8x1024", 4.0, True],
                               ["decode", "64x64", 5.0, False]]
    assert built["builds_in_trace_ms"] == [["prefill", "512", 6 / 1e6]]
    assert built["scope_ms_per_step"]["attn"] == {"fwd": 40 / 1e6 / 2}
    assert built["scope_ms_per_step"]["mlp"] == {"recomputed": 30 / 1e6 / 2}
    assert built["scope_ms_per_step"]["blocks"] == {"fwd": 30 / 1e6 / 2}
    assert built["scope_ms_per_step"]["unscoped"] == {"fwd": 30 / 1e6 / 2}
    # the sum check: the five scope metrics + unscoped over the busy
    # time; the named kernel under no scope (20 of 210) is the rest
    assert built["scopes_missing"] == []
    assert built["scopes_found"] == ["attn", "blocks", "head_ce", "mlp",
                                     "optimizer"]
    assert built["metric_scopes_pct_of_busy"] == pytest.approx(
        100 * 190 / 210)
    assert counts["first_token_ms"] == {
        "requests": 1, "p50": pytest.approx(150 / 1e6),
        "p95": pytest.approx(150 / 1e6)}
    assert counts["prefill_pad_pct"] == pytest.approx(100 * 12 / 512)
    assert counts["decode_by_bucket_ms"][0]["ticks"] == 1


def test_notes_of_an_older_programs_trace(monkeypatch, capsys):
    """No span, no path, no build log: no note at all, never a raise."""
    old = _ctx(monkeypatch, _serialized(os.path.join(HERE, "tiny_trace.pbtxt")),
               lambda: None)
    assert _notes(old, capsys, monkeypatch) == []
