"""The two readers PR 47 adds, on a hand-built trace of the K-EXAONE
cell: ``prefill_moe_routing_device_pct.serve`` (the prefill executions'
share under ``moe/dispatch`` + ``moe/combine``, loops included) and
``moe_rows_moved_pct.serve`` (``moe_rows_moved`` over 2 x ``moe_rows`` x
the experts a row); both say nothing of a program without the scopes or
the count, and the manifest lists both under the three cells whose
layers hold a share of the experts."""

import types

import pytest

import program_split as S
import run
from test_program_split import KERNEL, Plane, ctx_of

CELL = "kexaone-serve-mixed8k-backlog"
CELLS = ["dsv2-serve-doc5k-backlog", "nemotron3n-serve-reason2k-backlog",
         CELL]
NEW = ["prefill_moe_routing_device_pct.serve", "moe_rows_moved_pct.serve"]
DEC, PRE = "jit(p2t_decode)/", "jit(p2t_prefill)/"
# device: D [100, 300): a sort 30 under moe/dispatch, moe_gmm.1 170 under
# moe/experts; P [400, 800): a sort 40 under moe/dispatch, the gather's
# loop [440, 500) with one 50 ns gather inside it, moe_gmm.2 200 under
# moe/experts, the sum's loop [700, 760) with a 40 ns scatter inside it,
# a convert 40 under moe/combine
OPS = [("%sort.1 = s32[8]{0} sort(%p)", 100, 30, DEC + "moe/dispatch/sort"),
       ("%moe_gmm.1 = bf16[8]{0}" + KERNEL, 130, 170,
        DEC + "moe/experts/jit(_gmm)/moe_gmm/pallas_call"),
       ("%sort.2 = s32[8]{0} sort(%p)", 400, 40, PRE + "moe/dispatch/sort"),
       ("%while.1 = (s32[], bf16[8]{0}) while(%t)", 440, 60,
        PRE + "moe/dispatch/while"),
       ("%fusion.g = bf16[8]{0} fusion(%p), kind=kLoop", 445, 50,
        PRE + "moe/dispatch/while/body/gather"),
       ("%moe_gmm.2 = bf16[8]{0}" + KERNEL, 500, 200,
        PRE + "moe/experts/jit(_gmm)/moe_gmm/pallas_call"),
       ("%while.2 = (s32[], f32[8]{0}) while(%t)", 700, 60,
        PRE + "moe/combine/while"),
       ("%scatter.1 = f32[8]{0} scatter(%p)", 710, 40,
        PRE + "moe/combine/while/body/scatter-add"),
       ("%convert.1 = bf16[8]{0} convert(%p)", 760, 40,
        PRE + "moe/combine/convert_element_type")]
MODULES = [("jit_p2t_decode(7)", 100, 200, ""),
           ("jit_p2t_prefill(5)", 400, 400, "")]
ROUTING = {"moe_assignments": 3000, "moe_experts_hit": 60,
           "moe_load_max": 90, "moe_rows_routed_here": 330,
           "moe_rows": 500, "moe_tile_rows": 32000}


def traced(monkeypatch, moved=True, ops=OPS, cell_name=CELL):
    from jax.profiler import ProfileData
    step = {"rows": 125, "row_bucket": 128, "page_bucket": 576,
            "ctx_tokens": 350000, "program": S.DECODE, "launch": 40,
            **ROUTING}
    pre = {"req": 0, "tokens": 2000, "padded": 2048, "ahead": 1,
           **dict(ROUTING, moe_rows=8000)}
    if moved:
        # the step moved every assignment (2 x 500 x 8), the prefill two
        # trips of 1,024 each way in four layers
        step["moe_rows_moved"] = 8000
        pre["moe_rows_moved"] = 16384
    host = [("bench:traced_window", 0, 1000, {}),
            ("p2t:decode.dispatch", 10, 20, step),
            ("p2t:prefill", 300, 60, pre),
            ("p2t:prefill.dispatch", 302, 18,
             {"program": S.PREFILL, "launch": 5, "launches": 1})]
    dev = Plane(1, "/device:TPU:0")
    dev.line(1, "XLA Ops", ops)
    dev.line(2, "XLA Modules", MODULES)
    plane = Plane(2, "/host:CPU")
    plane.line(1, "python", host)
    raw = ProfileData.text_proto_to_serialized_xspace(
        dev.text() + plane.text())
    ctx = ctx_of(monkeypatch, raw, cell=cell_name)
    cell = run.load_cell(cell_name, False)
    ctx["cell"].update(workload=cell["workload"], config=cell["config"])
    ctx["spans"] = types.SimpleNamespace(counters={})
    return ctx


def test_the_manifest_lists_both_under_the_three_cells():
    per_layer = run.load_cell(CELL, False)["manifest"]["per_layer"]
    for name in NEW:
        entry, = [m for m in per_layer if m["name"] == name]
        assert entry["workloads"] == CELLS
        assert entry["better"] == "lower" and entry["layer"] == "model"
        assert entry["moves"] == "serve_tokens_per_s"


def test_readers_by_hand_arithmetic(monkeypatch):
    ctx = traced(monkeypatch)
    # dispatch: the sort 40 + the loop's 60 (50 of them its gather);
    # combine: the loop's 60 + the convert 40 — of the prefill's 400
    assert run.read_layer_metric(NEW[0], ctx) \
        == pytest.approx(100 * (100 + 100) / 400)
    assert run.read_layer_metric(NEW[1], ctx) \
        == pytest.approx(100 * (8000 + 16384) / (2 * 8 * (500 + 8000)))
    # the accepted share of the whole layer holds them
    assert run.read_layer_metric("prefill_moe_device_pct.serve", ctx) \
        == pytest.approx(100.0)


def test_readers_say_nothing_of_a_program_without_them(monkeypatch):
    """The parent's spans carry no ``moe_rows_moved``: the counter's
    reader returns None and the trace's reads the same scopes as of any
    program; ops under no such scope: None too."""
    ctx = traced(monkeypatch, moved=False)
    assert run.read_layer_metric(NEW[1], ctx) is None
    assert run.read_layer_metric(NEW[0], ctx) == pytest.approx(50.0)
    bare = [(text, a, d, path.replace("moe/dispatch", "moe")
             .replace("moe/combine", "moe")) for text, a, d, path in OPS]
    ctx = traced(monkeypatch, moved=False, ops=bare)
    for name in NEW:
        assert run.read_layer_metric(name, ctx) is None, name
