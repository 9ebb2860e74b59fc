"""``ssm_step_block_kb.serve`` (PR 42) on a hand-built trace, as the two
state-space cells' tests read theirs: the mean ``ssm_block_bytes`` of
the stretch's ``decode.dispatch`` spans in KB, None where no span
carries the count (the parent's program), and on exactly the two cells'
lists."""

import json
import os

import pytest

import program_split as S
import run
from test_program_split import KERNEL, Plane, ctx_of

CELLS = ["falconh1-serve-gen1k-backlog", "nemotron3n-serve-reason2k-backlog"]
METRIC = "ssm_step_block_kb.serve"
DEC = "jit(p2t_decode)/"
OPS = [("%ssm_state_step.1 = f32[8]{0}" + KERNEL, 100, 80,
        DEC + "ssm/step/jit(_state_step)/ssm_state_step/pallas_call"),
       ("%ssm_state_step.2 = f32[8]{0}" + KERNEL, 400, 80,
        DEC + "ssm/step/jit(_state_step)/ssm_state_step/pallas_call")]
MODULES = [("jit_p2t_decode(7)", 100, 100, ""),
           ("jit_p2t_decode(7)", 400, 100, "")]


def traced(monkeypatch, cell, blocks):
    """Two decode steps; ``blocks``: the ``ssm_block_bytes`` each
    dispatch carries (None: the span lacks the count)."""
    from jax.profiler import ProfileData
    spans = [("bench:traced_window", 0, 1000, {})]
    for k, block in enumerate(blocks):
        step = {"rows": 250, "row_bucket": 256, "page_bucket": 256,
                "ctx_tokens": 400000, "program": S.DECODE, "launch": 40,
                "state_bytes": 1, "state_reprefills": 0, "ssm_layers": 4}
        if block is not None:
            step.update(ssm_block_bytes=block,
                        ssm_grid_steps=256 * 4 * (2 << 20) // block)
        spans.append(("p2t:decode.dispatch", 10 + 300 * k, 20, step))
    dev = Plane(1, "/device:TPU:0")
    dev.line(1, "XLA Ops", OPS)
    dev.line(2, "XLA Modules", MODULES)
    plane = Plane(2, "/host:CPU")
    plane.line(1, "python", spans)
    raw = ProfileData.text_proto_to_serialized_xspace(
        dev.text() + plane.text())
    ctx = ctx_of(monkeypatch, raw, cell=cell)
    loaded = run.load_cell(cell, False)
    ctx["cell"].update(workload=loaded["workload"], config=loaded["config"])
    return ctx


@pytest.mark.parametrize("cell", CELLS)
def test_the_reader_reads_the_plans_block(monkeypatch, cell):
    ctx = traced(monkeypatch, cell, [2 << 20, 2 << 20])
    assert run.read_layer_metric(METRIC, ctx) == pytest.approx(2048.0)


@pytest.mark.parametrize("cell", CELLS)
def test_the_reader_takes_the_mean_over_the_stretch(monkeypatch, cell):
    ctx = traced(monkeypatch, cell, [1 << 20, 256 << 10])
    assert run.read_layer_metric(METRIC, ctx) == pytest.approx(640.0)
    # a span without the count is not a block of 0
    ctx = traced(monkeypatch, cell, [1 << 20, None])
    assert run.read_layer_metric(METRIC, ctx) == pytest.approx(1024.0)


@pytest.mark.parametrize("cell", CELLS)
def test_the_parents_spans_say_nothing(monkeypatch, cell):
    ctx = traced(monkeypatch, cell, [None, None])
    assert run.read_layer_metric(METRIC, ctx) is None


def test_the_metric_is_on_exactly_the_two_cells_lists():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"] if m["name"] == METRIC]
    assert entry["workloads"] == CELLS
    assert (entry["source"], entry["layer"], entry["better"],
            entry["moves"], entry["unit"]) == (
        "program_counter", "kernels", "higher", "serve_tokens_per_s", "KB")
    for wl in manifest["workloads"]:
        names = {m["name"] for m in run.metrics_of(
            run.load_cell(wl["name"], False), "per_layer")}
        assert (METRIC in names) == (wl["name"] in CELLS), wl["name"]
