"""The span contract of a family with state kinds (PERF.md section 3),
beside ``test_prefill_ahead_spans.py``: ``decode.dispatch`` carries
``state_bytes`` (what the enqueued step reads and writes of its REAL
rows' states, every kind: rows x 2 x bytes a slot) and
``state_reprefills`` (rows re-prefilled since the last dispatch because
a dropped step had moved their state); the admission's ``prefill`` span
carries ``scan_chunks`` (``padded`` / the chunk). The last tests run the
benchmark's three new readers over the engine's own spans."""

import os
import sys
import types

import numpy as np
import pytest

from paddle2_tpu.distributed.fault_tolerance import chaos
from paddle2_tpu.models import (FalconH1ForCausalLM, Lfm2MoeForCausalLM,
                                falcon_h1_tiny, lfm2_moe_tiny)
from served import (reader, seeded_engine, serve_traced,  # noqa: F401
                    shared_programs)

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
PROMPTS = (9, 12, 21)
pytestmark = pytest.mark.usefixtures("shared_programs")


def requests(max_new=4):
    rng = np.random.default_rng(0)
    return [(rng.integers(1, 503, n).tolist(), max_new) for n in PROMPTS]


@pytest.fixture(scope="module")
def falcon_traced(tmp_path_factory):
    engine = seeded_engine(FalconH1ForCausalLM, falcon_h1_tiny())
    return engine, serve_traced(tmp_path_factory, engine, requests())


def steps_of(spans):
    return [s[3] for s in spans if s[0] == "decode.dispatch"
            and "rows" in s[3]]


def test_dispatch_counts_the_state_the_step_moves(falcon_traced):
    engine, spans = falcon_traced
    slot = engine.cache.state_slot_bytes
    cfg = engine.model.cfg
    assert slot == cfg.num_hidden_layers * (
        3 * cfg.conv_dim * 4 + 4 * 16 * 16 * 4)
    steps = steps_of(spans)
    assert len(steps) == 3
    for c in steps:
        assert c["state_bytes"] == 2 * c["rows"] * slot
        assert c["state_reprefills"] == 0
        assert c["rows"] == 3 and c["row_bucket"] == 4    # a padded row
    admitted = [s[3] for s in spans if s[0] == "prefill"
                and "tokens" in s[3]]
    assert [(c["padded"], c["scan_chunks"]) for c in admitted] == \
        [(16, 2), (16, 2), (32, 4)]


def test_a_family_without_state_says_nothing_of_it(tmp_path_factory):
    from paddle2_tpu.models import GPTForCausalLM, gpt_tiny
    engine = seeded_engine(GPTForCausalLM, gpt_tiny(), batch_buckets=None,
                       page_buckets=None, max_model_len=64)
    prompts = [(list(range(3, 3 + n)), 3) for n in PROMPTS]
    spans = serve_traced(tmp_path_factory, engine, prompts)
    for c in steps_of(spans):
        assert "state_bytes" not in c and "state_reprefills" not in c
        assert "ssm_block_bytes" not in c and "ssm_grid_steps" not in c
    assert not any("scan_chunks" in s[3] for s in spans)


@pytest.mark.parametrize("model_class,config", [
    (FalconH1ForCausalLM, falcon_h1_tiny),
    (Lfm2MoeForCausalLM, lfm2_moe_tiny)])
def test_a_dropped_steps_rows_are_counted_on_the_next_dispatch(
        tmp_path_factory, monkeypatch, model_class, config):
    monkeypatch.setattr(chaos, "_ACTIVE",
                        chaos.ChaosInjector("drop_decode_step:2"))
    engine = seeded_engine(model_class, config())
    spans = serve_traced(tmp_path_factory, engine, requests())
    moved = [c["state_reprefills"] for c in steps_of(spans)]
    assert sum(moved) == engine.state_reprefills == 3
    assert moved[:2] == [0, 0] and moved[2] == 3
    # re-prefilled as after an eviction: three more admissions
    admitted = [s for s in spans if s[0] == "prefill" and "tokens" in s[3]]
    assert len(admitted) == 6
    assert engine.scheduler.total_evictions == 0


# -- the benchmark's new readers over the engine's real spans ---------------
@pytest.fixture()
def readers(monkeypatch, falcon_traced):
    """The three new readers with the engine's spans as the loaded trace
    of a context (ns, as the readers take them). A CPU run has no device
    ops: the kernel's device time is handed in where a test needs one."""
    monkeypatch.syspath_prepend(BENCHMARK)
    gone = ("program_trace", "program_split", "moe_trace", "trace_reduce",
            "common", "roofline", "roofline.falcon_h1")
    for name in gone:
        monkeypatch.delitem(sys.modules, name, raising=False)
    import program_trace
    engine, spans = falcon_traced
    pt = program_trace.ProgramTrace()
    pt.spans = list(spans)
    cfg = engine.model.cfg
    config = {k: getattr(cfg, k) for k in (
        "num_hidden_layers", "mamba_n_heads", "mamba_d_head",
        "mamba_n_groups", "mamba_d_state")}
    lo = min(s[1] for s in spans)
    hi = max(s[2] for s in spans)
    ctx = {"cell": {"trace_dir": "spans-of-the-test", "name": "a-cell",
                    "workload": {"kernels": {"ssm_state_step": {
                        "pattern": "ssm_state_step"}}},
                    "config": config,
                    "peaks": {"bf16_flops_per_s": 197e12,
                              "hbm_bytes_per_s": 819e9}},
           "trace": types.SimpleNamespace(window=(lo, hi), devices=[0]),
           "reduce": types.SimpleNamespace(
               pattern_time=lambda trace, pattern: {0: (0, 0)},
               busy_and_window_s=lambda trace: (0.0, 0.0))}
    monkeypatch.setattr(program_trace.trace_reduce, "find_xplane",
                        lambda trace_dir: trace_dir)
    monkeypatch.setitem(program_trace._LOADED, "spans-of-the-test", pt)
    yield types.SimpleNamespace(ctx=ctx, read=lambda name: reader(name).read(
        ctx))
    for name in gone:
        sys.modules.pop(name, None)


def test_the_roofline_reader_counts_the_engines_real_rows(readers,
                                                          falcon_traced):
    engine, spans = falcon_traced
    cfg = engine.model.cfg
    # no kernel event on a CPU: nothing to divide by, None and no raise
    assert readers.read("ssm_step_roofline_pct.serve") is None
    # with the kernel's device time handed in: 3 steps x 3 real rows
    readers.ctx["reduce"].pattern_time = lambda trace, pattern: {
        0: (2_000, 6)}
    rows, L = 9, cfg.num_hidden_layers
    state = 2 * rows * L * 4 * 16 * 16 * 4
    operands = rows * L * (2 * 4 * 16 + 2 * 2 * 16 + 4) * 4
    want = 100.0 * ((state + operands) / 819e9) / 2e-6
    assert readers.read("ssm_step_roofline_pct.serve") \
        == pytest.approx(want)
    # the count the reader keys on is the engine's own
    assert sum(c["state_bytes"] for c in steps_of(spans)) \
        == 2 * rows * engine.cache.state_slot_bytes


def test_the_scope_readers_say_nothing_without_device_ops(readers):
    assert readers.read("ssm_device_pct.serve") is None
    assert readers.read("prefill_ssm_device_pct.serve") is None


# -- what a grid step of the state step holds (PR 42) -----------------------
def test_dispatch_says_what_a_grid_step_of_the_state_step_holds(
        falcon_traced):
    """``ssm_block_bytes`` is the kernel's own plan at the mixer's shape
    (here the whole row: 4 heads of [16, 16] float32), ``ssm_grid_steps``
    the row BUCKET x the layers x the grid steps a row."""
    from paddle2_tpu.kernels import ssd
    engine, spans = falcon_traced
    cfg = engine.model.cfg
    shape = (cfg.mamba_n_heads, cfg.mamba_n_groups, cfg.mamba_d_head,
             cfg.mamba_d_state)
    hb, per_row = ssd.state_step_plan(*shape)
    assert (hb, per_row) == (4, 1)
    steps = steps_of(spans)
    assert len(steps) == 3
    for c in steps:
        assert c["ssm_block_bytes"] == 4 * 16 * 16 * 4
        assert c["ssm_grid_steps"] == c["row_bucket"] \
            * cfg.num_hidden_layers * per_row


def test_a_smaller_budget_shows_on_the_span(monkeypatch):
    """The counts follow the plan, not a constant: under a budget of one
    head the same engine says 1 KB blocks and four grid steps a row."""
    from paddle2_tpu.kernels import ssd
    engine = seeded_engine(FalconH1ForCausalLM, falcon_h1_tiny())
    monkeypatch.setattr(ssd, "STATE_BLOCK_BYTES", 16 * 16 * 4)
    counts = engine.runner.kernel_page_counts(
        engine.cache, np.zeros((4, 4), np.int32), [1, 1, 1, 1])
    assert counts["ssm_block_bytes"] == 16 * 16 * 4
    assert counts["ssm_grid_steps"] \
        == 4 * engine.model.cfg.num_hidden_layers * 4
    assert "kernel_pages_per_block" in counts
    assert "coalesced_pages" in counts


def test_the_block_reader_reads_the_plans_block(readers, falcon_traced):
    _, spans = falcon_traced
    assert readers.read("ssm_step_block_kb.serve") \
        == pytest.approx(4 * 16 * 16 * 4 / 1024.0)
    # a program whose spans lack the count (the parent's): None, no raise
    import program_trace
    pt = program_trace._LOADED["spans-of-the-test"]
    pt.spans = [(n, a, b, {k: v for k, v in c.items()
                           if not k.startswith("ssm_")})
                for n, a, b, c in pt.spans]
    assert readers.read("ssm_step_block_kb.serve") is None


def test_the_block_metric_is_on_the_two_state_space_cells_lists():
    import json
    with open(os.path.join(os.path.dirname(BENCHMARK),
                           "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"]
              if m["name"] == "ssm_step_block_kb.serve"]
    assert entry == {
        "name": "ssm_step_block_kb.serve", "unit": "KB", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "serve_tokens_per_s",
        "workloads": ["falconh1-serve-gen1k-backlog",
                      "nemotron3n-serve-reason2k-backlog"]}
    # appended behind the 52 entries PR 42 found (later PRs append too)
    assert manifest["per_layer"].index(entry) == 52
