"""The span contract of a family whose layers are of three kinds
(PERF.md section 3), beside ``test_falcon_h1_spans.py``: the program's
scopes — ``ssm`` (``in_proj``, ``conv``, ``scan`` or ``step``, ``norm``,
``out``, ``state_write``), ``moe`` (``router``, ``dispatch``,
``experts``, ``combine``, ``shared``), ``attn`` (``kv_write``, the
kernel, ``out``), ``norm``, ``embed``, ``head_ce`` — in the lowered
decode and prefill programs; on ``decode.dispatch`` and the admission's
``prefill`` span the experts' counts under their accepted names, the
static ``ssm_layers`` / ``attn_layers`` / ``moe_layers``, ``rows`` and
``state_bytes`` as the engine gives them, ``scan_chunks`` at prefill.
The last tests run the benchmark's two new readers over the engine's
own spans."""

import os
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from paddle2_tpu.incubate.moe import DroplessExperts
from paddle2_tpu.models import NemotronHForCausalLM, nemotron_h_tiny
from served import (reader, scope_in, seeded_engine,  # noqa: F401
                    serve_traced, shared_programs)

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
PROMPTS = (9, 12, 21)
pytestmark = pytest.mark.usefixtures("shared_programs")
LAYERS = {"ssm_layers": 3, "attn_layers": 1, "moe_layers": 3}


def tiny_engine(**kw):
    return seeded_engine(NemotronHForCausalLM, nemotron_h_tiny(), **kw)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    engine = tiny_engine()
    rng = np.random.default_rng(0)
    requests = [(rng.integers(1, 503, n).tolist(), 4) for n in PROMPTS]
    return engine, serve_traced(tmp_path_factory, engine, requests)


def steps_of(spans):
    return [s[3] for s in spans if s[0] == "decode.dispatch"
            and "rows" in s[3]]


@pytest.fixture(scope="module")
def lowered():
    engine = tiny_engine()
    runner, cache = engine.runner, engine.cache
    decode = runner._build_decode(4, 4, cache.block_size)
    dec = decode.lower(*runner._decode_args(
        cache, jnp.zeros((4, 1), jnp.int32), jnp.zeros((4,), jnp.int32),
        jnp.zeros((4, 4), jnp.int32), jnp.zeros((4,), jnp.int32)))
    pre = runner._build_prefill(16).lower(
        runner._weights(), jnp.zeros((1, 16), jnp.int32),
        jnp.asarray(4, jnp.int32))
    return {"decode": dec.as_text(dialect="hlo", debug_info=True),
            "prefill": pre.as_text(dialect="hlo", debug_info=True)}


SCOPES = ["embed", "norm", "head_ce", "sample",
          "ssm/in_proj", "ssm/conv", "ssm/norm", "ssm/out",
          "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
          "moe/shared", "moe/norm", "attn/out", "attn/norm", "state_write"]


@pytest.mark.parametrize("scope", SCOPES + ["ssm/step", "attn/kv_write",
                                            "ssm/state_write"])
def test_decode_program_carries_scope(lowered, scope):
    assert scope_in(lowered["decode"], scope)
    assert "jit_p2t_decode" in lowered["decode"]


@pytest.mark.parametrize("scope", SCOPES + ["ssm/scan"])
def test_prefill_program_carries_scope(lowered, scope):
    """(``kv_write`` holds no op of its own there: ONE attention layer's
    keys are stacked by a reshape, and the write is the scatter
    program's.)"""
    assert scope_in(lowered["prefill"], scope)
    assert "jit_p2t_prefill" in lowered["prefill"]


def test_the_programs_call_the_three_kernels(lowered):
    for name in ("ssm_state_step", "moe_gmm", "paged_decode"):
        assert name in lowered["decode"], name
    assert "moe_gmm" in lowered["prefill"]
    assert "ssm_state_step" not in lowered["prefill"]     # the scan, there


def test_dispatch_counts_layers_rows_state_and_routing(traced):
    engine, spans = traced
    slot = engine.cache.state_slot_bytes
    cfg = engine.model.cfg
    assert slot == LAYERS["ssm_layers"] * (
        3 * cfg.conv_dim * 4 + 4 * 16 * 16 * 4)
    steps = steps_of(spans)
    assert len(steps) == 3
    for c in steps:
        assert {k: c[k] for k in LAYERS} == LAYERS
        assert c["rows"] == 3 and c["row_bucket"] == 4    # a padded row
        assert c["state_bytes"] == 2 * c["rows"] * slot
        assert c["state_reprefills"] == 0
        assert "kernel_pages_per_block" in c
        assert "coalesced_pages" in c
    # the experts' counts ride on the dispatch that reads them back
    counted = [s[3] for s in spans if s[0] == "decode.dispatch"
               and "moe_assignments" in s[3]]
    assert counted
    for c in counted:
        assert all(name in c for name in DroplessExperts.COUNT_NAMES)
        # 3 real rows x 2 experts x 3 expert layers, all 8 experts held
        assert c["moe_assignments"] == 3 * 2 * LAYERS["moe_layers"]
        assert c["moe_rows"] == c["moe_rows_routed_here"] \
            == 3 * LAYERS["moe_layers"]


def test_prefill_span_counts_layers_chunks_and_routing(traced):
    _, spans = traced
    admitted = [s[3] for s in spans if s[0] == "prefill"
                and "tokens" in s[3]]
    assert [(c["tokens"], c["padded"], c["scan_chunks"])
            for c in admitted] == [(9, 16, 2), (12, 16, 2), (21, 32, 4)]
    for c in admitted:
        assert {k: c[k] for k in LAYERS} == LAYERS
    routed = [s[3] for s in spans if s[0] == "prefill"
              and "moe_assignments" in s[3]]
    assert sorted(c["moe_rows"] for c in routed) == sorted(
        n * LAYERS["moe_layers"] for n in PROMPTS)    # padding not routed


# -- the benchmark's new readers over the engine's real spans ---------------
@pytest.fixture()
def readers(monkeypatch, traced):
    """The two new readers with the engine's spans as the loaded trace
    of a context (ns, as the readers take them). A CPU run has no device
    ops: a kernel's device time is handed in where a test needs one."""
    monkeypatch.syspath_prepend(BENCHMARK)
    gone = ("program_trace", "program_split", "moe_trace", "trace_reduce",
            "common", "roofline", "roofline.falcon_h1",
            "roofline.nemotron_h")
    for name in gone:
        monkeypatch.delitem(sys.modules, name, raising=False)
    import program_trace
    engine, spans = traced
    pt = program_trace.ProgramTrace()
    pt.spans = list(spans)
    cfg = engine.model.cfg
    config = {k: getattr(cfg, k) for k in (
        "hidden_size", "moe_intermediate_size", "mamba_num_heads",
        "mamba_head_dim", "n_groups", "ssm_state_size")}
    lo = min(s[1] for s in spans)
    hi = max(s[2] for s in spans)
    ctx = {"cell": {"trace_dir": "spans-of-the-test", "name": "a-cell",
                    "workload": {"kernels": {
                        "ssm_state_step": {"pattern": "ssm_state_step"},
                        "moe_gmm": {"pattern": "moe_gmm"}}},
                    "config": config,
                    "peaks": {"bf16_flops_per_s": 197e12,
                              "hbm_bytes_per_s": 819e9}},
           "trace": types.SimpleNamespace(window=(lo, hi), devices=[0]),
           "reduce": types.SimpleNamespace(
               pattern_time=lambda trace, pattern: {0: (0, 0)})}
    monkeypatch.setattr(program_trace.trace_reduce, "find_xplane",
                        lambda trace_dir: trace_dir)
    monkeypatch.setitem(program_trace._LOADED, "spans-of-the-test", pt)
    yield types.SimpleNamespace(ctx=ctx, read=lambda name: reader(name).read(
        ctx))
    for name in gone:
        sys.modules.pop(name, None)


def test_the_state_step_reader_counts_the_ssm_layers_off_the_span(readers):
    # no kernel event on a CPU: nothing to divide by, None and no raise
    assert readers.read("ssm_mixer_step_roofline_pct.serve") is None
    readers.ctx["reduce"].pattern_time = lambda trace, pattern: {
        0: (2_000, 9)}
    rows, M = 9, LAYERS["ssm_layers"]          # 3 steps x 3 real rows
    state = 2 * rows * M * 4 * 16 * 16 * 4
    operands = rows * M * (2 * 4 * 16 + 2 * 2 * 16 + 4) * 4
    want = 100.0 * ((state + operands) / 819e9) / 2e-6
    assert readers.read("ssm_mixer_step_roofline_pct.serve") \
        == pytest.approx(want)
    # the accepted reader (Falcon-H1's key names) stays silent here
    assert readers.read("ssm_step_roofline_pct.serve") is None


def test_the_two_matrix_reader_counts_the_published_width(readers, traced):
    _, spans = traced
    assert readers.read("moe_gmm2_roofline_pct.serve") is None
    readers.ctx["reduce"].pattern_time = lambda trace, pattern: {
        0: (50_000, 12)}
    counted = [s[3] for s in spans
               if s[0] in ("decode.dispatch", "prefill")
               and "moe_assignments" in s[3]]
    H, F = 64, 40                   # the width, not the 48 lanes stored
    need = 0.0
    for c in counted:
        nbytes = (2 * c["moe_experts_hit"] * H * F
                  + 2 * c["moe_assignments"] * (H + F)) * 2
        flops = 4.0 * c["moe_assignments"] * H * F
        need += max(nbytes / 819e9, flops / 197e12)
    assert readers.read("moe_gmm2_roofline_pct.serve") \
        == pytest.approx(100.0 * need / 50e-6)


def test_a_program_without_the_counts_says_nothing(readers):
    """The parent commit's spans carry no ``ssm_layers``: the new
    reader returns None there and does not raise."""
    import program_trace
    pt = program_trace._LOADED["spans-of-the-test"]
    pt.spans = [(n, a, b, {k: v for k, v in c.items()
                           if k not in LAYERS and not k.startswith("moe_")})
                for n, a, b, c in pt.spans]
    readers.ctx["reduce"].pattern_time = lambda trace, pattern: {
        0: (2_000, 9)}
    assert readers.read("ssm_mixer_step_roofline_pct.serve") is None
    assert readers.read("moe_gmm2_roofline_pct.serve") is None


# -- what a grid step of the state step holds (PR 42) -----------------------
def test_dispatch_says_what_a_grid_step_of_the_state_step_holds(traced):
    """Beside ``ssm_layers``: the plan's block at the mixer's shape (the
    whole row here: 4 heads of [16, 16] float32 in 2 groups) and the
    grid steps of the step's three state-space layers over the row
    BUCKET."""
    from paddle2_tpu.kernels import ssd
    engine, spans = traced
    cfg = engine.model.cfg
    hb, per_row = ssd.state_step_plan(cfg.mamba_num_heads, cfg.n_groups,
                                      cfg.mamba_head_dim, cfg.ssm_state_size)
    assert (hb, per_row) == (4, 1)
    for c in steps_of(spans):
        assert c["ssm_block_bytes"] == 4 * 16 * 16 * 4
        assert c["ssm_grid_steps"] == c["row_bucket"] \
            * LAYERS["ssm_layers"] * per_row


def test_the_block_reader_reads_the_plans_block(readers):
    assert readers.read("ssm_step_block_kb.serve") \
        == pytest.approx(4 * 16 * 16 * 4 / 1024.0)
    import program_trace
    pt = program_trace._LOADED["spans-of-the-test"]
    pt.spans = [(n, a, b, {k: v for k, v in c.items()
                           if k not in ("ssm_block_bytes", "ssm_grid_steps")})
                for n, a, b, c in pt.spans]
    # the parent's spans: ``ssm_layers`` but no block — None, no raise
    assert readers.read("ssm_step_block_kb.serve") is None
