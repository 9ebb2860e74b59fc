"""The span contract of a family with two kinds of attention (PERF.md
section 3), beside ``test_nemotron_h_spans.py``: the program's scopes —
``attn`` around both kinds (``norm``, ``kv_write`` and the paged kernel
for the global layer, ``out``), ``window`` inside it for a sliding layer
(``state_write`` and the ring walk at decode),
``mlp`` for the dense layer, ``moe`` (``router``, ``dispatch``,
``experts``, ``combine``, ``shared``), ``embed``, ``head_ce`` — in the
lowered decode and prefill programs, the ring walk under its own kernel
name; on ``decode.dispatch`` and the admission's ``prefill`` span the
experts' counts under their accepted names, on ``decode.dispatch`` the
static ``window_layers`` and ``window_tokens``, the rows' ``min(context,
window)`` summed (what ``window_decode_roofline_pct.serve`` reads). The
last tests run the benchmark's new readers over the engine's own
spans."""

import os
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from paddle2_tpu.incubate.moe import DroplessExperts
from paddle2_tpu.models import ExaoneMoeForCausalLM, exaone_moe_tiny
from served import (reader, scope_in, seeded_engine,  # noqa: F401
                    serve_traced, shared_programs)

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
PROMPTS = (3, 12, 21)
NEW = 4
WINDOW = 8
pytestmark = pytest.mark.usefixtures("shared_programs")
LAYERS = {"window_layers": 3}


def tiny_engine(**kw):
    # the benchmark's cut without its last layer: dense sliding, two
    # sliding expert layers, the global expert layer
    return seeded_engine(ExaoneMoeForCausalLM, exaone_moe_tiny(
        num_hidden_layers=4), **kw)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    engine = tiny_engine()
    rng = np.random.default_rng(0)
    requests = [(rng.integers(1, 503, n).tolist(), NEW) for n in PROMPTS]
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


SCOPES = ["embed", "norm", "head_ce", "sample", "attn/norm", "attn/out",
          "attn/window", "window/norm", "window/out", "mlp/norm",
          "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
          "moe/shared", "moe/norm"]


@pytest.mark.parametrize("scope", SCOPES + ["attn/kv_write",
                                            "window/state_write"])
def test_decode_program_carries_scope(lowered, scope):
    assert scope_in(lowered["decode"], scope)
    assert "jit_p2t_decode" in lowered["decode"]


@pytest.mark.parametrize("scope", SCOPES + ["state_write"])
def test_prefill_program_carries_scope(lowered, scope):
    assert scope_in(lowered["prefill"], scope)
    assert "jit_p2t_prefill" in lowered["prefill"]


def test_the_ring_walk_has_a_kernel_name_of_its_own(lowered):
    """``window_decode`` for the three sliding layers, ``paged_decode``
    for the global one: the accepted pattern reads the global layer
    alone."""
    dec = lowered["decode"]
    assert dec.count("window/jit(_decode_single)/window_decode") \
        or "window_decode" in dec
    assert "paged_decode" in dec and "moe_gmm" in dec
    # under the sliding layers' scope no kernel of the other name
    for line in dec.splitlines():
        if "/window/" in line:
            assert "paged_decode" not in line, line
    assert "window_decode" not in lowered["prefill"]
    assert "moe_gmm" in lowered["prefill"]


def test_dispatch_counts_layers_rows_window_tokens_and_routing(traced):
    engine, spans = traced
    slot = engine.cache.state_slot_bytes
    assert slot == 2 * LAYERS["window_layers"] * WINDOW * 32 * 4
    steps = steps_of(spans)
    assert len(steps) == NEW - 1
    for i, c in enumerate(steps):
        assert {k: c[k] for k in LAYERS} == LAYERS
        assert c["rows"] == 3 and c["row_bucket"] == 4    # a padded row
        # a row whose new token is position p sees min(p + 1, window)
        # ring rows, the one just written among them: the prompt of 3 is
        # still below the window, the others are past it
        positions = [n + i for n in PROMPTS]
        assert c["ctx_tokens"] == sum(positions)
        assert c["window_tokens"] == sum(min(p + 1, WINDOW)
                                         for p in positions)
        assert c["state_bytes"] == 2 * c["rows"] * slot
        assert "kernel_pages_per_block" in c and "coalesced_pages" in c
    counted = [s[3] for s in spans if s[0] == "decode.dispatch"
               and "moe_assignments" in s[3]]
    assert counted
    for c in counted:
        assert all(name in c for name in DroplessExperts.COUNT_NAMES)
        # 3 real rows x 2 experts x 3 expert layers, all 8 experts held
        assert c["moe_assignments"] == 3 * 2 * 3
        assert c["moe_rows"] == c["moe_rows_routed_here"] == 3 * 3


def test_prefill_span_counts_tokens_and_routing(traced):
    _, spans = traced
    admitted = [s[3] for s in spans if s[0] == "prefill"
                and "tokens" in s[3]]
    assert [(c["tokens"], c["padded"]) for c in admitted] \
        == [(3, 16), (12, 16), (21, 32)]
    routed = [s[3] for s in spans if s[0] == "prefill"
              and "moe_assignments" in s[3]]
    assert sorted(c["moe_rows"] for c in routed) == sorted(
        n * 3 for n in PROMPTS)                   # padding is not routed


# -- the benchmark's new readers over the engine's real spans ---------------
@pytest.fixture()
def readers(monkeypatch, traced):
    """The new readers with the engine's spans as the loaded trace of a
    context (ns, as the readers take them). A CPU run has no device ops:
    a kernel's device time is handed in where a test needs one."""
    monkeypatch.syspath_prepend(BENCHMARK)
    gone = ("program_trace", "program_split", "moe_trace", "scope_trace",
            "trace_reduce", "common", "roofline", "roofline.paged_decode",
            "roofline.exaone_moe")
    for name in gone:
        monkeypatch.delitem(sys.modules, name, raising=False)
    import program_trace
    engine, spans = traced
    pt = program_trace.ProgramTrace()
    pt.spans = list(spans)
    cfg = engine.model.cfg
    config = {k: getattr(cfg, k) for k in (
        "hidden_size", "moe_intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "sliding_window", "layer_types")}
    lo = min(s[1] for s in spans)
    hi = max(s[2] for s in spans)
    ctx = {"cell": {"trace_dir": "spans-of-the-test", "name": "a-cell",
                    "workload": {"kernels": {
                        "window_decode": {"pattern": "window_decode"},
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


def test_the_ring_reader_counts_window_tokens_off_the_span(readers):
    # no kernel event on a CPU: nothing to divide by, None and no raise
    assert readers.read("window_decode_roofline_pct.serve") is None
    readers.ctx["reduce"].pattern_time = lambda trace, pattern: {
        0: (2_000, 9)}
    tokens = sum(min(n + 1 + i, WINDOW) for i in range(NEW - 1)
                 for n in PROMPTS)
    # K and V rows of 2 heads x 16 lanes in bf16, three sliding layers
    nbytes = 2 * tokens * LAYERS["window_layers"] * 32 * 2
    assert readers.read("window_decode_roofline_pct.serve") \
        == pytest.approx(100.0 * (nbytes / 819e9) / 2e-6)


def test_the_band_reader_takes_each_prompts_padded_length(readers):
    """``window_prefill_roofline_pct.serve``: the band's required time a
    prompt from ``padded`` on its ``prefill`` span (prompts of 3, 12 and
    21 padded to 16, 16 and 32; a window of 8; three sliding layers),
    over the ``window_fwd`` events' device time. No such event (a CPU,
    the parent commit, whose band is plain XLA): None and no raise."""
    name = "window_prefill_roofline_pct.serve"
    assert readers.read(name) is None
    asked = []

    def pattern_time(trace, pattern):
        asked.append(pattern)
        return {0: (3_000, 9)}

    readers.ctx["reduce"].pattern_time = pattern_time
    need_s = 0.0
    for padded in (16, 16, 32):
        pairs = (padded - WINDOW) * WINDOW + WINDOW * (WINDOW + 1) // 2
        flops = 4 * 16 * 4 * pairs          # 4 query heads of 16 lanes
        nbytes = 2 * padded * 16 * (2 * 4 + 2 * 2)    # q, o; k, v: bf16
        need_s += LAYERS["window_layers"] * max(flops / 197e12,
                                                nbytes / 819e9)
    assert readers.read(name) == pytest.approx(100.0 * need_s / 3e-6)
    # by its own pattern: the kernel's own line, neither the global
    # layer's kernel nor an op that takes the kernel's output
    import re
    pattern, = asked
    assert re.search(pattern, "%window_fwd.1 = bf16[1,8192,8192]{2,1,0} "
                     "custom-call(bf16[1,8192,8192]{2,1,0} %fusion.3, ")
    for line in ("%flash_fwd.2 = bf16[1,64,8192,128]{3,2,1,0} custom-call(",
                 "%fusion.139 = bf16[1,8192,6144]{2,1,0} fusion(bf16[1,8192,"
                 "8192]{2,1,0} %window_fwd.1, bf16[8192,6144]{1,0} %p.4)"):
        assert not re.search(pattern, line)
    # a family without sliding layers has nothing to read
    readers.ctx["cell"]["config"]["layer_types"] = ["full_attention"] * 4
    assert readers.read(name) is None


def test_a_program_without_the_counts_says_nothing(readers):
    """The parent commit's spans carry no ``window_layers`` (and its
    trace no ``window`` scope): every new reader returns None there and
    none raises."""
    import program_trace
    pt = program_trace._LOADED["spans-of-the-test"]
    pt.spans = [(n, a, b, {k: v for k, v in c.items()
                           if k not in LAYERS and k != "window_tokens"})
                for n, a, b, c in pt.spans]
    readers.ctx["reduce"].pattern_time = lambda trace, pattern: {
        0: (2_000, 9)}
    for name in ("window_decode_roofline_pct.serve",
                 "window_device_pct.serve",
                 "prefill_window_device_pct.serve"):
        assert readers.read(name) is None, name
