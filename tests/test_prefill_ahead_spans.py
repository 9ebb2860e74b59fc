"""The span contract of a prefill whose first token is read back later
(PERF.md section 3), beside ``test_decode_ahead_spans.py``. The admission
writes ``prefill`` (``req``, ``tokens``, ``padded``, ``ahead``) >
``prefill.dispatch``, ``prefill.scatter``; the read-back writes a SECOND
span named ``prefill`` (``req`` and the family's routing counts, no
``tokens`` / ``padded``) > ``prefill.readback`` — inside the ``decode``
that enqueued the step consuming the token, or, where the engine reads in
place, inside the ``admit`` — so that the benchmark's readers, which may
not be edited and take counts with ``.get``, keep reading what they read:
the last test runs their own code over the engine's spans."""

import os
import sys
import types

import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.models import Lfm2MoeForCausalLM, lfm2_moe_tiny
from paddle2_tpu.serving import EngineConfig, ServingEngine
from paddle2_tpu.serving.spec import SpeculativeConfig
from served import (PROMPTS, ROUTING, reader, seeded_engine, serve_traced,
                    tiny_gpt_engine, visits_by_hand)

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
LFM2_PROMPTS = (9, 12, 14)


def inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def split(spans):
    """(admission-time ``prefill`` spans, read-back ones)."""
    prefills = [s for s in spans if s[0] == "prefill"]
    return ([s for s in prefills if "tokens" in s[3]],
            [s for s in prefills if "tokens" not in s[3]])


@pytest.fixture(scope="module")
def gpt_spans(tmp_path_factory):
    return serve_traced(tmp_path_factory, tiny_gpt_engine(),
                        [(PROMPTS[0], 4), (PROMPTS[1], 3)])


@pytest.fixture(scope="module")
def lfm2_traced(tmp_path_factory):
    engine = seeded_engine(Lfm2MoeForCausalLM, lfm2_moe_tiny())
    rng = np.random.default_rng(0)
    spans = serve_traced(
        tmp_path_factory, engine,
        [(rng.integers(1, 503, n).tolist(), 4) for n in LFM2_PROMPTS])
    return engine, spans


def test_the_admission_span_closes_with_the_token_unread(gpt_spans):
    admitted, delivered = split(gpt_spans)
    assert [s[3]["req"] for s in admitted] == [0, 1]
    for span in admitted:
        assert set(span[3]) == {"req", "tokens", "padded", "ahead"}
        assert span[3]["ahead"] == 1
        kids = sorted(s[0] for s in gpt_spans
                      if s[0].startswith("prefill.") and inside(s, span))
        assert kids == ["prefill.dispatch", "prefill.scatter"]
        assert [s for s in gpt_spans if s[0] == "admit" and inside(span, s)]


def test_the_readback_sits_in_a_prefill_span_of_the_consuming_call(gpt_spans):
    admitted, delivered = split(gpt_spans)
    assert [s[3] for s in delivered] == [{"req": 0}, {"req": 1}]
    backs = [s for s in gpt_spans if s[0] == "prefill.readback"]
    assert len(backs) == 2
    tick = next(s for s in gpt_spans if s[0] == "decode")
    dispatch = next(s for s in gpt_spans if s[0] == "decode.dispatch")
    for span, back in zip(delivered, backs):
        assert inside(back, span) and inside(span, tick)
        # read only once the step that takes the token is on its way
        assert dispatch[2] <= span[1] and admitted[-1][2] <= tick[1]
    # no other call delivers a first token
    assert all(inside(s, tick) for s in delivered)


def test_an_engine_that_reads_in_place_says_so(tmp_path_factory):
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(0)
    engine = ServingEngine(GPTForCausalLM(gpt_tiny()), config=EngineConfig(
        block_size=4, num_blocks=32, max_batch=4, max_model_len=64,
        spec=SpeculativeConfig(num_draft_tokens=2)))
    spans = serve_traced(tmp_path_factory, engine,
                         [(PROMPTS[0], 5), (PROMPTS[1], 4)])
    admitted, delivered = split(spans)
    assert [s[3]["ahead"] for s in admitted] == [0, 0]
    assert [s[3] for s in delivered] == [{"req": 0}, {"req": 1}]
    admits = [s for s in spans if s[0] == "admit"]
    for span, first in zip(delivered, admitted):
        assert first[2] <= span[1]
        assert any(inside(span, a) and inside(first, a) for a in admits)
        assert [s for s in spans
                if s[0] == "prefill.readback" and inside(s, span)]


def test_the_routing_counts_arrive_with_the_token(lfm2_traced):
    engine, spans = lfm2_traced
    layers, k = engine.runner.family.routed
    admitted, delivered = split(spans)
    assert [s[3]["ahead"] for s in admitted] == [1, 1, 1]
    assert not any(name in s[3] for s in admitted for name in ROUTING)
    assert [s[3]["req"] for s in delivered] == [0, 1, 2]
    for span, n in zip(delivered, LFM2_PROMPTS):
        c = span[3]
        assert set(c) == {"req", *ROUTING}
        # every prompt token, k experts each, every expert layer
        assert c["moe_assignments"] == n * k * layers
        assert 1 <= c["moe_load_max"] <= n and c["moe_experts_hit"] >= layers
    assert engine.prefill_ahead == 3 and engine.ahead_dropped == 0


# -- the benchmark's readers over the engine's real spans --------------------
@pytest.fixture()
def readers(monkeypatch, lfm2_traced):
    """``benchmark/program_trace.py``, ``moe_trace.py`` and the
    ``prefill_span_ms_per_ktok.serve`` reader, with the engine's spans
    as the loaded trace of a context; spans in ns as the readers take
    them."""
    monkeypatch.syspath_prepend(BENCHMARK)
    for name in ("program_trace", "moe_trace", "trace_reduce", "common"):
        monkeypatch.delitem(sys.modules, name, raising=False)
    import moe_trace
    import program_trace
    per_ktok = reader("prefill_span_ms_per_ktok.serve")
    pt = program_trace.ProgramTrace()
    pt.spans = list(lfm2_traced[1])
    ctx = {"cell": {"trace_dir": "spans-of-the-test"},
           "trace": types.SimpleNamespace(window=None)}
    monkeypatch.setattr(program_trace.trace_reduce, "find_xplane",
                        lambda trace_dir: trace_dir)
    monkeypatch.setitem(program_trace._LOADED, "spans-of-the-test", pt)
    yield types.SimpleNamespace(program_trace=program_trace, pt=pt, ctx=ctx,
                                moe_trace=moe_trace, per_ktok=per_ktok)
    for name in ("program_trace", "moe_trace", "trace_reduce", "common"):
        sys.modules.pop(name, None)


def test_the_benchmarks_readers_keep_reading_what_they_read(
        readers, lfm2_traced):
    engine, spans = lfm2_traced
    layers, k = engine.runner.family.routed
    admitted, delivered = split(spans)
    # moe_gmm_roofline_pct.serve / moe_load_max_over_mean.serve: every
    # prefill's required work is counted, once, beside the decode steps'
    found = readers.moe_trace.routing_counts(readers.ctx)
    of_prefills = [c for name, c in found if name == "prefill"]
    assert [c["req"] for c in of_prefills] == [0, 1, 2]
    assert sum(c["moe_assignments"] for c in of_prefills) \
        == sum(LFM2_PROMPTS) * k * layers
    steps = [c for name, c in found if name == "decode.dispatch"]
    assert len(steps) == 3 and all(c["moe_assignments"] == 3 * k * layers
                                   for c in steps)
    # prefill_span_ms_per_ktok.serve: a prefill's host-visible time, both
    # of its spans, over its tokens, counted once
    every = admitted + delivered
    want = sum(s[2] - s[1] for s in every) / 1e6 / (sum(LFM2_PROMPTS) / 1e3)
    assert readers.per_ktok.read(readers.ctx) == pytest.approx(want)
    assert want > 0
    # prefill_pad_pct: the admission's counts alone (9, 12, 14 pad to 16)
    assert readers.program_trace.prefill_pad_pct(readers.pt) \
        == pytest.approx(100.0 * (48 - sum(LFM2_PROMPTS)) / 48)


# -- the rows the grouped matmul's tiles multiply (PR 40) ---------------------
def tile_rows_by_hand(chosen, padded, E):
    """``visits x tm`` of ``kernels.moe_gmm``'s plan, recomputed from
    the experts chosen ``[rows, layers, k]`` of a program that ran
    ``padded`` rows: a layer's assignments sorted by expert, the padding
    parked behind the last one; a visit is a (row tile, expert) pair
    that share rows."""
    from paddle2_tpu.kernels.moe_gmm import _row_tile
    rows, layers, k = chosen.shape
    tm = _row_tile(padded * k, E + 1)
    return tm * sum(
        visits_by_hand(np.bincount(chosen[:, layer].ravel(), minlength=E),
                       0, E, tm) for layer in range(layers))


def test_tile_rows_ride_with_the_routing_counts(readers, lfm2_traced):
    """``moe_tile_rows`` on the read-back ``prefill`` span and on every
    ``decode.dispatch`` that read a step back is the plan's visits times
    its row tile, summed over the expert layers — recomputed here from
    the experts the engine says it chose — and
    ``moe_gmm_tile_fill_pct.serve`` reads assignments over it."""
    engine, spans = lfm2_traced
    assert ROUTING[-2:] == ("moe_tile_rows", "moe_rows_moved")
    E = engine.runner.model.cfg.num_experts
    chosen = [engine.routed_experts(rid) for rid in range(3)]
    _, delivered = split(spans)
    for span, n, ch in zip(delivered, LFM2_PROMPTS, chosen):
        # a prompt of 9, 12 or 14 tokens ran padded to 16 rows
        assert span[3]["moe_tile_rows"] == tile_rows_by_hand(ch[:n], 16, E)
        assert span[3]["moe_tile_rows"] >= span[3]["moe_assignments"]
    steps = [s[3] for s in spans
             if s[0] == "decode.dispatch" and "moe_tile_rows" in s[3]]
    assert len(steps) == 3
    for j, c in enumerate(steps):
        # step j fed each request the token behind its prompt + j, in a
        # batch of 4 rows
        rows = np.stack([ch[n + j] for n, ch in zip(LFM2_PROMPTS, chosen)])
        assert c["moe_tile_rows"] == tile_rows_by_hand(rows, 4, E)
    fill = reader("moe_gmm_tile_fill_pct.serve")
    counted = [s[3] for s in delivered] + steps
    want = 100.0 * sum(c["moe_assignments"] for c in counted) \
        / sum(c["moe_tile_rows"] for c in counted)
    assert fill.read(readers.ctx) == pytest.approx(want) and 0 < want <= 100
    # a program from before the count (the parent's spans): nothing
    for s in readers.pt.spans:
        s[3].pop("moe_tile_rows", None)
    assert fill.read(readers.ctx) is None
