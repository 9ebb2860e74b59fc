"""The span contract of the DeepSeek-V2 family (PERF.md section 3),
beside ``test_prefill_ahead_spans.py``: ``decode.dispatch`` and the
read-back ``prefill`` span carry, beside the routing counts every routed
family writes, ``moe_rows_routed_here`` and ``moe_rows``; ``ctx_tokens``,
``live_pages`` and ``kernel_pages_per_block`` are of LATENT pages,
``coalesced_pages`` those of them the kernel fetches a run of consecutive
pages at a time — and the benchmark's readers (``moe_rows_here_pct.serve``,
``mla_pages_coalesced_pct.serve``, and the counts
``paged_mla_roofline_pct.serve`` / ``flash_mla_prefill_roofline_pct.serve``
take) read them off the engine's own spans."""

import os
import sys
import types

import numpy as np
import pytest

from paddle2_tpu.models import DeepseekV2ForCausalLM, deepseek_v2_tiny
from paddle2_tpu.observability import metrics
from paddle2_tpu.serving import ServingEngine
from paddle2_tpu.serving import paged_attention as pa
from served import ROUTING, reader, seeded_engine, serve_traced

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
PROMPTS = (9, 12, 14)
NEW = 4


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    engine = seeded_engine(DeepseekV2ForCausalLM,
                           deepseek_v2_tiny(held_group=1))
    rng = np.random.default_rng(0)
    spans = serve_traced(
        tmp_path_factory, engine,
        [(rng.integers(1, 503, n).tolist(), NEW) for n in PROMPTS])
    return engine, spans


def test_rows_routed_and_rows_here_ride_with_the_routing_counts(traced):
    engine, spans = traced
    layers, k = engine.runner.family.routed
    assert ROUTING[3:5] == ("moe_rows_routed_here", "moe_rows")
    delivered = [s[3] for s in spans
                 if s[0] == "prefill" and "tokens" not in s[3]]
    assert [c["req"] for c in delivered] == [0, 1, 2]
    for c, n in zip(delivered, PROMPTS):
        assert set(c) == {"req", *ROUTING}
        # every prompt token is routed in every expert layer; those
        # with an expert of the held group are the rows computed for
        assert c["moe_rows"] == n * layers
        assert c["moe_assignments"] <= k * c["moe_rows_routed_here"]
        assert c["moe_rows_routed_here"] <= c["moe_assignments"] \
            <= c["moe_rows"] * k
    steps = [s[3] for s in spans if s[0] == "decode.dispatch"]
    read = [c for c in steps if "moe_rows" in c]
    assert read and all(c["moe_rows"] == 3 * layers for c in read)
    assert all(c["moe_rows_routed_here"] <= c["moe_rows"] for c in read)


def test_the_page_counts_are_of_latent_pages(traced):
    engine, spans = traced
    width = engine.runner.family.kv_widths[0]
    steps = [s[3] for s in spans
             if s[0] == "decode.dispatch" and "rows" in s[3]]
    assert steps
    first = steps[0]
    # the step after the prefills: each row at its prompt's length
    assert first["ctx_tokens"] == sum(PROMPTS)
    assert first["live_pages"] == sum(-(-(n + 1) // 8) for n in PROMPTS)
    assert first["kernel_pages_per_block"] == pa.mla_pages_per_block(
        4, 8, width, engine.cache.dtype)
    assert first["blocks_total"] == 64
    # a copy group is 16 pages of 8 slots here, no row holds 16: every
    # page of these steps arrives a page a copy
    assert pa.mla_pages_per_copy(4, 8, width, engine.cache.dtype) == 16
    assert all(c["coalesced_pages"] == 0 for c in steps)


def test_coalesced_pages_of_a_table_by_hand(traced):
    """The count the engine asks of the family (``_step_counts`` ->
    runner -> family) for a step's tables: the pages of the aligned
    groups of entries — 48 at this table's shapes — that are all live
    and hold consecutive ascending page ids."""
    engine, _ = traced
    shape = (96, 8, engine.runner.family.kv_widths[0], engine.cache.dtype)
    assert pa.mla_pages_per_copy(*shape) == 48
    tables = np.zeros((4, 96), np.int32)
    tables[0] = np.arange(5, 101)            # one run, 60 pages live
    tables[1] = np.arange(300, 204, -1)      # a run that descends
    tables[2, :48] = np.arange(120, 168)     # a run, then scattered ids
    tables[2, 48:] = np.arange(400, 496, 2)
    live = [60, 96, 96]                      # row 3 is batch padding
    counts = engine._step_counts(3, tables, 0, live, [])
    assert counts["live_pages"] == 252
    assert counts["coalesced_pages"] == 48 + 0 + 48
    assert counts["kernel_pages_per_block"] == pa.mla_pages_per_block(*shape)
    assert (counts["row_bucket"], counts["page_bucket"]) == (4, 96)


@pytest.fixture()
def readers(monkeypatch, traced):
    monkeypatch.syspath_prepend(BENCHMARK)
    for name in ("program_trace", "moe_trace", "trace_reduce", "common"):
        monkeypatch.delitem(sys.modules, name, raising=False)
    import program_trace

    pt = program_trace.ProgramTrace()
    pt.spans = list(traced[1])
    ctx = {"cell": {"trace_dir": "spans-of-the-test"},
           "trace": types.SimpleNamespace(window=None)}
    monkeypatch.setattr(program_trace.trace_reduce, "find_xplane",
                        lambda trace_dir: trace_dir)
    monkeypatch.setitem(program_trace._LOADED, "spans-of-the-test", pt)
    yield types.SimpleNamespace(program_trace=program_trace, pt=pt, ctx=ctx,
                                reader=reader)
    for name in ("program_trace", "moe_trace", "trace_reduce", "common"):
        sys.modules.pop(name, None)


def test_the_new_readers_read_the_engines_own_spans(readers, traced):
    engine, spans = traced
    counted = [s[3] for s in spans if s[0] in ("decode.dispatch", "prefill")
               and "moe_rows" in s[3]]
    want = 100.0 * sum(c["moe_rows_routed_here"] for c in counted) \
        / sum(c["moe_rows"] for c in counted)
    got = readers.reader("moe_rows_here_pct.serve").read(readers.ctx)
    assert got == pytest.approx(want) and 0 < got < 100
    # the coalesced share: 0 of these steps' live pages (the test above),
    # the steps' own ratio once a span says otherwise, nothing where no
    # span carries the count (a program from before it)
    coalesced = readers.reader("mla_pages_coalesced_pct.serve")
    assert coalesced.read(readers.ctx) == 0.0
    dispatch = [s for s in readers.pt.spans if s[0] == "decode.dispatch"
                and "coalesced_pages" in s[3]]
    dispatch[0][3]["coalesced_pages"] = dispatch[0][3]["live_pages"]
    assert coalesced.read(readers.ctx) == pytest.approx(
        100.0 * dispatch[0][3]["live_pages"]
        / sum(s[3]["live_pages"] for s in dispatch))
    for s in dispatch:
        del s[3]["coalesced_pages"]
    assert coalesced.read(readers.ctx) is None
    # what the two roofline readers take off the spans is there: the
    # steps' contexts and rows, the admissions' padded lengths
    steps = [c for _, _, _, c in readers.program_trace.spans_named(
        readers.pt, "decode.dispatch") if "ctx_tokens" in c and "rows" in c]
    assert len(steps) == NEW - 1 and all(c["rows"] == 3 for c in steps)
    assert [c["ctx_tokens"] for c in steps] == \
        [sum(PROMPTS) + 3 * i for i in range(NEW - 1)]
    padded = [c["padded"] for _, _, _, c in readers.program_trace.spans_named(
        readers.pt, "prefill") if c.get("padded")]
    assert padded == [16, 16, 16]
    # and without a device trace or peaks they say nothing, not zero
    cell = {"workload": {"kernels": {"paged_mla_decode": {}, "flash_fwd": {}}},
            "config": {"kv_lora_rank": 32}, "peaks": None,
            "trace_dir": "spans-of-the-test"}
    ctx = dict(readers.ctx, cell=cell,
               trace=types.SimpleNamespace(window=None, devices={}))
    for name in ("paged_mla_roofline_pct.serve",
                 "flash_mla_prefill_roofline_pct.serve"):
        assert readers.reader(name).read(ctx) is None


def test_the_metrics_plane_counts_the_rows(tmp_path):
    plane = metrics.enable(str(tmp_path), rank=0)
    try:
        stats = ServingEngine._count_stats(
            {"moe_assignments": [5, 4], "moe_experts_hit": [2, 2],
             "moe_load_max": [3, 2], "moe_rows_routed_here": [4, 3],
             "moe_rows": [9, 9], "moe_tile_rows": [64, 32]})
        assert stats == {"moe_assignments": 9, "moe_experts_hit": 4,
                         "moe_load_max": 3, "moe_rows_routed_here": 7,
                         "moe_rows": 18, "moe_tile_rows": 96}
        assert plane.counter("serving_moe_rows_total").value() == 18
        assert plane.counter(
            "serving_moe_rows_routed_here_total").value() == 7
    finally:
        metrics.disable()
