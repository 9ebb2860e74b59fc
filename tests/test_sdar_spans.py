"""The span contract of a block-diffusion family (PERF.md section 3),
beside ``test_prefill_ahead_spans.py``: ``decode.dispatch`` says of the
step it ENQUEUES ``seqs``, ``block_length``, ``denoise_rows``,
``commit_rows``, ``fresh_blocks`` and ``ctx_tokens`` (a sequence's
context once a pass), and of the step it READS BACK ``tokens_fixed`` and
``tokens_committed`` beside the routing counts; ``prefill`` keeps
``tokens`` / ``padded`` and gains ``block_tokens``. The last test runs
the benchmark's new readers' own code over the engine's spans."""

import os
import sys
import types

import numpy as np
import pytest

from paddle2_tpu.models import SdarMoeForCausalLM, sdar_moe_tiny
from served import ROUTING, reader, seeded_engine, serve_traced

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
PROMPTS, NEW = (9, 12, 14), (8, 7, 4)           # left over: 1, 0, 2


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    engine = seeded_engine(SdarMoeForCausalLM,
                           sdar_moe_tiny(num_hidden_layers=2),
                           denoising_steps=2)
    rng = np.random.default_rng(0)
    spans = serve_traced(
        tmp_path_factory, engine,
        [(rng.integers(1, 503, n).tolist(), m)
         for n, m in zip(PROMPTS, NEW)])
    return engine, spans


def test_prefill_says_what_it_left_for_the_first_block(traced):
    _, spans = traced
    admitted = [s[3] for s in spans if s[0] == "prefill"
                and "tokens" in s[3]]
    assert [(c["tokens"], c["padded"], c["block_tokens"], c["ahead"])
            for c in admitted] == [(8, 16, 1, 1), (12, 16, 0, 1),
                                   (12, 16, 2, 1)]
    # the read-back brings the routing counts of the rows prefilled
    back = [s[3] for s in spans if s[0] == "prefill"
            and "tokens" not in s[3]]
    assert [c["moe_assignments"] for c in back] == [
        n * 2 * 2 for n in (8, 12, 12)]


def test_dispatch_counts_the_step_it_enqueues_and_the_one_it_reads(traced):
    engine, spans = traced
    steps = [s[3] for s in spans if s[0] == "decode.dispatch"
             and "block_length" in s[3]]
    # the first step opens three blocks (two with prompt tokens in them)
    first = steps[0]
    assert (first["seqs"], first["block_length"], first["fresh_blocks"],
            first["denoise_rows"], first["commit_rows"]) == (3, 4, 3, 3, 0)
    assert first["ctx_tokens"] == (8 + 4) + (12 + 4) + (12 + 4)
    assert first["ahead"] == 0 and all(c["ahead"] == 1 for c in steps[1:])
    for c in steps:
        assert c["seqs"] == c["rows"] == c["denoise_rows"] + c["commit_rows"]
        # the paged kernel's counts ride the block step too; a pool of
        # a test's size holds no run: every page a copy of its own
        assert c["kernel_pages_per_block"] > 0
        assert c["coalesced_pages"] == 0
    # what was read back: every position fixed once, every token
    # committed once, the routing counts of B rows a sequence
    read = [s[3] for s in spans if s[0] == "decode.dispatch"
            and "tokens_committed" in s[3]]
    assert sum(c["tokens_committed"] for c in read) == sum(NEW)
    blocks = [-(-(n % 4 + m) // 4) for n, m in zip(PROMPTS, NEW)]
    assert sum(c["tokens_fixed"] for c in read) \
        == 4 * sum(blocks) - sum(n % 4 for n in PROMPTS)
    assert all(set(ROUTING) <= set(c) for c in read)
    assert engine.ahead_steps == len(steps) - 1
    assert engine.ahead_dropped == 0


@pytest.fixture()
def readers(monkeypatch, traced):
    """The benchmark's four new readers with the engine's spans as the
    loaded trace of a context."""
    monkeypatch.syspath_prepend(BENCHMARK)
    mods = ("program_trace", "moe_trace", "trace_reduce", "common",
            "roofline", "roofline.sdar_moe")
    for name in mods:
        monkeypatch.delitem(sys.modules, name, raising=False)
    import program_trace
    pt = program_trace.ProgramTrace()
    pt.spans = list(traced[1])
    monkeypatch.setattr(program_trace.trace_reduce, "find_xplane",
                        lambda trace_dir: trace_dir)
    monkeypatch.setitem(program_trace._LOADED, "spans-of-the-test", pt)

    yield lambda name: reader(name).read
    for name in mods:
        sys.modules.pop(name, None)


def test_the_new_readers_read_the_engines_own_spans(readers, traced):
    engine, spans = traced
    cfg = engine.model.cfg
    steps = [s[3] for s in spans if s[0] == "decode.dispatch"
             and "block_length" in s[3]]
    seen = {}
    reduce = types.SimpleNamespace(pattern_time=lambda trace, pattern: (
        seen.update(pattern=pattern) or {0: (2_000_000, 7)}))
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = {"cell": {"trace_dir": "spans-of-the-test", "peaks": peaks,
                    "workload": {"kernels": {
                        "paged_block": {"pattern": "paged_decode"}}},
                    "config": {
                        "num_hidden_layers": cfg.num_hidden_layers,
                        "num_attention_heads": cfg.num_attention_heads,
                        "num_key_value_heads": cfg.num_key_value_heads,
                        "head_dim": cfg.head_dim}},
           "trace": types.SimpleNamespace(window=(0, 2 ** 62), devices=[0]),
           "reduce": reduce}
    # committed_tokens_per_step.serve
    assert readers("committed_tokens_per_step.serve")(ctx) \
        == pytest.approx(sum(NEW) / len(steps))
    # paged_block_roofline_pct.serve: K and V of every context ONCE a
    # pass and layer, over the kernel's 2 ms
    ctx_sum = sum(c["ctx_tokens"] for c in steps)
    nbytes = 2 * ctx_sum * cfg.num_key_value_heads * cfg.head_dim \
        * cfg.num_hidden_layers * 2
    assert readers("paged_block_roofline_pct.serve")(ctx) \
        == pytest.approx(100.0 * (nbytes / 1e9) / 2e-3)
    assert seen["pattern"] == "paged_decode"
    # the scope readers find no device ops in a CPU trace: nothing, not 0
    assert readers("unmask_device_pct.serve")(ctx) is None
    assert readers("attn_device_pct.serve")(ctx) is None
    # a program that wrote no block counts (the parent): nothing
    import program_trace
    program_trace._LOADED["spans-of-the-test"].spans = [
        (n, a, b, {k: v for k, v in c.items() if k != "block_length"})
        for n, a, b, c in spans]
    assert readers("committed_tokens_per_step.serve")(ctx) is None
    assert readers("paged_block_roofline_pct.serve")(ctx) is None
