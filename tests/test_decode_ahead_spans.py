"""The span contract of the run-ahead decode step (PERF.md section 3),
beside ``test_program_spans.py``: the nesting ``decode`` > ``select``,
``build_batch``, ``dispatch`` > ``readback``, ``emit`` holds as before;
``decode.dispatch`` carries ``ahead`` (1 when the step went out with the
one before it un-read) and ``dropped_ahead`` (tokens in flight thrown
away since the last dispatch); the routing counts arrive with the
read-back and sit on every ``decode.dispatch`` that read a step back."""

import numpy as np
import pytest

from paddle2_tpu.models import Lfm2MoeForCausalLM, lfm2_moe_tiny
from paddle2_tpu.serving import EngineConfig, ServingEngine
from paddle2_tpu.serving.spec import SpeculativeConfig
from served import (PARENT, PROMPTS, ROUTING, serve_traced,
                    tiny_gpt_engine)


def ticks_of(spans):
    """[(the ``decode`` span, {child name: [spans]})] in order."""
    out = []
    for tick in (s for s in spans if s[0] == "decode"):
        kids = {}
        for s in spans:
            if PARENT.get(s[0]) in ("decode", "decode.dispatch") \
                    and tick[1] <= s[1] and s[2] <= tick[2]:
                kids.setdefault(s[0], []).append(s)
        out.append((tick, kids))
    return out


@pytest.fixture(scope="module")
def gpt_ticks(tmp_path_factory):
    # 4 and 3 new tokens: steps 1..3, the shorter leaves after step 2
    return ticks_of(serve_traced(tmp_path_factory, tiny_gpt_engine(),
                                 [(PROMPTS[0], 4), (PROMPTS[1], 3)]))


def test_nesting_holds_and_the_readback_is_of_the_step_before(gpt_ticks):
    assert len(gpt_ticks) == 4
    for tick, kids in gpt_ticks:
        for name, found in kids.items():
            parent = kids[PARENT[name]][0] if PARENT[name] != "decode" \
                else tick
            for s in found:
                assert parent[1] <= s[1] and s[2] <= parent[2], name
        assert len(kids["decode.dispatch"]) == 1
    names = [sorted(kids) for _, kids in gpt_ticks]
    enqueue = ["decode.build_batch", "decode.dispatch", "decode.select"]
    deliver = ["decode.emit", "decode.readback"]
    # the first call only enqueues; the last (both sequences' last
    # tokens in flight: nothing left to select) only delivers
    assert names[0] == enqueue
    assert names[1] == names[2] == sorted(enqueue + deliver)
    assert names[3] == sorted(deliver + ["decode.dispatch", "decode.select"])
    for _, kids in gpt_ticks[1:]:
        disp, back = kids["decode.dispatch"][0], kids["decode.readback"][0]
        emit = kids["decode.emit"][0]
        assert disp[1] <= back[1] and back[2] <= disp[2] <= emit[1]


def test_ahead_is_zero_on_the_first_tick_and_one_on_steady_ticks(gpt_ticks):
    counts = [kids["decode.dispatch"][0][3] for _, kids in gpt_ticks]
    assert [c["ahead"] for c in counts] == [0, 1, 1, 0]
    assert [c["dropped_ahead"] for c in counts] == [0, 0, 0, 0]
    # the counts of a step are on the dispatch that enqueued it; a call
    # that only delivers describes no step
    assert [c.get("rows") for c in counts] == [2, 2, 1, None]
    assert "row_bucket" not in counts[3] and "blocks_total" not in counts[3]


def test_a_speculative_engine_is_never_ahead(tmp_path_factory):
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    import paddle2_tpu as paddle
    paddle.seed(0)
    engine = ServingEngine(GPTForCausalLM(gpt_tiny()), config=EngineConfig(
        block_size=4, num_blocks=32, max_batch=4, max_model_len=64,
        spec=SpeculativeConfig(num_draft_tokens=2)))
    ticks = ticks_of(serve_traced(tmp_path_factory, engine,
                                  [(PROMPTS[0], 5), (PROMPTS[1], 4)]))
    assert len(ticks) >= 2
    for _, kids in ticks:
        assert kids["decode.dispatch"][0][3]["ahead"] == 0
        # today's order: every step is read back in its own call
        assert len(kids["decode.readback"]) == len(kids["decode.emit"]) == 1


def test_routing_counts_are_on_every_dispatch_that_read_back(
        tmp_path_factory):
    import paddle2_tpu as paddle
    paddle.seed(0)
    model = Lfm2MoeForCausalLM(lfm2_moe_tiny())
    model.eval()
    engine = ServingEngine(model, config=EngineConfig(
        block_size=8, num_blocks=64, max_batch=4, max_model_len=96,
        batch_buckets=(4,), page_buckets=(4,), interpret=True))
    rng = np.random.default_rng(0)
    ticks = ticks_of(serve_traced(
        tmp_path_factory, engine,
        [(rng.integers(1, 503, n).tolist(), 4) for n in (9, 12)]))
    assert len(ticks) == 4
    layers, k = engine.runner.family.routed
    for i, (_, kids) in enumerate(ticks):
        c = kids["decode.dispatch"][0][3]
        if "decode.readback" in kids:
            # two rows of the step READ, k experts each, every layer
            assert c["moe_assignments"] == 2 * k * layers
            assert 1 <= c["moe_load_max"] <= 2
            assert c["moe_experts_hit"] >= layers
        else:
            assert i == 0 and not any(name in c for name in ROUTING)
    assert [kids["decode.dispatch"][0][3]["ahead"]
            for _, kids in ticks] == [0, 1, 1, 0]
