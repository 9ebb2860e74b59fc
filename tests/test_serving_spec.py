"""Speculative decoding through the serving engine: token for token, the
oracle and the always-wrong drafter, the program census (moved from
``test_serving_throughput.py``)."""

import pytest

import paddle2_tpu as paddle
from paddle2_tpu.serving import (EngineConfig, SpeculativeConfig,
                                 ServingEngine, poisson_trace,
                                 simulate_serving)
from served import shared_programs  # noqa: F401

pytestmark = pytest.mark.usefixtures("shared_programs")


@pytest.fixture(scope="module")
def tiny_model():
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(0)
    cfg = gpt_tiny(use_scan=False, max_position_embeddings=128)
    return GPTForCausalLM(cfg)


def _mk_engine(model, **kw):
    defaults = dict(block_size=16, num_blocks=48, max_batch=4,
                    prefill_budget_tokens=64, max_model_len=128)
    defaults.update(kw)
    return ServingEngine(model, config=EngineConfig(**defaults))


def _trace(model, n=6, seed=7, vocab=None, gen=(10, 14)):
    return poisson_trace(n, rate_per_s=5000.0, prompt_lens=[16, 24],
                         gen_tokens=list(gen),
                         vocab=vocab or model.cfg.vocab_size, seed=seed)


def test_spec_decode_token_for_token(tiny_model):
    """ACCEPTANCE: speculative decoding (n-gram self-draft) emits the
    EXACT non-speculative stream in fewer decode steps, and the
    allocator drains clean (rejected tails rolled back)."""
    trace = _trace(tiny_model)
    e0 = _mk_engine(tiny_model)
    simulate_serving(e0, [dict(t) for t in trace])
    toks0 = [e0.sequence(i).generated for i in range(len(trace))]
    e1 = _mk_engine(tiny_model, spec=SpeculativeConfig(
        num_draft_tokens=3))
    rep1 = simulate_serving(e1, [dict(t) for t in trace])
    toks1 = [e1.sequence(i).generated for i in range(len(trace))]
    assert toks1 == toks0
    assert e1.spec_accepted + e1.spec_rejected > 0
    assert e1.allocator.free_count == e1.allocator.num_blocks - 1
    assert rep1.spec_accepted == e1.spec_accepted


def test_spec_decode_oracle_and_wrong_drafts(tiny_model):
    """A perfect oracle collapses steps ~4x; an adversarial always-
    wrong drafter changes NOTHING but the step count."""
    trace = _trace(tiny_model, n=4, seed=9)
    e0 = _mk_engine(tiny_model)
    rep0 = simulate_serving(e0, [dict(t) for t in trace])
    truth = [e0.sequence(i).generated for i in range(len(trace))]

    def oracle(seq):
        t = truth[seq.req_id]
        done = len(seq.generated)
        return t[done:done + 3]

    e1 = _mk_engine(tiny_model, spec=SpeculativeConfig(
        num_draft_tokens=3, draft_fn=oracle))
    rep1 = simulate_serving(e1, [dict(t) for t in trace])
    assert [e1.sequence(i).generated
            for i in range(len(trace))] == truth
    assert rep1.decode_steps < rep0.decode_steps
    assert e1.spec_rejected == 0

    def wrong(seq):
        t = truth[seq.req_id]
        done = len(seq.generated)
        nxt = t[done] if done < len(t) else 0
        return [(int(nxt) + 1) % tiny_model.cfg.vocab_size]

    e2 = _mk_engine(tiny_model, spec=SpeculativeConfig(
        num_draft_tokens=1, draft_fn=wrong))
    rep2 = simulate_serving(e2, [dict(t) for t in trace])
    assert [e2.sequence(i).generated
            for i in range(len(trace))] == truth
    assert e2.spec_accepted == 0 and e2.spec_rejected > 0


def test_spec_program_census_stays_bounded(tiny_model):
    e = _mk_engine(tiny_model, spec=SpeculativeConfig(
        num_draft_tokens=3))
    simulate_serving(e, [dict(t) for t in _trace(tiny_model, n=4)])
    assert e.num_decode_programs <= e.program_budget
    # the ladder covers the widest verify batch
    assert e.scheduler.config.batch_buckets[-1] >= 4 * (1 + 3)
