"""The run-ahead decode step's contract (beside ``test_decode_ahead.py``, from
which these moved): GPT's tokens are ``generate``'s, any bucket follows any
other, one step warms every later one, what a call delivers, and what must
not overtake the step in flight."""

import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.models import GPTForCausalLM, gpt_tiny
from paddle2_tpu.serving.reliability import EngineFailedError
from paddle2_tpu.serving.spec import SpeculativeConfig
from served import (FAMILIES, armed, assert_same, drive,  # noqa: F401
                    engine_of, models, own_programs, prompts_of,
                    shared_programs, step_by_step)

pytestmark = pytest.mark.usefixtures("shared_programs")


def test_gpt_tokens_are_generates(models):
    """The reference of the reference: ``model.generate``, greedy."""
    model = models["gpt"]
    p = prompts_of(model, (12, 9), seed=6)
    engine = engine_of(model)
    tokens, _ = drive(engine, [(0, p[0], 7), (1, p[1], 5)])
    for prompt, got in zip(p, tokens):
        ref = model.generate(np.asarray(prompt, np.int32)[None],
                             max_new_tokens=len(got), temperature=0.0)
        assert got == np.asarray(ref.numpy())[0][len(prompt):].tolist()
    assert engine.ahead_steps >= 4


def test_any_bucket_follows_any_other(models):
    """The step before may have had another row bucket: the tokens kept
    on the device are as wide as the widest batch, so the ladder's
    programs follow each other without a build beyond the grid."""
    model = models["gpt"]
    p = prompts_of(model, (6, 9, 7, 11), seed=7)
    arrivals = [(0, p[0], 10), (2, p[1], 8), (3, p[2], 3), (5, p[3], 4)]
    ladder = dict(batch_buckets=None, page_buckets=None)
    ahead = engine_of(model, **ladder)
    got = drive(ahead, arrivals)
    plain = engine_of(model, **ladder)
    with step_by_step():
        want = drive(plain, arrivals)
    assert_same(got, want)
    assert len({b for b, _ in ahead.runner._decode_programs}) >= 2
    assert ahead.num_decode_programs <= ahead.program_budget
    assert ahead.ahead_steps >= 6


def test_one_step_warms_every_later_one(models, caplog, own_programs):
    """The benchmark's warm-up serves two tokens a request: ONE decode
    step per bucket, with the device's tokens still zeros. Steps fed by
    a step in flight must find that program and compile nothing (a
    compilation inside a measured window is ``correct: false``)."""
    import jax
    model = models["gpt"]
    engine = engine_of(model)
    p = prompts_of(model, (10, 12, 9), seed=13)
    drive(engine, [(0, p[0], 2), (0, p[1], 2)])
    assert engine.num_decode_programs == 1 and engine.decode_steps == 1
    def canary(x):
        return x * 3 + 1

    with jax.log_compiles(), caplog.at_level("WARNING"):
        drive(engine, [(0, p[2], 6), (1, p[0], 5)])
        jax.jit(canary)(np.ones(7, np.float32))
    compiled = [r.getMessage() for r in caplog.records
                if "Compiling" in r.getMessage()]
    assert [m for m in compiled if "canary" in m]   # the log does see them
    assert not [m for m in compiled if "p2t_decode" in m]
    assert engine.ahead_steps >= 4


# -- the call's contract ------------------------------------------------------
def test_a_call_delivers_the_step_before_it(models):
    model = models["gpt"]
    engine = engine_of(model)
    rid = engine.submit(prompts_of(model, (9,))[0], 4)
    seq = engine.sequence(rid)
    first = engine.tick(0.0)
    # prefill's token; step 1 is on the device, not in the log
    assert first["dispatched"] and first["tokens"] == 0
    assert len(seq.generated) == 1 and not engine.idle()
    second = engine.tick(1.0)
    assert second["dispatched"] and second["tokens"] == 1
    assert len(seq.generated) == 2
    third = engine.tick(2.0)          # step 3 carries the last token
    assert third["dispatched"] and len(seq.generated) == 3
    assert not seq.done and not engine.idle()
    last = engine.tick(3.0)           # nothing to enqueue: delivers only
    assert last is not None and not last["dispatched"]
    assert last["tokens"] == 1 and last["n_active"] == 1
    assert {"bucket", "n_active", "tokens", "evictions", "cost"} <= set(last)
    assert seq.done and engine.idle() and engine.tick(4.0) is None
    assert engine.ahead_steps == 2 and engine.decode_steps == 3


def test_speculation_never_runs_ahead(models):
    model = models["gpt"]
    engine = engine_of(model, batch_buckets=None, page_buckets=None,
                       spec=SpeculativeConfig(num_draft_tokens=2))
    p = prompts_of(model, (10, 8), seed=8)
    rids = [engine.submit(q, 6) for q in p]
    while not engine.idle():
        info = engine.tick(0.0)
        assert engine._ahead is None and info["dispatched"]
    assert engine.ahead_steps == 0
    plain = engine_of(model)
    tokens, _ = drive(plain, [(0, q, 6) for q in p])
    assert [engine.sequence(r).generated for r in rids] == tokens


# -- what must not overtake the step in flight ------------------------------
def run_until_in_flight(engine, ticks=3):
    for t in range(ticks):
        engine.tick(float(t))
    assert engine._ahead is not None
    return [len(s.tokens) for s in engine.scheduler.running()]


@pytest.mark.parametrize("family", FAMILIES)
def test_drop_hook_armed_with_a_step_in_flight(models, family):
    """The hook is armed between two calls: the next call delivers the
    step in flight and enqueues nothing — and the hook, firing on that
    very step, discards it: no token of it is in a log, and the step is
    computed again."""
    model = models[family]
    p = prompts_of(model, (10, 13), seed=9)
    arrivals = [(0, p[0], 7), (0, p[1], 7)]
    engine = engine_of(model)
    rids = [engine.submit(q, n) for _, q, n in arrivals]
    before = run_until_in_flight(engine)
    with armed("drop_decode_step:1"):
        info = engine.tick(3.0)
        assert info["dropped"] and not info["dispatched"]
        assert info["tokens"] == 0 and engine._ahead is None
        assert [len(engine.sequence(r).tokens) for r in rids] == before
        moved = family == "lfm2"
        # a family with per-sequence state: the discarded step has
        # shifted the convolution state its repeat would read, so its
        # rows went back to the queue to be re-prefilled (ROADMAP D13)
        assert len(engine.scheduler.running()) == (0 if moved else 2)
        assert engine.state_reprefills == (2 if moved else 0)
        while not engine.idle():
            info = engine.tick(4.0)
            assert engine._ahead is None
            assert info["dispatched"] or moved
    assert [len(engine.sequence(r).generated) for r in rids] == [7, 7]
    plain = engine_of(model)
    with step_by_step():
        want = drive(plain, arrivals)
    assert_same(([engine.sequence(r).generated for r in rids],
                 [engine.routed_experts(r) for r in rids]), want)


@pytest.mark.parametrize("family", FAMILIES)
def test_killed_engine_drops_the_step_in_flight(models, family):
    """``fail`` (an operator's kill, the router's verdict) with a step
    in flight: the device's state is lost, so is the step; the logs the
    adopter re-prefills hold none of its tokens and the streams end as
    a fault-free run's."""
    model = models[family]
    p = prompts_of(model, (10, 13), seed=10)
    arrivals = [(0, p[0], 7), (0, p[1], 7)]
    dead = engine_of(model)
    for _, q, n in arrivals:
        dead.submit(q, n)
    before = run_until_in_flight(dead)
    dead.fail("test kill", now=3.0)
    assert dead._ahead is None and dead.ahead_dropped == 2
    with pytest.raises(EngineFailedError):
        dead.tick(4.0)
    seqs = dead.recover_inflight()
    assert [len(s.tokens) for s in seqs] == before
    heir = engine_of(model)
    rids = [heir.adopt(s, now=4.0) for s in seqs]
    while not heir.idle():
        heir.tick(5.0)
    plain = engine_of(model)
    with step_by_step():
        want = drive(plain, arrivals)
    assert_same(([heir.sequence(r).generated for r in rids],
                 [heir.routed_experts(r) for r in rids]), want)


def test_chaos_kill_reads_back_first(models):
    """Armed from the start, ``kill_engine`` holds the engine to depth
    0: at the kill no step is in flight and every delivered token is in
    its log."""
    model = models["gpt"]
    engine = engine_of(model)
    engine.submit(prompts_of(model, (10,), seed=11)[0], 8)
    with armed("kill_engine:3"):
        engine.tick(0.0)
        engine.tick(1.0)
        assert engine._ahead is None and engine.decode_steps == 2
        with pytest.raises(EngineFailedError):
            engine.tick(2.0)
    assert engine.ahead_steps == 0 and engine.ahead_dropped == 0
    assert [len(s.generated) for s in engine.recover_inflight()] == [3]


def test_swap_weights_delivers_the_step_in_flight(models):
    """The step in flight ran with the old weights: the swap delivers
    it first, so the streams are those of an engine that swapped
    between the same two steps, read back one by one."""
    model = models["gpt"]
    paddle.seed(1)
    other = GPTForCausalLM(gpt_tiny(use_scan=False))
    p = prompts_of(model, (10, 13), seed=12)

    def run(engine):
        rids = [engine.submit(q, 8) for q in p]
        for t in range(3):
            engine.tick(float(t))
        engine.swap_weights(other, now=3.0)
        assert engine._ahead is None
        delivered = [len(engine.sequence(r).generated) for r in rids]
        while not engine.idle():
            engine.tick(4.0)
        return delivered, [engine.sequence(r).generated for r in rids]

    ahead = engine_of(model)
    got = run(ahead)
    with step_by_step():
        want = run(engine_of(model))
    assert got == want and got[0] == [4, 4]
    old = drive(engine_of(model), [(0, q, 8) for q in p])[0]
    assert got[1] != old          # the new weights did speak
    assert ahead.ahead_dropped == 0
