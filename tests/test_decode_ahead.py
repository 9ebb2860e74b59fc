"""The decode step runs one tick ahead of the host (``ServingEngine.
decode_once``): step n+1 is enqueued, fed on the device by step n's
tokens, before step n is read back.

What is held here, for both served families (GPT, LFM2-MoE), tiny sizes
on the CPU: the served tokens and the routed experts are, request by
request, those of the SAME engine held to reading every step back in
the call that enqueued it. That reference is reached through the
engine's own rule — an armed chaos hook on the step makes it read back
first, and this one never fires — so the two runs differ in nothing but
the depth (1 step ahead, or 0)."""

import contextlib

import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.distributed.fault_tolerance import chaos
from paddle2_tpu.models import (GPTForCausalLM, Lfm2MoeForCausalLM, gpt_tiny,
                                lfm2_moe_tiny)
from paddle2_tpu.serving import EngineConfig, ServingEngine
from paddle2_tpu.serving.reliability import EngineFailedError
from paddle2_tpu.serving.spec import SpeculativeConfig

NEVER = "drop_decode_step:1000000000"
FAMILIES = ["gpt", "lfm2"]


@contextlib.contextmanager
def armed(spec: str):
    chaos.arm(spec)
    try:
        yield
    finally:
        chaos.disarm()


def step_by_step(also: str = ""):
    """Inside, an engine reads every step back before it selects the
    next (today's order before the run-ahead step): its own rule, an
    armed hook on the step. ``also`` arms further chaos beside it."""
    return armed(",".join(s for s in (NEVER, also) if s))


@pytest.fixture(scope="module")
def models():
    paddle.seed(0)
    gpt = GPTForCausalLM(gpt_tiny(use_scan=False))
    lfm2 = Lfm2MoeForCausalLM(lfm2_moe_tiny())
    gpt.eval()
    lfm2.eval()
    return {"gpt": gpt, "lfm2": lfm2}


def engine_of(model, **over):
    """One decode program whatever the batch: 4 rows x 8 pages."""
    kw = dict(block_size=8, num_blocks=48, max_batch=4, max_model_len=64,
              prefill_budget_tokens=64, batch_buckets=(4,),
              page_buckets=(8,), interpret=True)
    kw.update(over)
    return ServingEngine(model, config=EngineConfig(**kw))


def prompts_of(model, lengths, seed=0, shared=0):
    rng = np.random.default_rng(seed)
    vocab = model.cfg.vocab_size
    head = rng.integers(1, vocab, shared).tolist()
    return [head + rng.integers(1, vocab, n - shared).tolist()
            for n in lengths]


def drive(engine, arrivals, max_ticks=400):
    """``arrivals``: [(tick at which it is submitted, prompt, max new)].
    Ticks the engine until every arrival is in and it is idle; returns
    ([tokens of each request], [its routed experts or None])."""
    todo = sorted(arrivals, key=lambda a: a[0])
    rids, tick = [], 0
    while todo or not engine.idle():
        while todo and todo[0][0] <= tick:
            _, prompt, max_new = todo.pop(0)
            rids.append(engine.submit(prompt, max_new))
        engine.tick(float(tick))
        tick += 1
        assert tick < max_ticks, "engine did not drain"
    assert engine._ahead is None
    for rid, (_, _, max_new) in zip(rids, sorted(arrivals,
                                                 key=lambda a: a[0])):
        assert len(engine.sequence(rid).generated) == max_new
    return ([list(engine.sequence(r).generated) for r in rids],
            [engine.routed_experts(r) for r in rids])


def assert_same(got, want):
    tokens, routed = got
    ref_tokens, ref_routed = want
    assert tokens == ref_tokens
    for a, b in zip(routed, ref_routed):
        if b is None:
            assert a is None
        else:
            np.testing.assert_array_equal(a, b)


def both(model, arrivals, also="", **over):
    """(run-ahead engine, its result, step-by-step engine, its result)
    over the same arrivals."""
    ahead = engine_of(model, **over)
    with armed(also) if also else contextlib.nullcontext():
        got = drive(ahead, arrivals)
    plain = engine_of(model, **over)
    with step_by_step(also):
        want = drive(plain, arrivals)
    assert plain.ahead_steps == 0 and plain.ahead_dropped == 0
    # what it was for the same buckets: the one program of the grid
    assert ahead.num_decode_programs == plain.num_decode_programs <= 1
    return ahead, got, plain, want


# -- the same tokens and experts, request by request ------------------------
@pytest.mark.parametrize("family", FAMILIES)
def test_admitted_while_a_step_is_in_flight(models, family):
    """A sequence prefilled between two calls joins the next step, its
    first token fed on the device (``cache.firsts``) beside the rows fed
    by the step in flight (``test_prefill_ahead.py``)."""
    model = models[family]
    p = prompts_of(model, (11, 7, 13))
    ahead, got, _, want = both(model, [(0, p[0], 9), (2, p[1], 6),
                                      (4, p[2], 5)])
    assert_same(got, want)
    assert ahead.ahead_steps >= 6 and ahead.ahead_dropped == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_finishing_on_the_token_in_flight(models, family):
    """A row whose token in flight is its last is not selected again,
    and its slot is free one call later."""
    model = models[family]
    p = prompts_of(model, (9, 12, 10), seed=1)
    ahead, got, _, want = both(model, [(0, p[0], 3), (0, p[1], 7),
                                      (1, p[2], 4)])
    assert_same(got, want)
    assert ahead.ahead_steps >= 4


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("max_new", [1, 2])
def test_one_and_two_new_tokens(models, family, max_new):
    """One token: the prefill's, no decode step. Two: one decode step,
    delivered by the call after the one that enqueued it."""
    model = models[family]
    p = prompts_of(model, (8, 14), seed=2)
    ahead, got, _, want = both(model, [(0, p[0], max_new),
                                      (0, p[1], max_new)])
    assert_same(got, want)
    assert ahead.decode_steps == max_new - 1


@pytest.mark.parametrize("family", FAMILIES)
def test_prefix_cache_hit(models, family):
    """The second and third prompts share the first's leading blocks."""
    model = models[family]
    p = prompts_of(model, (20, 22, 19), seed=3, shared=16)
    ahead, got, _, want = both(model, [(0, p[0], 6), (2, p[1], 6),
                                      (3, p[2], 4)],
                               enable_prefix_cache=True)
    assert_same(got, want)
    assert ahead.prefix_cache.hits >= 2 and ahead.ahead_steps >= 4


@pytest.mark.parametrize("family", FAMILIES)
def test_eviction_of_a_row_in_flight(models, family):
    """Nine usable blocks of 8 under two sequences that grow past 36
    tokens each: the newer is evicted while a token of it is in flight;
    that token is dropped and the re-prefill computes it again."""
    model = models[family]
    p = prompts_of(model, (27, 30), seed=4)
    ahead, got, plain, want = both(model, [(0, p[0], 12), (0, p[1], 12)],
                                   num_blocks=10)
    assert ahead.scheduler.total_evictions >= 1
    assert plain.scheduler.total_evictions >= 1
    assert ahead.ahead_dropped >= 1
    assert_same(got, want)


@pytest.mark.parametrize("family", FAMILIES)
def test_corrupt_table_requeue_of_a_row_in_flight(models, family):
    """A scribbled block table (chaos, third selection) requeues its
    sequence with a token in flight; the table hook does not make the
    engine read back first, only hooks on the step do."""
    model = models[family]
    p = prompts_of(model, (10, 12), seed=5)
    ahead, got, plain, want = both(model, [(0, p[0], 8), (0, p[1], 8)],
                                   also="corrupt_block_table:3")
    assert ahead.ahead_dropped >= 1
    for e in (ahead, plain):
        assert sum(s.recoveries for s in e.scheduler.finished) == 1
    assert_same(got, want)


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


def test_one_step_warms_every_later_one(models, caplog):
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
