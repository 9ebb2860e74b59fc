"""A prefill's first token stays on the device until the decode step that
consumes it is enqueued (``ServingEngine._prefill_admitted`` /
``decode_once``): the admission reads nothing back, the step takes the
token from ``cache.firsts``, and the host reads it after that step went
out, before the step's own token reaches the log.

Beside ``test_decode_ahead.py`` and with its reference: the SAME engine
held to reading everything back in place by its own rule (an armed hook
on the step that never fires), both served families, tiny sizes on the
CPU. The two runs differ in nothing but the depth."""

import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu import profiler
from paddle2_tpu.models import GPTForCausalLM, gpt_tiny
from paddle2_tpu.serving.scheduler import SeqState
from paddle2_tpu.serving.spec import SpeculativeConfig
from served import (FAMILIES, NEVER, armed, assert_same, both,  # noqa: F401
                    drive, engine_of, models, own_programs,
                    prompts_of, shared_programs, step_by_step)

pytestmark = pytest.mark.usefixtures("shared_programs")


def streams(engine, rids):
    return ([list(engine.sequence(r).generated) for r in rids],
            [engine.routed_experts(r) for r in rids])


def drain(engine, now=50.0):
    while not engine.idle():
        engine.tick(now)
        now += 1.0
        assert now < 400, "engine did not drain"


# -- the same tokens and experts, request by request ------------------------
@pytest.mark.parametrize("family", FAMILIES)
def test_mixed_admissions_and_steps_equal_the_read_in_place_order(
        models, family):
    """Admissions before, between and beside decode steps, two in one
    round, requests of one and two tokens among them."""
    model = models[family]
    p = prompts_of(model, (11, 7, 13, 9, 16, 5), seed=20)
    arrivals = [(0, p[0], 9), (0, p[1], 2), (1, p[2], 6), (3, p[3], 1),
                (4, p[4], 5), (7, p[5], 4)]
    ahead, got, plain, want = both(model, arrivals)
    assert_same(got, want)
    assert ahead.prefill_ahead == len(arrivals) and plain.prefill_ahead == 0
    assert ahead.ahead_dropped == 0 and not ahead._firsts


@pytest.mark.parametrize("family", FAMILIES)
def test_a_whole_batch_admitted_before_the_first_step(models, family):
    """The benchmark's warm-up admits as many sequences as there are
    slots before its first decode step: as many first tokens in flight,
    each in a row of its own."""
    model = models[family]
    p = prompts_of(model, (9, 12, 10, 14), seed=21)

    def run(engine):
        rids = [engine.submit(q, 3) for q in p]
        while len(engine.scheduler.running()) < 4:
            assert engine.admit_and_prefill(0.0)
        in_flight = [(f.row, f.seq.req_id) for f in engine._firsts]
        drain(engine, 1.0)
        return in_flight, streams(engine, rids)

    ahead = engine_of(model, prefill_budget_tokens=16)
    in_flight, got = run(ahead)
    assert in_flight == [(0, 0), (1, 1), (2, 2), (3, 3)]
    with step_by_step():
        nothing, want = run(engine_of(model, prefill_budget_tokens=16))
    assert nothing == []
    assert_same(got, want)
    assert ahead.prefill_ahead == 4 and ahead.num_decode_programs == 1


# -- the call's contract ------------------------------------------------------
@pytest.mark.parametrize("family", FAMILIES)
def test_the_token_is_delivered_after_the_step_that_consumes_it(
        models, family, monkeypatch):
    model = models[family]
    engine = engine_of(model)
    rid = engine.submit(prompts_of(model, (10,), seed=22)[0], 3)
    seq = engine.sequence(rid)
    calls = []          # the runner's enqueues and read-backs, in order
    for name in ("prefill_dispatch", "decode", "split_counts"):
        def tapped(*args, _name=name, _real=getattr(engine.runner, name)):
            calls.append(_name)
            return _real(*args)
        monkeypatch.setattr(engine.runner, name, tapped)
    infos = engine.admit_and_prefill(0.0)
    assert calls == ["prefill_dispatch"]            # nothing read back
    assert [i["seq"] for i in infos] == [seq] and infos[0]["prompt_tokens"] == 10
    # running, stamped and counted by lengths alone; nothing read back
    assert seq.state is SeqState.RUNNING and seq.first_token_t == 0.0
    assert seq.num_cached == 10 and seq.generated == []
    assert len(engine._firsts) == 1 and not engine.idle()
    routed = engine.routed_experts(rid)
    assert routed is None or len(routed) == 0
    info = engine.decode_once(0.0)
    assert calls == ["prefill_dispatch", "decode", "split_counts"]
    # the step went out with the token taken on the device: row 0 of
    # cache.firsts, which lies behind the rows of cache.tokens
    step = engine._ahead
    assert info["dispatched"] and info["tokens"] == 0 and step is not None
    assert step.arrays[0][0, 0] == -1 - engine.cache.tokens.shape[0]
    assert step.arrays[1][0] == 10
    assert len(seq.generated) == 1 and not engine._firsts
    if routed is not None:      # the prompt's experts came with the token
        assert len(engine.routed_experts(rid)) == 10
    engine.decode_once(1.0)
    assert len(seq.generated) == 2
    drain(engine)
    assert engine.prefill_ahead == 1 and engine.ahead_dropped == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_a_request_of_one_token_finishes_at_delivery(models, family):
    model = models[family]
    engine = engine_of(model)
    rid = engine.submit(prompts_of(model, (12,), seed=23)[0], 1)
    seq = engine.sequence(rid)
    engine.admit_and_prefill(2.0)
    assert not seq.done and not engine.idle()
    info = engine.decode_once(3.0)          # no step: delivers only
    assert info is not None and not info["dispatched"]
    assert info["tokens"] == 0 and info["n_active"] == 0
    assert seq.done and seq.state is SeqState.FINISHED
    assert seq.finish_t == seq.ready_at == 2.0
    assert engine.idle() and engine.decode_once(4.0) is None
    assert engine.decode_steps == 0 and engine.prefill_ahead == 1
    with step_by_step():
        plain = engine_of(model)
        want = drive(plain, [(0, seq.request.prompt, 1)])
    assert_same(streams(engine, [rid]), want)


def test_a_sequence_held_by_its_lane_is_delivered_all_the_same(models):
    """``ready_at`` in the future (the modeled lanes): not selected, its
    token still reaches the log with the next call."""
    model = models["gpt"]
    engine = engine_of(model)
    rid = engine.submit(prompts_of(model, (9,), seed=24)[0], 4)
    engine.admit_and_prefill(0.0, ready_at_fn=lambda info: 5.0)
    info = engine.decode_once(0.0)
    assert not info["dispatched"] and engine._ahead is None
    assert len(engine.sequence(rid).generated) == 1
    assert engine.decode_once(1.0) is None      # nothing ready, nothing held
    assert engine.decode_once(5.0)["dispatched"]


# -- depth follows from what the engine observes -----------------------------
def test_speculation_reads_in_place(models):
    model = models["gpt"]
    engine = engine_of(model, batch_buckets=None, page_buckets=None,
                       spec=SpeculativeConfig(num_draft_tokens=2))
    rid = engine.submit(prompts_of(model, (10,), seed=25)[0], 6)
    engine.admit_and_prefill(0.0)
    assert len(engine.sequence(rid).generated) == 1 and not engine._firsts
    drain(engine, 1.0)
    assert engine.prefill_ahead == 0 and engine.ahead_steps == 0


@pytest.mark.parametrize("hook", [NEVER, "kill_engine:1000000000"])
def test_an_armed_step_hook_reads_in_place(models, hook):
    model = models["gpt"]
    engine = engine_of(model)
    rid = engine.submit(prompts_of(model, (10,), seed=26)[0], 4)
    with armed(hook):
        engine.admit_and_prefill(0.0)
        assert len(engine.sequence(rid).generated) == 1
        assert not engine._firsts
        drain(engine, 1.0)
    assert engine.prefill_ahead == 0 and engine.ahead_steps == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_a_hook_armed_with_a_first_token_in_flight(models, family):
    """Armed between the admission and the next call: that call reads
    the token before it selects, and its step in its own call."""
    model = models[family]
    p = prompts_of(model, (10, 13), seed=27)
    engine = engine_of(model)
    rids = [engine.submit(q, 5) for q in p]
    engine.admit_and_prefill(0.0)
    assert len(engine._firsts) == 2
    with step_by_step():
        info = engine.decode_once(0.0)
        assert info["dispatched"] and info["tokens"] == 2
        assert engine._ahead is None and not engine._firsts
        assert [len(engine.sequence(r).generated) for r in rids] == [2, 2]
        drain(engine, 1.0)
        want = drive(engine_of(model), [(0, q, 5) for q in p])
    assert_same(streams(engine, rids), want)


# -- a first token in flight is dropped and computed again, exactly ----------
@pytest.mark.parametrize("family", FAMILIES)
def test_eviction_with_the_first_token_in_flight(models, family):
    """Nine usable blocks of 8: the older sequence's next block can only
    come from the one just admitted (the newest is the victim), whose
    first token nobody has read."""
    model = models[family]
    p = prompts_of(model, (23, 40), seed=28)

    def run(engine):
        a = engine.submit(p[0], 12)
        engine.tick(0.0)
        b = engine.submit(p[1], 6)
        engine.admit_and_prefill(1.0)
        victim = engine.sequence(b)
        assert victim.state is SeqState.RUNNING
        held = (len(engine._firsts), len(victim.generated))
        dropped = engine.ahead_dropped
        engine.decode_once(1.0)
        assert victim.state is SeqState.WAITING and victim.evictions == 1
        assert not engine._firsts
        fell = engine.ahead_dropped - dropped
        drain(engine, 2.0)
        return held, fell, streams(engine, [a, b])

    ahead = engine_of(model, num_blocks=10)
    held, fell, got = run(ahead)
    # the token was in flight, fell with the eviction, and is not in
    # the log the re-prefill starts from
    assert held == (1, 0) and fell == 1
    with step_by_step():
        plain_held, plain_fell, want = run(engine_of(model, num_blocks=10))
    assert plain_held == (0, 1) and plain_fell == 0
    assert_same(got, want)


@pytest.mark.parametrize("family", FAMILIES)
def test_corrupt_table_requeue_with_the_first_token_in_flight(
        models, family):
    """The second selection scribbles the table of the sequence admitted
    just before it (chaos): requeued, its first token dropped."""
    model = models[family]
    p = prompts_of(model, (10, 12), seed=29)
    ahead, got, plain, want = both(model, [(0, p[0], 8), (1, p[1], 8)],
                                   also="corrupt_block_table:2:1")
    assert ahead.ahead_dropped == 1 and ahead.prefill_ahead == 3
    for e in (ahead, plain):
        assert [s.recoveries for s in e.scheduler.finished
                if s.req_id == 1] == [1]
    assert_same(got, want)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_killed_engine_drops_the_first_tokens_in_flight(models, family):
    model = models[family]
    p = prompts_of(model, (10, 13), seed=30)
    arrivals = [(0, p[0], 6), (0, p[1], 6)]
    dead = engine_of(model)
    for _, q, n in arrivals:
        dead.submit(q, n)
    dead.admit_and_prefill(0.0)
    assert len(dead._firsts) == 2
    dead.fail("test kill", now=1.0)
    assert not dead._firsts and dead.ahead_dropped == 2
    seqs = dead.recover_inflight()
    assert [len(s.generated) for s in seqs] == [0, 0]
    heir = engine_of(model)
    rids = [heir.adopt(s, now=2.0) for s in seqs]
    drain(heir, 3.0)
    with step_by_step():
        want = drive(engine_of(model), arrivals)
    assert_same(streams(heir, rids), want)


def test_swap_weights_delivers_the_first_token_first(models):
    """The prefill ran with the old weights: its token is in the log
    before the swap is stamped, and no step has consumed it yet."""
    model = models["gpt"]
    paddle.seed(1)
    other = GPTForCausalLM(gpt_tiny(use_scan=False))
    p = prompts_of(model, (10, 13), seed=31)

    def run(engine):
        rids = [engine.submit(q, 6) for q in p]
        engine.admit_and_prefill(0.0)
        engine.swap_weights(other, now=1.0)
        assert engine._ahead is None and not engine._firsts
        delivered = [len(engine.sequence(r).generated) for r in rids]
        drain(engine, 2.0)
        return delivered, [engine.sequence(r).generated for r in rids]

    ahead = engine_of(model)
    got = run(ahead)
    with step_by_step():
        want = run(engine_of(model))
    assert got == want and got[0] == [1, 1]
    assert ahead.prefill_ahead == 2 and ahead.ahead_dropped == 0
    old = drive(engine_of(model), [(0, q, 6) for q in p])[0]
    assert [g[0] for g in got[1]] == [g[0] for g in old]    # old weights'
    assert got[1] != old                                    # then the new


# -- no program beyond the grid, nothing built after the warm-up -------------
@pytest.mark.parametrize("family", FAMILIES)
def test_nothing_is_built_after_the_warm_up(models, family, caplog,
                                            own_programs):
    """The warm-up's shape (a full batch, two tokens each) reaches every
    program a window of mixed iterations calls: no build record, no
    compilation of a ``p2t_`` program, the same program counts as the
    engine that reads in place. (Two rounds: the pools are committed
    arrays only once a decode step has returned them, and a scatter
    program that met the first kind compiles for the second.)"""
    import jax
    model = models[family]
    lengths = (9, 12, 10, 14)           # all pad to 16
    warm_up = [(0, q, 2) for q in prompts_of(model, lengths, seed=32)]
    engine = engine_of(model)
    drive(engine, warm_up)
    drive(engine, warm_up)
    assert engine.num_decode_programs == 1 and engine.decode_steps == 2
    programs = (engine.num_decode_programs,
                len(engine.runner._prefill_programs))
    built = len(profiler.builds())
    rng = np.random.default_rng(33)
    arrivals = [(int(t), q, int(rng.integers(1, 7))) for t, q in zip(
        sorted(rng.integers(0, 20, 12)),
        prompts_of(model, lengths * 3, seed=34))]

    def canary(x):
        return x * 3 + 1

    with jax.log_compiles(), caplog.at_level("WARNING"):
        got = drive(engine, arrivals)
        jax.jit(canary)(np.ones(7, np.float32))
    compiled = [r.getMessage() for r in caplog.records
                if "Compiling" in r.getMessage()]
    assert [m for m in compiled if "canary" in m]   # the log does see them
    assert not [m for m in compiled if "p2t_" in m]
    assert len(profiler.builds()) == built
    assert (engine.num_decode_programs,
            len(engine.runner._prefill_programs)) == programs
    assert engine.prefill_ahead == 8 + len(arrivals)
    plain = engine_of(model)
    with step_by_step():
        drive(plain, warm_up)
        want = drive(plain, arrivals)
    assert_same(got, want)
    assert (plain.num_decode_programs,
            len(plain.runner._prefill_programs)) == programs
