"""Falcon-H1 served: the per-sequence states — a prefill's state is the state
at the last real position, prefill + decode is a longer prefill, a dropped
step's rows are re-prefilled (moved from ``test_falcon_h1.py``; harness:
``served.py``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle2_tpu.distributed.fault_tolerance import chaos
from paddle2_tpu.serving.model_runner import PagedRunner
from served import (LOGIT_TOL, STATE_TOL, build, run_to_idle,  # noqa: F401
                    shared_programs, tiny_engine)
from served import falcon_h1_bench as bench

pytestmark = pytest.mark.usefixtures("shared_programs")


def test_prefill_state_is_the_state_at_the_last_real_position(bench):
    """A 21-token prompt is padded to 32: the states handed to the slot
    are those of an unpadded pass over the 21 tokens (the padded END
    would read otherwise: the control of the same name)."""
    model, _, _ = build(bench, 7)
    runner = PagedRunner(model, interpret=True)
    ids = np.random.default_rng(7).integers(1, 503, 21).tolist()
    _, _, _, conv, ssm_state = runner.prefill(ids)
    with runner.bound():
        _, _, states = model.model.full(jnp.asarray([ids], jnp.int32))
    for li, (xbc, H) in enumerate(states):
        assert float(jnp.abs(ssm_state[li] - H[0]).max()) <= STATE_TOL
        assert float(jnp.abs(conv[li] - xbc[0, -3:]).max()) <= STATE_TOL
    with runner.bound():
        padded = jnp.asarray([ids + [0] * 11], jnp.int32)
        _, _, at_end = model.model.full(padded)
    assert float(jnp.abs(at_end[0][1][0] - ssm_state[0]).max()) \
        > 100 * STATE_TOL


@pytest.mark.parametrize("n,m", [(5, 6), (16, 3), (23, 9)])
def test_prefill_plus_decode_is_a_longer_prefill(bench, n, m):
    """A prefill of n tokens + m decode steps leaves the slot's states,
    and yields the tokens, of a prefill of n + m tokens."""
    model, _, _ = build(bench, 8)
    prompt = np.random.default_rng(n).integers(1, 503, n).tolist()
    engine = tiny_engine(model, max_batch=1)
    rid = engine.submit(prompt, m + 1)
    now = 0.0
    while len(engine.sequence(rid).generated) < m + 1:
        now += 1.0
        engine.tick(now)
        if engine.sequence(rid).done:
            break
    gen = list(engine.sequence(rid).generated)
    # the slot after m decode steps (the last token is not fed)
    conv = np.asarray(engine.cache.states["conv"][:, 1])
    ssm_state = np.asarray(engine.cache.states["ssm"][:, 1])
    runner = PagedRunner(model, interpret=True)
    first, _, _, conv2, ssm2 = runner.prefill(prompt + gen[:m])
    assert first == gen[m]
    assert np.abs(conv - np.asarray(conv2)).max() <= STATE_TOL
    assert np.abs(ssm_state - np.asarray(ssm2)).max() <= STATE_TOL


@pytest.mark.parametrize("fault", ["drop_decode_step:2",
                                   "drop_decode_step:3,drop_decode_step:5"])
def test_dropped_step_leaves_the_served_tokens(bench, fault, monkeypatch):
    """ROADMAP D13: a discarded step has already moved the states its
    repeat would read. Its rows are re-prefilled, and the served tokens
    are those of an undisturbed run."""
    model, _, _ = build(bench, 10)
    rng = np.random.default_rng(10)
    prompts = [rng.integers(1, 503, n).tolist() for n in (9, 14, 20)]
    want = run_to_idle(tiny_engine(model), prompts, 10)
    monkeypatch.setattr(chaos, "_ACTIVE", chaos.ChaosInjector(fault))
    engine = tiny_engine(model)
    got = run_to_idle(engine, prompts, 10)
    assert engine.state_reprefills >= 3
    assert got == want
    assert engine.allocator.state_slots_used == 0


def test_repeating_a_step_on_a_moved_state_would_differ(bench):
    """The control of the test above: the same step run twice over the
    pools gives other logits the second time (the first moved the
    states), so a plain repeat is not a repair."""
    model, _, _ = build(bench, 10)
    engine = tiny_engine(model, max_batch=1)
    prompt = np.random.default_rng(10).integers(1, 503, 9).tolist()
    rid = engine.submit(prompt, 4)
    engine.admit_and_prefill(0.0)
    seq = engine.sequence(rid)
    fam, cache = engine.runner.family, engine.cache
    args = (jnp.asarray([[seq.tokens[-1]]], jnp.int32),
            jnp.asarray([len(prompt)], jnp.int32),
            jnp.asarray([seq.table.padded(2)], jnp.int32),
            jnp.asarray([seq.table.state_slot], jnp.int32), 8, True, None)
    with engine.runner.bound():
        lg1, k, v, pools, _ = fam.decode(
            cache.k, cache.v, tuple(cache.states.values()), *args)
        lg2, *_ = fam.decode(k, v, pools, *args)
    assert float(jnp.abs(lg1 - lg2).max()) > 100 * LOGIT_TOL
