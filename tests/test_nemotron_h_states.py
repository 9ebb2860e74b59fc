"""Nemotron-H served: the per-sequence states — a prefill's state is the
state at the last real position, prefill + decode is a longer prefill, a
dropped step's rows are re-prefilled (moved from ``test_nemotron_h.py``;
harness: ``served.py``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle2_tpu.distributed.fault_tolerance import chaos
from paddle2_tpu.incubate.moe import DroplessExperts
from paddle2_tpu.serving.model_runner import PagedRunner
from served import (STATE_TOL, build, run_to_idle,  # noqa: F401
                    shared_programs, tiny_engine)
from served import nemotron_h_bench as bench, NEMOTRON_PATTERN as PATTERN

pytestmark = pytest.mark.usefixtures("shared_programs")


def test_prefill_state_is_the_state_at_the_last_real_position(bench):
    """A 21-token prompt is padded to 32: the states handed to the slot
    are those of an unpadded pass over the 21 tokens, and the padded
    tail is not routed."""
    model, _, _ = build(bench, 7)
    runner = PagedRunner(model, interpret=True)
    ids = np.random.default_rng(7).integers(1, 503, 21).tolist()
    _, _, _, conv, ssm_state = runner.prefill(ids)
    with runner.bound():
        _, _, states, records = model.model.full(
            jnp.asarray([ids], jnp.int32), interpret=True)
    assert len(states) == PATTERN.count("M") == conv.shape[0]
    for li, (xbc, H) in enumerate(states):
        assert float(jnp.abs(ssm_state[li] - H[0]).max()) <= STATE_TOL
        assert float(jnp.abs(conv[li] - xbc[0, -3:]).max()) <= STATE_TOL
    with runner.bound():
        padded = jnp.asarray([ids + [0] * 11], jnp.int32)
        _, _, at_end, _ = model.model.full(padded, interpret=True)
        valid = (jnp.arange(32) <= 20)[None]
        _, _, _, routed = model.model.full(padded, valid, interpret=True)
    assert float(jnp.abs(at_end[0][1][0] - ssm_state[0]).max()) \
        > 100 * STATE_TOL
    rows = DroplessExperts.COUNT_NAMES.index("moe_rows")
    assert [int(r[rows]) for r in routed] == [21] * PATTERN.count("E")
    assert [int(r[rows]) for r in records] == [21] * PATTERN.count("E")


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
