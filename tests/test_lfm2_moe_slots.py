"""LFM2-MoE served: a state slot that is reused and a prefix hit fill the
conv state as a first use does, every step's logits against the benchmark's
plain reference (moved from ``test_lfm2_moe.py``, which states the
tolerances; harness: ``served.py``)."""

import numpy as np
import pytest

from served import (build, check_against_reference, logit_tap,  # noqa: F401
                    serve, shared_programs, tiny_engine)
from served import lfm2_bench as bench

pytestmark = pytest.mark.usefixtures("shared_programs")


def test_reused_slot_leaks_nothing(bench, logit_tap):
    """One slot, two requests one after the other: the second takes the
    slot the first left (no clear in between) and its logits are the
    reference's."""
    model, _, params = build(bench, 8)
    engine = tiny_engine(model, max_batch=1)
    rng = np.random.default_rng(8)
    first = rng.integers(1, 503, 30).tolist()
    rids, rows = serve(engine, [first], 6, logit_tap)
    state_after_first = np.asarray(engine.cache.states["conv"][:, 1])
    assert np.abs(state_after_first).max() > 0      # stale state is there
    second = rng.integers(1, 503, 3).tolist()
    rids2, rows2 = serve(engine, [second], 6, logit_tap)
    check_against_reference(bench, params, engine, rids + rids2,
                            {**rows, **rows2})


def test_prefix_cache_hit_still_fills_the_state(bench, logit_tap):
    """A prefix hit shares the K/V blocks but runs the whole prefill,
    which is where the conv state comes from."""
    model, _, params = build(bench, 10)
    engine = tiny_engine(model, enable_prefix_cache=True)
    rng = np.random.default_rng(10)
    shared = rng.integers(1, 503, 24).tolist()
    prompts = [shared + rng.integers(1, 503, n).tolist() for n in (3, 6)]
    rids, rows = serve(engine, prompts[:1], 4, logit_tap)
    rids2, rows2 = serve(engine, prompts[1:], 4, logit_tap)
    assert engine.sequence(rids2[0]).prefix_cached_tokens >= 16
    check_against_reference(bench, params, engine, rids + rids2,
                            {**rows, **rows2})
