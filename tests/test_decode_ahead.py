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

import pytest

from served import (FAMILIES, assert_same, both, models,  # noqa: F401
                    prompts_of, shared_programs)

pytestmark = pytest.mark.usefixtures("shared_programs")


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
