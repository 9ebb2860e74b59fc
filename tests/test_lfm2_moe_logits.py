"""LFM2-MoE served: every step's logits through prefill and paged decode
against the benchmark's plain reference (moved from ``test_lfm2_moe.py``,
which states the tolerances; a reused state slot and a prefix hit:
``test_lfm2_moe_slots.py``; harness: ``served.py``)."""

import numpy as np
import pytest

from paddle2_tpu.serving.block_cache import audit_kv_ledger
from served import (build, check_against_reference, logit_tap,  # noqa: F401
                    serve, shared_programs, tiny_engine)
from served import lfm2_bench as bench

pytestmark = pytest.mark.usefixtures("shared_programs")


# ------------------------------------------------- prefill + paged decode
def test_prefill_then_paged_decode_logits(bench, logit_tap):
    """Prompts that are no multiples of 16 (nor of the block size 8),
    three sequences in one batch: every step's logits against the
    reference's full forward over prompt + generated."""
    model, _, params = build(bench, 5)
    engine = tiny_engine(model)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 503, n).tolist() for n in (5, 21, 37)]
    rids, rows = serve(engine, prompts, 7, logit_tap)
    check_against_reference(bench, params, engine, rids, rows)
    # both kinds of state are back with the manager
    assert engine.allocator.used_count == 0
    assert engine.allocator.state_slots_used == 0
    audit_kv_ledger(engine.allocator, [])


def test_single_token_prompt_state_is_zero_padded(bench, logit_tap):
    """A 1-token prompt has no z before it: the slot's older entry is
    the zero that stands before the sequence."""
    model, _, params = build(bench, 6)
    engine = tiny_engine(model)
    rids, rows = serve(engine, [[17]], 5, logit_tap)
    check_against_reference(bench, params, engine, rids, rows)


def test_eviction_and_readmission_give_same_logits(bench, logit_tap):
    """A pool too small for the batch: sequences are evicted (blocks
    AND slot freed) and re-prefilled from their token logs; every
    logits row still matches the reference."""
    model, _, params = build(bench, 7)
    engine = tiny_engine(model, num_blocks=12, max_batch=3)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 503, n).tolist() for n in (19, 23, 27)]
    rids, rows = serve(engine, prompts, 12, logit_tap)
    assert engine.scheduler.total_evictions > 0
    check_against_reference(bench, params, engine, rids, rows)
    assert engine.allocator.state_slots_used == 0
