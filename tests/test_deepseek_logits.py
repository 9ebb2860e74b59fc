"""DeepSeek-V2 served: every step's logits — the expanded prefill's and the
ABSORBED decode's through the latent cache — against the benchmark's plain
reference (moved from ``test_deepseek.py``, which states the tolerances;
harness: ``served.py``)."""

import numpy as np
import pytest

from paddle2_tpu.serving.block_cache import audit_kv_ledger
from served import (LOGIT_TOL, build, check_against_reference,  # noqa: F401
                    logit_tap, ref_logits, serve, shared_programs,
                    tiny_engine)
from served import deepseek_bench as bench

pytestmark = pytest.mark.usefixtures("shared_programs")


# ----------------------------------------------------- the serving plane
def test_prefill_then_paged_decode_logits(bench, logit_tap):
    """Prompts that are no multiples of 16 (nor of the block size 8),
    three sequences in one batch: every step's logits — the expanded
    prefill's and the ABSORBED decode's through the latent cache —
    against the reference's full expanded forward over prompt +
    generated."""
    model, _, params = build(bench, 5)
    engine = tiny_engine(model)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 503, n).tolist() for n in (5, 21, 37)]
    rids, rows = serve(engine, prompts, 7, logit_tap)
    check_against_reference(bench, params, engine, rids, rows)
    assert engine.allocator.used_count == 0
    audit_kv_ledger(engine.allocator, [])


def test_eviction_and_readmission_give_same_logits(bench, logit_tap):
    model, _, params = build(bench, 6)
    engine = tiny_engine(model, num_blocks=10)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 503, n).tolist() for n in (27, 30)]
    rids, rows = serve(engine, prompts, 12, logit_tap)
    assert engine.scheduler.total_evictions >= 1
    for rid in rids:        # a re-prefill recomputes the evicted tail
        gen = engine.sequence(rid).generated
        rows[rid] = rows[rid][-len(gen):] if len(rows[rid]) > len(gen) \
            else rows[rid]
    for rid in rids:
        seq = engine.sequence(rid)
        ref = ref_logits(bench, params, seq.tokens)
        # the last row of every request was computed once, at decode
        got = rows[rid][-1]
        assert float(np.abs(got - ref[len(seq.tokens) - 2]).max()) \
            <= LOGIT_TOL
