"""EXAONE-MoE against the benchmark's plain reference
(``benchmark/reference/exaone_moe.py``: float32, no cache, the dense
``[S, S]`` band mask): the model's full forward, then every step's
logits through prefill and decode over the two kinds of cache — the
global layer's pages and the sliding layers' rings — for contexts
shorter than, equal to and several times the window (8 here), the ring's
wrap included. A sliding layer given the whole history must FAIL.
Harness: ``served.py``."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.serving.block_cache import audit_kv_ledger
from served import (LOGIT_TOL, build, check_against_reference,  # noqa: F401
                    logit_tap, ref_logits, serve, shared_programs,
                    tiny_engine)
from served import exaone_moe_bench as bench

pytestmark = pytest.mark.usefixtures("shared_programs")
WINDOW = 8
PAD = 64         # the reference runs at one length: one compile of each op
# one decode program whatever the batch and the context (4 rows x 12
# pages), the engine file's
ONE_PROGRAM = dict(batch_buckets=(4,), page_buckets=(12,))


@pytest.mark.parametrize("length", [5, WINDOW, 29])
def test_full_forward_logits(bench, length):
    """Shorter than the window, the window, three windows and a half
    (no multiple of it: the band's chunks are padded)."""
    assert bench["cfg"]["sliding_window"] == WINDOW
    model, _, params = build(bench, 3)
    seq = np.random.default_rng(length).integers(1, 503, length).tolist()
    got = np.asarray(model(paddle.Tensor(
        jnp.asarray([seq], jnp.int32)))._data[0])
    assert np.abs(got - ref_logits(bench, params, seq, pad_to=PAD)).max() \
        <= LOGIT_TOL


def test_prefill_then_ring_and_paged_decode_logits(bench, logit_tap):
    """Four sequences in one batch whose contexts end below the window,
    cross it while decoding (the ring fills, then wraps) and start
    several windows long (the prefill hands over the last 8 keys and
    values, each at its row): every step's logits against the reference's
    full forward over prompt + generated."""
    model, _, params = build(bench, 5)
    engine = tiny_engine(model, **ONE_PROGRAM)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 503, n).tolist() for n in (2, 5, 21, 37)]
    rids, rows = serve(engine, prompts, 12, logit_tap)
    check_against_reference(bench, params, engine, rids, rows, pad_to=PAD)
    # blocks and slots are back with the manager
    assert engine.allocator.used_count == 0
    assert engine.allocator.state_slots_used == 0
    audit_kv_ledger(engine.allocator, [], state_pools=engine.cache.states)


def test_a_sliding_layer_given_the_whole_history_fails(bench, logit_tap):
    """The same served rows against a reference whose sliding layers see
    everything: equal while the context is inside the window, far off
    past it."""
    model, _, params = build(bench, 7)
    engine = tiny_engine(model, **ONE_PROGRAM)
    prompt = np.random.default_rng(7).integers(1, 503, 30).tolist()
    (rid,), rows = serve(engine, [prompt], 4, logit_tap)
    seq = prompt + list(engine.sequence(rid).generated)
    whole = dict(bench["cfg"], sliding_window=256)
    ref = ref_logits(bench, params, seq, cfg=whole, pad_to=PAD)
    sound = ref_logits(bench, params, seq, pad_to=PAD)
    assert np.abs(ref[:WINDOW] - sound[:WINDOW]).max() <= LOGIT_TOL
    worst = max(float(np.abs(row - ref[len(prompt) - 1 + j]).max())
                for j, row in enumerate(rows[rid]))
    assert worst > 100 * LOGIT_TOL, worst
    with pytest.raises(AssertionError):
        bench_whole = dict(bench, cfg=whole)
        check_against_reference(bench_whole, params, engine, [rid], rows,
                                pad_to=PAD)
