"""EXAONE-MoE served: the engine's contract for the family — the stream
is the reference's greedy stream, what the pools and the rings count, a
prefix hit, an eviction with re-prefill, a freed slot's ring reused by a
shorter sequence, refusals, the artifact path. Harness: ``served.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.models import ExaoneMoeForCausalLM, exaone_moe_tiny
from paddle2_tpu.serving.block_cache import audit_kv_ledger
from paddle2_tpu.serving.spec import SpeculativeConfig
from served import (TINY_ENGINE, build, ref_logits,  # noqa: F401
                    run_to_idle, shared_programs, tiny_engine)
from served import exaone_moe_bench as bench

pytestmark = pytest.mark.usefixtures("shared_programs")
TIE = 1e-4       # two logits closer than this may part the two streams


def one_program_engine(model, **kw):
    """``tiny_engine`` with ONE decode program whatever the batch and the
    context (4 rows x 12 pages): the module traces it once."""
    return tiny_engine(model, batch_buckets=(4,), page_buckets=(12,), **kw)


def assert_reference_greedy(bench, params, prompt, gen):
    """Every served token is the reference's argmax over prompt +
    stream (or ties with it to within rounding)."""
    ref = ref_logits(bench, params, list(prompt) + list(gen), pad_to=64)
    for j, tok in enumerate(gen):
        row = ref[len(prompt) - 1 + j]
        assert row.max() - row[tok] <= TIE, (j, tok, int(row.argmax()))


def test_engine_stream_is_the_references_greedy_stream(bench):
    """Contexts below the window, across it and several windows long;
    the experts the served path chose are the reference's own top k."""
    model, _, params = build(bench, 12)
    engine = one_program_engine(model)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, 503, n).tolist() for n in (3, 19, 41)]
    gens = run_to_idle(engine, prompts, 10)
    for prompt, gen in zip(prompts, gens):
        assert len(gen) == 10
        assert_reference_greedy(bench, params, prompt, gen)
    rid = 1
    routed = engine.routed_experts(rid)
    seq = prompts[rid] + gens[rid]
    n = len(seq) - 1
    assert routed.shape == (n, 3, 2)
    ref, cfg = bench["ref"], bench["cfg"]
    # at the length the other reference runs have (-1: a row's own top k)
    ids = np.zeros((1, 64), np.int32)
    ids[0, :n] = seq[:n]
    forced = np.full((1, 64) + routed.shape[1:], -1, np.int32)
    forced[0, :n] = routed
    with jax.default_matmul_precision("highest"):
        _, _, deficit = ref.forward(params, jnp.asarray(ids), cfg,
                                    forced=jnp.asarray(forced))
    assert float(deficit[:, :n].max()) <= 1e-6


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16"])
def test_pools_count_the_global_layers_and_rings_the_sliding_ones(kv_dtype):
    """The K/V pools count the ONE global layer; the two ring kinds the
    four sliding layers, ``window`` rows a slot in the cache's dtype; the
    ledger closes."""
    paddle.seed(0)
    model = ExaoneMoeForCausalLM(exaone_moe_tiny())
    model.eval()
    engine = tiny_engine(model, max_batch=2, kv_dtype=kv_dtype)
    cache, family = engine.cache, engine.runner.family
    assert family.window_layers == 4 and family.attn_layers == 1
    assert family.routed == (4, 2)
    assert cache.k.shape[0] == cache.v.shape[0] == 1
    assert list(cache.states) == ["ring_k", "ring_v"]
    for pool in cache.states.values():
        assert pool.shape == (4, 3, 8, 2 * 16)
        assert pool.dtype == jnp.dtype(kv_dtype)
    assert cache.state_slot_bytes \
        == 2 * 4 * 8 * 32 * jnp.dtype(kv_dtype).itemsize
    census = audit_kv_ledger(engine.allocator, [],
                             state_pools=cache.states)
    assert census["state_kinds"] == 2 and census["state_slots_free"] == 2


def test_running_sequences_hold_one_ring_slot_each(bench):
    model, _, _ = build(bench, 12)
    engine = one_program_engine(model)
    engine.submit([5, 6, 7], 8)
    engine.submit([8, 9], 8)
    engine.tick(0.0)
    engine.tick(1.0)
    assert engine.allocator.state_slots_used == 2
    run_to_idle(engine)
    assert engine.allocator.state_slots_used == 0


def test_a_prefix_hit_rebuilds_the_ring(bench):
    """Two prompts share 24 tokens (three blocks): the second's pages
    come from the prefix cache, its prefill still runs whole and builds
    its own ring; both streams are the reference's."""
    model, _, params = build(bench, 15)
    engine = one_program_engine(model, enable_prefix_cache=True)
    rng = np.random.default_rng(15)
    head = rng.integers(1, 503, 24).tolist()
    prompts = [head + rng.integers(1, 503, n).tolist() for n in (5, 9)]
    first = run_to_idle(engine, prompts[:1], 6)
    rid = engine.submit(prompts[1], 6)
    run_to_idle(engine)
    seq = engine.sequence(rid)
    assert seq.prefix_cached_tokens == 24
    for prompt, gen in zip(prompts, first + [list(seq.generated)]):
        assert_reference_greedy(bench, params, prompt, gen)


def test_eviction_and_reprefill_keep_the_stream(bench):
    model, _, params = build(bench, 16)
    rng = np.random.default_rng(16)
    prompts = [rng.integers(1, 503, n).tolist() for n in (19, 23, 27)]
    engine = one_program_engine(model, num_blocks=12, max_batch=3)
    gens = run_to_idle(engine, prompts, 12)
    assert engine.scheduler.total_evictions > 0
    for prompt, gen in zip(prompts, gens):
        assert len(gen) == 12
        assert_reference_greedy(bench, params, prompt, gen)
    assert engine.allocator.state_slots_used == 0


def test_a_freed_slots_ring_is_reused_by_a_shorter_sequence(bench):
    """A sequence several windows long leaves its ring full; a sequence
    of three tokens takes the slot next (the allocator hands out the
    slot freed last). Its prefill hands over a WHOLE ring, zeros where
    it has no position yet, so none of the long one's keys stays; and
    ``min(p + 1, window)`` live rows keep the rest out of sight."""
    model, _, params = build(bench, 17)
    engine = one_program_engine(model)
    rng = np.random.default_rng(17)
    long, short = (rng.integers(1, 503, n).tolist() for n in (40, 3))
    (gen_long,) = run_to_idle(engine, [long], 4)
    stale = np.asarray(engine.cache.states["ring_k"][:, 1])
    assert np.abs(stale).min(axis=-1).min() > 0        # every row written
    (gen_short,) = run_to_idle(engine, [short], 4)
    # positions 0..5 of the short one are in rows 0..5 of the SAME slot
    ring = np.asarray(engine.cache.states["ring_k"][:, 1])
    assert np.abs(ring[:, :6] - stale[:, :6]).min(axis=-1).min() > 0
    assert not ring[:, 6:].any()
    assert_reference_greedy(bench, params, long, gen_long)
    assert_reference_greedy(bench, params, short, gen_short)


@pytest.mark.parametrize("feature", [
    dict(weight_only_int8=True), dict(weight_only_lm_head=True),
    dict(spec=SpeculativeConfig(num_draft_tokens=2)),
    dict(enable_prefix_cache=True, enable_kv_spill=True)])
def test_engine_refuses_what_the_family_lacks(feature):
    paddle.seed(0)
    model = ExaoneMoeForCausalLM(exaone_moe_tiny())
    with pytest.raises(ValueError, match="not served with"):
        tiny_engine(model, **feature)


def test_artifact_path_serves_the_family(bench, tmp_path):
    """jit.save -> inference.Config -> create_serving_engine: the tokens
    of the live-model engine."""
    from paddle2_tpu import inference
    model, mcfg, _ = build(bench, 14)
    prompt = np.random.default_rng(14).integers(1, 503, 13).tolist()
    want = run_to_idle(one_program_engine(model), [prompt], 5)
    path = str(tmp_path / "model")
    paddle.jit.save(model, path)
    conf = inference.Config(path)
    conf.enable_continuous_batching(**dict(
        TINY_ENGINE, batch_buckets=(4,), page_buckets=(12,)))
    engine = conf.create_serving_engine(gpt_config=mcfg)
    assert isinstance(engine.model, ExaoneMoeForCausalLM)
    assert run_to_idle(engine, [prompt], 5) == want
