"""Nemotron-H served: the engine's contract for the family — the experts
the served path chose, what the pools count, refusals, the model's own
argmax, the artifact path (moved from ``test_nemotron_h.py``; harness:
``served.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.models import NemotronHForCausalLM, nemotron_h_tiny
from paddle2_tpu.serving.block_cache import audit_kv_ledger
from paddle2_tpu.serving.spec import SpeculativeConfig
from served import (TINY_ENGINE, build, run_to_idle,  # noqa: F401
                    shared_programs, tiny_engine)
from served import nemotron_h_bench as bench, NEMOTRON_PATTERN as PATTERN

pytestmark = pytest.mark.usefixtures("shared_programs")


def test_served_experts_are_the_references_choice(bench):
    """``engine.routed_experts``: per fed token and expert layer the
    experts the served path chose — the reference's own top k (deficit 0
    in its biased scores) in float32."""
    model, _, params = build(bench, 12)
    engine = tiny_engine(model)
    prompt = np.random.default_rng(12).integers(1, 503, 19).tolist()
    rid = engine.submit(prompt, 6)
    now = 0.0
    while not engine.idle():
        now += 1.0
        engine.tick(now)
    routed = engine.routed_experts(rid)
    seq = prompt + list(engine.sequence(rid).generated)
    n = len(seq) - 1
    assert routed.shape == (n, PATTERN.count("E"), 2)
    ref, cfg = bench["ref"], bench["cfg"]
    with jax.default_matmul_precision("highest"):
        _, _, deficit = ref.forward(
            params, jnp.asarray([seq[:n]], jnp.int32), cfg,
            forced=jnp.asarray(routed[None]))
    assert float(deficit.max()) <= 1e-6


# ------------------------------------------------ what the pools count
@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16"])
def test_pools_count_three_different_sets_of_layers(kv_dtype):
    """State pools count the ``M`` layers, the K/V pools the ``*``
    layers, the routing record the ``E`` layers; ``conv`` in the cache's
    dtype, ``ssm`` float32 whatever it is; the ledger closes."""
    paddle.seed(0)
    mcfg = nemotron_h_tiny()
    assert mcfg.hybrid_override_pattern == "MEM*EME"
    model = NemotronHForCausalLM(mcfg)
    model.eval()
    engine = tiny_engine(model, max_batch=2, kv_dtype=kv_dtype)
    cache, alloc, family = engine.cache, engine.allocator, \
        engine.runner.family
    assert family.layer_counts == {"ssm_layers": 3, "attn_layers": 1,
                                   "moe_layers": 3}
    assert family.routed == (3, 2)
    assert cache.k.shape[0] == cache.v.shape[0] == 1
    assert cache.k.shape[-1] == 2 * 16
    assert list(cache.states) == ["conv", "ssm"]
    assert cache.states["conv"].shape == (3, 3, 3, mcfg.conv_dim)
    assert cache.states["conv"].dtype == jnp.dtype(kv_dtype)
    assert cache.states["ssm"].shape == (3, 3, 4, 16, 16)
    assert cache.states["ssm"].dtype == jnp.float32
    assert cache.state_slot_bytes == 3 * (
        3 * mcfg.conv_dim * jnp.dtype(kv_dtype).itemsize + 4 * 16 * 16 * 4)
    rid = engine.submit([5, 6, 7], 3)
    engine.admit_and_prefill(0.0)
    slot = engine.sequence(rid).table.state_slot
    census = audit_kv_ledger(
        alloc, [engine.sequence(rid).table.blocks],
        live_state_slots=[slot], state_pools=cache.states)
    assert census["state_kinds"] == 2 and census["state_slots_claimed"] == 1
    now = 0.0
    while not engine.idle():
        now += 1.0
        engine.tick(now)
    assert engine.routed_experts(rid).shape == (3 + 3 - 1, 3, 2)


@pytest.mark.parametrize("feature", [
    dict(weight_only_int8=True), dict(weight_only_lm_head=True),
    dict(spec=SpeculativeConfig(num_draft_tokens=2)),
    dict(enable_prefix_cache=True, enable_kv_spill=True)])
def test_engine_refuses_what_the_family_lacks(feature):
    paddle.seed(0)
    model = NemotronHForCausalLM(nemotron_h_tiny())
    with pytest.raises(ValueError, match="not served with"):
        tiny_engine(model, **feature)


def test_served_tokens_are_the_models_own_argmax():
    """No reference weights: the tiny preset served through the engine
    yields the argmax of the model's own full forward over prompt +
    stream."""
    paddle.seed(5)
    model = NemotronHForCausalLM(nemotron_h_tiny())
    model.eval()
    prompt = np.random.default_rng(5).integers(1, 503, 21).tolist()
    (gen,) = run_to_idle(tiny_engine(model), [prompt], 6)
    lg = np.asarray(model(paddle.to_tensor(
        np.asarray([prompt + gen], np.int32)))._data)[0]
    assert gen == [int(lg[len(prompt) - 1 + i].argmax())
                   for i in range(len(gen))]


def test_artifact_path_serves_the_family(bench, tmp_path):
    """jit.save -> inference.Config -> create_serving_engine: the tokens
    of the live-model engine."""
    from paddle2_tpu import inference
    model, mcfg, _ = build(bench, 14)
    prompt = np.random.default_rng(14).integers(1, 503, 13).tolist()
    want = run_to_idle(tiny_engine(model), [prompt], 5)
    path = str(tmp_path / "model")
    paddle.jit.save(model, path)
    conf = inference.Config(path)
    conf.enable_continuous_batching(**TINY_ENGINE)
    engine = conf.create_serving_engine(gpt_config=mcfg)
    assert isinstance(engine.model, NemotronHForCausalLM)
    assert run_to_idle(engine, [prompt], 5) == want
