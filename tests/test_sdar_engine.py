"""SDAR-MoE served: a mask id in a prompt, an eviction inside a block, and
the engine's contract for the family (moved from ``test_sdar.py``; the
generation cases: ``test_sdar_generation.py``; harness: ``served.py``)."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu import inference
from paddle2_tpu.models import SdarMoeForCausalLM
from paddle2_tpu.serving import EngineConfig, ServingEngine
from paddle2_tpu.serving.spec import SpeculativeConfig
from served import (TINY_ENGINE, build_as_read, generate_both,  # noqa: F401
                    run_to_idle, shared_programs, tiny_engine)
from served import sdar_bench as bench

VOCAB = 503
pytestmark = pytest.mark.usefixtures("shared_programs")


def test_mask_id_in_a_prompt_is_a_token(bench):
    """Masked-ness is a bit, never ``id == mask_token_id``: a prompt may
    hold the id, also among the tokens that open its first block."""
    model, cfg, params = build_as_read(bench, 51)
    engine = tiny_engine(model, denoising_steps=2)
    m = cfg["mask_token_id"]
    prompts = [[9, m, 4, 4, 17, m], [m] * 7]
    generate_both(bench, cfg, params, engine, prompts, [6, 5], 2)


def test_eviction_mid_block_recomputes_exactly(bench):
    """A pool too small for the batch: sequences are evicted inside a
    block, the block is thrown away, and the re-prefill from the
    committed log recomputes it: the reference's tokens and passes."""
    model, cfg, params = build_as_read(bench, 7)
    engine = tiny_engine(model, num_blocks=12, max_batch=3,
                         denoising_steps=2)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (19, 23, 26)]
    generate_both(bench, cfg, params, engine, prompts, [14, 13, 12], 2)
    assert engine.scheduler.total_evictions > 0
    assert engine.allocator.used_count == 0


# ------------------------------------------------------ engine contract
@pytest.mark.parametrize("feature", [
    dict(weight_only_int8=True), dict(weight_only_lm_head=True),
    dict(spec=SpeculativeConfig(num_draft_tokens=2)),
    dict(enable_prefix_cache=True, enable_kv_spill=True)])
def test_engine_refuses_what_the_family_lacks(bench, feature):
    model, _, _ = build_as_read(bench, 11)
    with pytest.raises(ValueError, match="not served with"):
        tiny_engine(model, **feature)


def test_engine_refuses_schedules_it_cannot_run(bench):
    model, _, _ = build_as_read(bench, 11)
    with pytest.raises(ValueError, match="must divide the block length"):
        tiny_engine(model, denoising_steps=3)
    with pytest.raises(ValueError, match="is not served"):
        tiny_engine(model, unmask_strategy="low_confidence_dynamic")
    with pytest.raises(ValueError, match="must divide the cache's"):
        tiny_engine(model, block_size=6)
    from paddle2_tpu.models import GPTForCausalLM, gpt_tiny
    with pytest.raises(ValueError, match="denoising_steps does not apply"):
        ServingEngine(GPTForCausalLM(gpt_tiny(use_scan=False)),
                      config=EngineConfig(denoising_steps=2, interpret=True))
    # whole blocks must fit the model's length
    engine = tiny_engine(model, max_model_len=30)
    from paddle2_tpu.serving.reliability import PromptTooLongError
    with pytest.raises(PromptTooLongError, match="whole blocks"):
        engine.submit(list(range(1, 26)), 5)       # 30 tokens, 32 slots
    engine.submit(list(range(1, 26)), 3)


def test_artifact_path_serves_the_family(bench, tmp_path):
    """jit.save -> inference.Config -> create_serving_engine(gpt_config=
    <SdarMoeConfig>): the tokens of the live-model engine."""
    model, _, _ = build_as_read(bench, 12)
    prompt = np.random.default_rng(12).integers(1, VOCAB, 13).tolist()
    live = tiny_engine(model, denoising_steps=2)
    rid = live.submit(prompt, 6)
    run_to_idle(live)
    path = str(tmp_path / "model")
    paddle.jit.save(model, path)
    conf = inference.Config(path)
    conf.enable_continuous_batching(**TINY_ENGINE, denoising_steps=2)
    engine = conf.create_serving_engine(gpt_config=model.cfg)
    assert isinstance(engine.model, SdarMoeForCausalLM)
    rid2 = engine.submit(prompt, 6)
    run_to_idle(engine)
    assert engine.sequence(rid2).generated == live.sequence(rid).generated


def test_record_holds_the_experts_of_every_row(bench):
    """Prefill rows (``routed_experts``) and the B rows of every pass,
    commits included: the float32 reference's own choice on the same
    inputs."""
    model, cfg, params = build_as_read(bench, 17)
    engine = tiny_engine(model, denoising_steps=2)
    prompt = np.random.default_rng(17).integers(1, VOCAB, 14).tolist()
    rid = engine.submit(prompt, 8)
    run_to_idle(engine)
    seq = engine.sequence(rid)
    L, k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    assert engine.routed_experts(rid).shape == (12, L, k)
    record = engine.block_passes(rid)
    assert [r[3] for r in record] == [False, True] + [False, False,
                                                      True] * 2
    assert all(r[2].shape == (4, L, k) for r in record)
    # the commits' rows and the prefill's, against one clean forward
    ids = jnp.asarray([seq.tokens[:12] + sum(
        (r[1].tolist() for r in record if r[3]), [])], jnp.int32)
    _, used, _ = bench["ref"].forward(params, ids, cfg)
    chosen = np.concatenate([engine.routed_experts(rid)]
                            + [r[2] for r in record if r[3]])
    np.testing.assert_array_equal(np.sort(chosen, -1),
                                  np.sort(np.asarray(used[0]), -1))
