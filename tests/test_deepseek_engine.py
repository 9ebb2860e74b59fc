"""DeepSeek-V2 served: the engine's contract for the family — the latent
pool, refusals, the artifact path, routing records (moved from
``test_deepseek.py``; harness: ``served.py``)."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu import inference
from paddle2_tpu.incubate.moe import DroplessExperts
from paddle2_tpu.models import DeepseekV2ForCausalLM
from paddle2_tpu.serving import paged_attention as pa
from paddle2_tpu.serving.block_cache import audit_kv_ledger
from served import (TINY_ENGINE, build, run_to_idle,  # noqa: F401
                    shared_programs, tiny_engine)
from served import deepseek_bench as bench

pytestmark = pytest.mark.usefixtures("shared_programs")


def test_cache_holds_one_latent_row_a_token(bench):
    """ONE pool of ``[c | RoPE(k_rope) | zeros]`` rows and no V pool:
    per-head keys and values are never stored; the allocator's bytes
    follow the row; the ledger closes with sequences live and gone."""
    model, mcfg, _ = build(bench, 7)
    engine = tiny_engine(model)
    cache, family = engine.cache, engine.runner.family
    rank, dr = mcfg.kv_lora_rank, mcfg.qk_rope_head_dim
    width = pa.mla_row_width(rank, dr)
    assert (family.kv_widths, family.num_kv_heads, family.head_dim) == \
        ((width, 0), 1, rank + dr)
    assert cache.v is None and cache.k.shape == (3, 64, 8, width)
    assert cache.block_bytes == 3 * 8 * width * 4
    assert pa.mla_row_width(512, 64) == 640          # 1,280 B in bf16
    rid = engine.submit(list(range(1, 20)), 3)
    engine.admit_and_prefill(0.0)
    seq = engine.sequence(rid)
    audit_kv_ledger(engine.allocator, [seq.table.blocks])
    # the rows written: the latent and the rotary key, then zeros
    rows = np.asarray(cache.k[:, seq.table.blocks[0]])
    assert np.abs(rows[..., :rank + dr]).min() > 0
    assert not rows[..., rank + dr:].any()
    run_to_idle(engine)
    assert engine.allocator.used_count == 0
    audit_kv_ledger(engine.allocator, [])


@pytest.mark.parametrize("feature", [
    {"weight_only_int8": True}, {"weight_only_lm_head": True},
    {"spec": "spec"}, {"enable_kv_spill": True, "enable_prefix_cache": True}])
def test_engine_refuses_what_the_family_lacks(bench, feature):
    from paddle2_tpu.serving.spec import SpeculativeConfig
    model, _, _ = build(bench, 8)
    if feature.get("spec"):
        feature = {"spec": SpeculativeConfig(num_draft_tokens=2)}
    with pytest.raises(ValueError, match="not served with"):
        tiny_engine(model, **feature)


def test_artifact_path_serves_the_family(bench, tmp_path):
    """jit.save -> inference.Config -> create_serving_engine(gpt_config=
    <DeepseekV2Config>): the tokens of the live-model engine, with
    run-ahead decode and the deferred first token on."""
    model, mcfg, _ = build(bench, 12)
    prompt = np.random.default_rng(12).integers(1, 503, 13).tolist()
    live = tiny_engine(model)
    rid = live.submit(prompt, 6)
    run_to_idle(live)
    path = str(tmp_path / "model")
    paddle.jit.save(model, path)
    conf = inference.Config(path)
    conf.enable_continuous_batching(**TINY_ENGINE)
    engine = conf.create_serving_engine(gpt_config=mcfg)
    assert isinstance(engine.model, DeepseekV2ForCausalLM)
    rid2 = engine.submit(prompt, 6)
    run_to_idle(engine)
    assert engine.sequence(rid2).generated == live.sequence(rid).generated
    assert engine.ahead_steps > 0 and engine.prefill_ahead > 0


def test_routing_counts_ride_behind_the_tokens(bench):
    model, _, _ = build(bench, 13)
    engine = tiny_engine(model)
    out = engine.runner.prefill_dispatch(list(range(1, 12)))
    tok, counts, chosen = engine.runner.split_counts(out[0], 1)
    assert tok.shape == (1,)
    # two expert layers, the experts chosen for every (padded) row
    assert chosen.shape == (16, 2, 2)
    assert ((0 <= chosen) & (chosen < 8)).all()
    assert set(counts) == set(DroplessExperts.COUNT_NAMES)
    # 11 real tokens routed (the padded tail is not); this chip holds
    # experts 0 and 1 of 8
    assert counts["moe_rows"] == [11, 11]
    here = [int(((chosen[:11, l] < 2).any(-1)).sum()) for l in range(2)]
    assert counts["moe_rows_routed_here"] == here
    assert counts["moe_assignments"] == \
        [int((chosen[:11, l] < 2).sum()) for l in range(2)]
    assert all(h <= 2 for h in counts["moe_experts_hit"])
    stats = engine._count_stats(counts)
    assert stats["moe_rows"] == 22 and \
        stats["moe_rows_routed_here"] == sum(here)


def test_engine_keeps_the_experts_the_served_path_chose(bench):
    """``routed_experts``: one row per token the model was FED, equal to
    the float32 reference's own choice on the same tokens — through the
    expanded prefill and the absorbed decode alike."""
    model, _, params = build(bench, 17)
    engine = tiny_engine(model)
    rng = np.random.default_rng(17)
    rids = [engine.submit(rng.integers(1, 503, n).tolist(), 9)
            for n in (11, 30)]
    run_to_idle(engine)
    for rid in rids:
        seq = engine.sequence(rid)
        chosen = engine.routed_experts(rid)
        assert chosen.shape == (len(seq.tokens) - 1, 2, 2)
        ids = jnp.asarray([seq.tokens[:-1]], jnp.int32)
        _, used, _ = bench["ref"].forward(params, ids, bench["cfg"])
        np.testing.assert_array_equal(np.sort(chosen, -1),
                                      np.sort(np.asarray(used[0]), -1))
        _, _, forced_deficit = bench["ref"].forward(
            params, ids, bench["cfg"], forced=jnp.asarray(chosen)[None])
        assert float(forced_deficit.max()) == 0.0
