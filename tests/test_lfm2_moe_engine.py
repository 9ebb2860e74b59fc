"""LFM2-MoE served: the engine's contract for the family — state slots,
refusals, the artifact path, routing records, a dropped step
(moved from ``test_lfm2_moe.py``; harness: ``served.py``)."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu import inference
from paddle2_tpu.models import Lfm2MoeForCausalLM
from paddle2_tpu.serving import EngineConfig, ServingEngine
from paddle2_tpu.serving.block_cache import audit_kv_ledger
from paddle2_tpu.serving.spec import SpeculativeConfig
from served import (TINY_ENGINE, build, shared_programs,  # noqa: F401
                    tiny_engine)
from served import lfm2_bench as bench

pytestmark = pytest.mark.usefixtures("shared_programs")


def test_admission_waits_for_a_state_slot(bench):
    model, _, _ = build(bench, 9)
    engine = tiny_engine(model, max_batch=2)
    alloc = engine.allocator
    assert alloc.state_slots == 2 \
        and engine.cache.states["conv"].shape[1] == 3
    a, b = alloc.take_state_slot(), alloc.take_state_slot()
    assert not alloc.can_admit(1) and alloc.can_allocate(1)
    engine.submit([1, 2, 3], 2)
    assert engine.admit_and_prefill(0.0) == []      # blocks, but no slot
    audit_kv_ledger(alloc, [], live_state_slots=[a, b])
    alloc.free_state_slot(a)
    assert len(engine.admit_and_prefill(1.0)) == 1
    alloc.free_state_slot(b)
    with pytest.raises(ValueError):
        alloc.free_state_slot(b)                    # a double free


@pytest.mark.parametrize("feature", [
    dict(weight_only_int8=True), dict(weight_only_lm_head=True),
    dict(spec=SpeculativeConfig(num_draft_tokens=2)),
    dict(enable_prefix_cache=True, enable_kv_spill=True)])
def test_engine_refuses_what_the_family_lacks(bench, feature):
    model, _, _ = build(bench, 11)
    with pytest.raises(ValueError, match="not served with"):
        tiny_engine(model, **feature)


def test_artifact_path_serves_the_family(bench, tmp_path):
    """jit.save -> inference.Config -> create_serving_engine(gpt_config=
    <Lfm2MoeConfig>): the tokens of the live-model engine."""
    model, mcfg, _ = build(bench, 12)
    prompt = np.random.default_rng(12).integers(1, 503, 13).tolist()
    live = tiny_engine(model)
    rid = live.submit(prompt, 5)
    while not live.idle():
        live.tick(0.0)
    path = str(tmp_path / "model")
    paddle.jit.save(model, path)
    conf = inference.Config(path)
    conf.enable_continuous_batching(**TINY_ENGINE)
    engine = conf.create_serving_engine(gpt_config=mcfg)
    assert isinstance(engine.model, Lfm2MoeForCausalLM)
    rid2 = engine.submit(prompt, 5)
    while not engine.idle():
        engine.tick(0.0)
    assert engine.sequence(rid2).generated == live.sequence(rid).generated


def test_routing_counts_ride_behind_the_tokens(bench):
    model, _, _ = build(bench, 13)
    engine = tiny_engine(model)
    out = engine.runner.prefill_dispatch(list(range(1, 12)))
    tok, counts, chosen = engine.runner.split_counts(out[0], 1)
    assert tok.shape == (1,)
    k = model.cfg.num_experts_per_tok
    # the experts chosen for every (padded) row, per expert layer
    assert chosen.shape == (16, 4, k)
    assert ((0 <= chosen) & (chosen < 8)).all()
    # four expert layers; 11 real tokens routed, the padded tail is not
    assert counts["moe_assignments"] == [11 * k] * 4
    assert all(1 <= h <= 8 for h in counts["moe_experts_hit"])
    assert all(m >= -(-11 * k // 8) for m in counts["moe_load_max"])
    assert engine._count_stats(counts)["moe_assignments"] == 4 * 11 * k


def test_engine_keeps_the_experts_the_served_path_chose(bench):
    """``routed_experts``: one row per token the model was FED (prompt
    and generated but the last), equal to the float32 reference's own
    choice on the same tokens — through prefill, paged decode, and an
    eviction's re-prefill alike."""
    model, _, params = build(bench, 17)
    # 9 blocks of 8: two 30-token sequences grow into each other
    engine = tiny_engine(model, num_blocks=10)
    rng = np.random.default_rng(17)
    rids = [engine.submit(rng.integers(1, 503, n).tolist(), 12)
            for n in (27, 30)]
    now = 0.0
    while not engine.idle():
        now += 1.0
        engine.tick(now)
    assert engine.scheduler.total_evictions >= 1
    for rid in rids:
        seq = engine.sequence(rid)
        chosen = engine.routed_experts(rid)
        assert chosen.shape == (len(seq.tokens) - 1, 4, 2)
        ids = jnp.asarray([seq.tokens[:-1]], jnp.int32)
        _, used, deficit = bench["ref"].forward(params, ids, bench["cfg"])
        np.testing.assert_array_equal(np.sort(chosen, -1),
                                      np.sort(np.asarray(used[0]), -1))
        # handed the program's experts, the reference finds them sound
        _, _, forced_deficit = bench["ref"].forward(
            params, ids, bench["cfg"], forced=jnp.asarray(chosen)[None])
        assert float(forced_deficit.max()) == 0.0
    # a family that routes nothing has no record
    from paddle2_tpu.models import GPTForCausalLM, gpt_tiny
    gpt = ServingEngine(GPTForCausalLM(gpt_tiny(use_scan=False)),
                        config=EngineConfig(block_size=8, num_blocks=16,
                                            max_batch=2, max_model_len=32,
                                            interpret=True))
    rid = gpt.submit([1, 2, 3], 2)
    gpt.tick(0.0)
    assert gpt.routed_experts(rid) is None


def test_dropped_step_rows_are_reprefilled_not_repeated(bench, monkeypatch):
    """ROADMAP D13. A discarded decode step has already shifted the
    convolution state its repeat would read. The seeded tiny model
    serves one token over and over (the tied head finds the input's own
    embedding), which is why its tokens "happen not to move"; with the
    token table at 0.03 and the convolutions' output projections at 16
    times their seeded scale the operator decides the next token, the
    served tokens vary (11-14 distinct of 16) and a repeated step DOES
    move them (this case fails where the step is simply repeated). The
    engine re-prefills the dropped step's rows, as after an eviction,
    and serves the tokens of an undisturbed run."""
    from paddle2_tpu.distributed.fault_tolerance import chaos
    model, _, _ = build(bench, 23, fresh=True)      # its weights are written
    for name, p in model.named_parameters():
        scale = 0.03 if "embed_tokens" in name else \
            16.0 if "conv.out_proj" in name else None
        if scale:
            p.set_value(paddle.Tensor(p._data * scale))
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, 503, n).tolist() for n in (9, 14, 20)]

    def run(engine):
        rids = [engine.submit(p, 16) for p in prompts]
        now = 0.0
        while not engine.idle():
            now += 1.0
            engine.tick(now)
        return [list(engine.sequence(r).generated) for r in rids]

    want = run(tiny_engine(model))
    assert min(len(set(w)) for w in want) > 8
    monkeypatch.setattr(chaos, "_ACTIVE", chaos.ChaosInjector(
        "drop_decode_step:3,drop_decode_step:6,drop_decode_step:9,"
        "drop_decode_step:12"))
    engine = tiny_engine(model)
    got = run(engine)
    assert got == want
    assert engine.state_reprefills >= 4
    assert engine.allocator.state_slots_used == 0
