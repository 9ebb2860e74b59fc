"""LFM2-MoE (``models/lfm2.py``) against the benchmark's plain reference
(``benchmark/reference/lfm2_moe.py``), tiny sizes, float32 on the CPU,
Pallas kernels interpreted, seeded random weights placed through the
benchmark's own layout (``benchmark/configs/lfm2-24b-a2b.json``).

Tolerances. Model and reference compute the same float32 mathematics in
another order (grouped matmul over sorted rows against a scan over
experts, paged softmax per page block against one row), so logits of
scale ~1 agree to a few 1e-6; ``LOGIT_TOL`` = 5e-5 leaves an order of
magnitude of room and is two orders under what a bf16-for-f32
substitution gives (bf16 keeps 8 bits: ~4e-3 on such logits, shown by
``test_tolerance_rejects_bf16``) and three under int8."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu import inference
from paddle2_tpu.incubate.moe import DroplessExperts, sigmoid_topk_route
from paddle2_tpu.kernels.moe_gmm import gmm_reference, moe_gmm
from paddle2_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM
from paddle2_tpu.serving import EngineConfig, ServingEngine
from paddle2_tpu.serving.block_cache import audit_kv_ledger
from paddle2_tpu.serving.model_runner import PagedRunner
from paddle2_tpu.serving.paged_attention import (paged_attention_decode,
                                                 paged_attention_reference)
from paddle2_tpu.serving.spec import SpeculativeConfig

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
LOGIT_TOL = 5e-5


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules and the tiny (rehearsal) configuration."""
    added = [p for p in (BENCH,) if p not in sys.path]
    sys.path[:0] = added
    import run as harness
    from common import load_module
    from drivers import program
    from weights import make_weights
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    cfg = harness.merge(cfg, cfg["rehearsal"])
    cfg["name"] = "lfm2-24b-a2b"
    ref = load_module("reference", cfg["reference"])
    yield {"cfg": cfg, "ref": ref, "program": program,
           "make_weights": make_weights}
    for p in added:
        sys.path.remove(p)


def build(bench, seed, **overrides):
    """(model with the seed's weights, its config, the reference's
    float32 leaves of the same seed)."""
    cfg = bench["cfg"]
    model, mcfg = bench["program"].build_model(cfg, overrides)
    model.eval()
    bench["program"].set_weights(model, cfg, "per_layer", bench["ref"], seed)
    params = bench["make_weights"](bench["ref"].leaf_specs(cfg), seed,
                                   jnp.float32)
    return model, mcfg, params


def ref_logits(bench, params, seq):
    return np.asarray(bench["ref"].logits(
        params, jnp.asarray([seq], jnp.int32), bench["cfg"])[0])


@pytest.fixture
def logit_tap(monkeypatch):
    """Every logits array the runner's sampling wrapper is handed, in
    call order, without a new engine flag. ``serve`` pairs a call of
    ``decode_once`` with the logits of the step it ran, so the engine
    is held to reading every step back in the call that enqueued it —
    by its own rule: an armed drop hook (which never fires here)."""
    from paddle2_tpu.distributed.fault_tolerance import chaos
    monkeypatch.setattr(chaos, "_ACTIVE",
                        chaos.ChaosInjector("drop_decode_step:1000000000"))
    store = []
    sample = PagedRunner._sample

    def tapped(logits, counts):
        jax.debug.callback(lambda lg: store.append(np.asarray(lg)), logits,
                           ordered=True)
        return sample(logits, counts)

    monkeypatch.setattr(PagedRunner, "_sample", staticmethod(tapped))
    return store


def serve(engine, prompts, max_new, store):
    """Drive the engine to idle; {request id: [logits row of each
    generated token, in order]} and the request ids."""
    rids = [engine.submit(p, max_new) for p in prompts]
    rows = {r: [] for r in rids}
    now = 0.0
    while not engine.idle():
        now += 1.0
        for info in engine.admit_and_prefill(now):
            jax.effects_barrier()
            rows[info["seq"].req_id].append(store.pop(0)[0])
        active = [s for s in engine.scheduler.running()
                  if getattr(s, "ready_at", 0.0) <= now]
        before = engine.scheduler.total_evictions
        if engine.decode_once(now):
            jax.effects_barrier()
            lg = store.pop(0)
            # an eviction inside the step drops rows from the END of
            # the running list (LIFO victims)
            gone = engine.scheduler.total_evictions - before
            for i, s in enumerate(active[:len(active) - gone]):
                rows[s.req_id].append(lg[i])
    assert not store
    return rids, rows


def check_against_reference(bench, params, engine, rids, rows):
    worst = 0.0
    for rid in rids:
        seq = engine.sequence(rid)
        prompt, gen = seq.request.prompt, seq.generated
        assert len(rows[rid]) == len(gen)
        ref = ref_logits(bench, params, list(prompt) + list(gen))
        for j, row in enumerate(rows[rid]):
            worst = max(worst, float(np.abs(
                row - ref[len(prompt) - 1 + j]).max()))
    assert worst <= LOGIT_TOL, worst
    return worst


def tiny_engine(model, **kw):
    conf = dict(block_size=8, num_blocks=64, max_batch=4, max_model_len=96,
                kv_dtype="float32", interpret=True)
    conf.update(kw)
    return ServingEngine(model, config=EngineConfig(**conf))


# ------------------------------------------------------------- the model
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_forward_logits_match_reference(bench, seed):
    model, _, params = build(bench, seed)
    ids = np.random.default_rng(seed).integers(1, 503, (2, 37))
    got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._data)
    want = np.asarray(bench["ref"].logits(params, jnp.asarray(ids),
                                          bench["cfg"]))
    assert np.abs(got - want).max() <= LOGIT_TOL


def test_tolerance_rejects_bf16(bench):
    """The control of LOGIT_TOL: the reference itself with bf16 (and
    int8) matmul operands lies far outside it."""
    from reference import common as rc
    _, _, params = build(bench, 3)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 503, (1, 37)))
    want = bench["ref"].logits(params, ids, bench["cfg"])
    for prec in ("bfloat16", "int8"):
        low = bench["ref"].logits(params, ids, bench["cfg"],
                                  rc.MATMULS[prec])
        assert float(jnp.abs(low - want).max()) > 20 * LOGIT_TOL, prec


def test_config_takes_published_keys_and_pattern():
    cfg = Lfm2MoeConfig()          # the published 40-layer defaults
    assert cfg.layer_types.count("full_attention") == 10
    assert all((t == "full_attention") == (i % 4 == 2)
               for i, t in enumerate(cfg.layer_types))
    assert cfg.head_dim == 64
    from paddle2_tpu.models import lfm2_moe_tiny
    tiny = lfm2_moe_tiny(num_experts=16)
    assert (tiny.num_experts, tiny.layer_types[1]) == (16, "full_attention")
    with pytest.raises(ValueError):
        Lfm2MoeConfig(num_hidden_layers=4, layer_types=["conv"] * 3)
    with pytest.raises(ValueError):
        Lfm2MoeConfig(conv_bias=True)


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
    conf.enable_continuous_batching(block_size=8, num_blocks=64,
                                    max_batch=4, max_model_len=96,
                                    kv_dtype="float32", interpret=True)
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


def test_forced_experts_and_their_deficit(bench):
    """The reference with ANOTHER choice handed in: the weights are its
    own scores at those experts, the deficit is how far the choice lies
    from its own top k, and -1 leaves a row to the reference."""
    ref, cfg = bench["ref"], bench["cfg"]
    from reference.common import matmul_f32
    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.standard_normal((6, 64)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((64, 8)) * 0.1, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(8) * 0.02, jnp.float32)
    own, w, deficit = ref.route(a, gate, bias, cfg, matmul_f32)
    assert float(deficit.max()) == 0.0
    pick = np.asarray(jax.nn.sigmoid(a @ gate) + bias)
    order = np.argsort(-pick, -1)
    # take the 1st and the 4th instead of the 1st and the 2nd
    forced = np.stack([order[:, 0], order[:, 3]], -1).astype(np.int32)
    forced[5] = -1                                # this row stays free
    got, w2, deficit = ref.route(a, gate, bias, cfg, matmul_f32,
                                 jnp.asarray(forced))
    np.testing.assert_array_equal(np.asarray(got)[:5], forced[:5])
    np.testing.assert_array_equal(np.asarray(got)[5], np.asarray(own)[5])
    want = np.take_along_axis(pick, order[:, 1:2], -1)[:, 0] \
        - np.take_along_axis(pick, order[:, 3:4], -1)[:, 0]
    np.testing.assert_allclose(np.asarray(deficit)[:5], want[:5],
                               rtol=1e-5)
    assert float(deficit[5]) == 0.0
    s = np.asarray(jax.nn.sigmoid(a @ gate))
    ws = np.take_along_axis(s[:5], forced[:5], -1)
    np.testing.assert_allclose(np.asarray(w2)[:5],
                               ws / (ws.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-5)


# ------------------------------------------------------------- routing
def test_bias_selects_and_unbiased_scores_weigh():
    a = jnp.eye(4, dtype=jnp.float32)[:1]           # picks row 0 of W_g
    gate = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0, -3.0]]
                       + [[0.0] * 6] * 3, jnp.float32)
    s = jax.nn.sigmoid(gate[0])
    ids, w = sigmoid_topk_route(a, gate, None, 2)
    assert ids.tolist() == [[0, 1]]
    # a bias lifts experts 4 and 5 over the rest: they are selected ...
    bias = jnp.asarray([0, 0, 0, 0, 5.0, 4.0], jnp.float32)
    ids, w = sigmoid_topk_route(a, gate, bias, 2)
    assert ids.tolist() == [[4, 5]]
    # ... and weighed by their UNBIASED scores, normed with 1e-6
    want = s[jnp.asarray([4, 5])] / (s[4] + s[5] + 1e-6)
    np.testing.assert_allclose(np.asarray(w[0]), np.asarray(want),
                               rtol=1e-6)
    assert float(w.sum()) < 1.0                     # the 1e-6 is there
    _, raw = sigmoid_topk_route(a, gate, bias, 2, norm_topk=False,
                                scale=2.5)
    np.testing.assert_allclose(np.asarray(raw[0]),
                               2.5 * np.asarray(s[jnp.asarray([4, 5])]),
                               rtol=1e-6)


def test_router_is_float32_under_bf16_parameters():
    """Two experts whose float32 scores differ by less than a bf16 step:
    the float32 router tells them apart."""
    a = jnp.ones((1, 2), jnp.bfloat16)
    gate = jnp.asarray([[1.0, 1.0], [0.001, 0.002]], jnp.float32)
    ids, _ = sigmoid_topk_route(a, gate.astype(jnp.float32), None, 1)
    assert ids.tolist() == [[1]]


# ------------------------------------------------------- grouped matmul
@pytest.mark.parametrize("sizes,first,held", [
    ([5, 0, 11, 3, 0, 13], 0, 6),        # uneven, empty experts
    ([32, 0, 0, 0, 0, 0], 0, 6),         # one expert takes everything
    ([0, 0, 0, 0, 0, 40], 0, 6),         # ... the last one
    ([8, 8, 8, 8, 8, 8], 0, 6),          # even
    ([5, 0, 11, 3, 0, 13], 2, 3),        # a held share in the middle
    ([4, 4, 4, 4, 4, 4, 8], 1, 4),       # rows parked behind the share
    ([130, 7, 0, 250, 1, 60], 0, 6),     # groups across row tiles
])
def test_grouped_matmul_against_plain_loop(sizes, first, held):
    rng = np.random.default_rng(sum(sizes))
    sizes = np.asarray(sizes, np.int32)
    lhs = jnp.asarray(rng.standard_normal((int(sizes.sum()), 64)),
                      jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), 64, 128)),
                      jnp.float32)[first:first + held]
    got = moe_gmm(lhs, rhs, sizes, first, interpret=True)
    want = gmm_reference(lhs, rhs, sizes, first)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("router,E,k", [("sigmoid", 64, 4),
                                        ("softmax", 128, 8)])
def test_expert_shares_add_up_to_the_whole_layer(bench, router, E, k):
    """Eight shares of one expert layer (8 of 64 sigmoid-routed experts,
    16 of 128 softmax-routed ones): each routes over all and computes its
    own experts' part; the parts add up to the uncut reference's layer
    (and each part is the reference's share)."""
    H, F, n = 64, 48, E // 8
    paddle.seed(21)
    whole = DroplessExperts(H, F, E, k, std=0.2, router=router)
    from common import load_module
    from reference.common import matmul_f32
    if router == "sigmoid":
        ref, pre = bench["ref"], "moe0_"
        extra = {"moe0_bias": whole.expert_bias._data}
        cfg = dict(bench["cfg"], num_experts=E, num_experts_per_tok=k)
    else:
        ref, pre, extra = load_module("reference", "sdar_moe"), "l0_", {}
        cfg = {"num_experts": E, "num_experts_per_tok": k,
               "norm_topk_prob": True}
    p = dict({pre + "gate": whole.gate_weight._data,
              pre + "w1": whole.w1._data, pre + "w3": whole.w3._data,
              pre + "w2": whole.w2._data}, **extra)
    a = jnp.asarray(np.random.default_rng(21).standard_normal((40, H)),
                    jnp.float32)
    want, used, _ = ref.experts_ff(a, p, 0, cfg, matmul_f32)
    total = jnp.zeros_like(a)
    assigned = 0
    for share in range(8):
        lo = n * share
        part = DroplessExperts(H, F, E, k, held=(lo, n), router=router)
        part.gate_weight.set_value(whole.gate_weight)
        if router == "sigmoid":
            part.expert_bias.set_value(whole.expert_bias)
        for name in ("w1", "w3", "w2"):
            getattr(part, name).set_value(paddle.Tensor(
                getattr(whole, name)._data[lo:lo + n]))
        out, counts = part.route_and_run(a, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(ref.experts_ff(a, p, 0, cfg, matmul_f32,
                                      held=(lo, n))[0]), atol=2e-5)
        total = total + out
        assigned += int(counts[0])
    assert assigned == 40 * k                  # every assignment, once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    out, counts = whole.route_and_run(a, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5)
    assert int(counts[0]) == 40 * k
    # every row is routed, and holding all experts, routed here
    assert counts[3:5].tolist() == [40, 40]
    # 40 x k rows in tiles of 32 or 64 (128 halved until it divides the
    # rows): the (row tile, expert) pairs that share rows, times the tile
    from test_moe_gmm_tiles import visits_by_hand
    n_counts = len(DroplessExperts.COUNT_NAMES)
    tm = 32 if k == 4 else 64
    sizes = np.bincount(np.asarray(used).ravel(), minlength=E)
    assert n_counts == 6 \
        and int(counts[5]) == tm * visits_by_hand(sizes, 0, E, tm)
    # behind the counts, the experts chosen row by row
    np.testing.assert_array_equal(
        np.sort(np.asarray(counts[n_counts:]).reshape(40, k), -1),
        np.sort(np.asarray(used), -1))


# ---------------------------------------------- paged kernel, H_kv < H
@pytest.mark.parametrize("H,Hkv,D,bs,pages,pps", [
    (4, 2, 16, 8, 6, None), (8, 2, 64, 16, 8, None), (8, 2, 64, 16, 8, 2),
    (4, 1, 16, 8, 5, 2), (32, 8, 64, 16, 4, None)])
def test_paged_kernel_grouped_query(H, Hkv, D, bs, pages, pps):
    rng = np.random.default_rng(H * 100 + Hkv)
    B, N = 3, 40
    kp, vp = (jnp.asarray(rng.standard_normal((2, N, bs, Hkv * D)),
                          jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    bt = rng.permutation(np.arange(1, N))[:B * pages].reshape(
        B, pages).astype(np.int32)
    ctx = np.asarray([pages * bs - 3, 5, bs + 1], np.int32)
    got = paged_attention_decode(q, kp, vp, bt, ctx, pages_per_split=pps,
                                 layer=1, interpret=True)
    want = paged_attention_reference(q, kp[1], vp[1], bt, ctx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


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
    model, _, _ = build(bench, 23)
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
