"""Nemotron-H (``models/nemotron_h.py``, ``serving/nemotron_h_family.py``,
the two-matrix form of ``incubate/moe.DroplessExperts``) against the
benchmark's plain reference (``benchmark/reference/nemotron_h.py``: the
recurrence as a token-by-token scan, the experts one after another),
tiny sizes, float32 on the CPU, Pallas kernels interpreted, seeded
random weights placed a leaf at a time through the benchmark's own
layout (``benchmark/configs/nemotron-3-nano-30b-a3b.json``,
``drivers/serve_staged_dense.place_weights``). The rehearsal size keeps
what is awkward in the real one: ONE mixer a layer in the cut's own
pattern ``MEMEM*EME`` (its first six layers, which hold every kind,
where an engine's programs are compiled); 4 of 8 routed experts held; an
expert width (40) that its storage alignment (16) does not divide; 6
query heads over 2 key/value heads; a selection bias that changes
choices; no rotary embedding anywhere. ``nemotron_h_tiny`` (pattern
``MEM*EME``, all 8 experts held) serves the tests that need no reference
weights.

Tolerances. Model and reference compute the same float32 mathematics in
another order (the chunked scan against the token-by-token recurrence,
the grouped matmul over sorted rows against one expert after another
over all rows, paged softmax per page block against one row), so logits
of scale ~1 agree to a few 1e-6; ``LOGIT_TOL`` = 5e-5 leaves an order of
magnitude of room and is two orders under what a bf16-for-f32
substitution gives (``test_tolerance_rejects_bf16``). States are
compared to ``STATE_TOL`` = 2e-5 (absolute, on states of scale ~1)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.distributed.fault_tolerance import chaos
from paddle2_tpu.incubate.moe import DroplessExperts, sigmoid_topk_route
from paddle2_tpu.models import (NemotronHConfig, NemotronHForCausalLM,
                                nemotron_h_tiny)
from paddle2_tpu.models._decoder import GroupedQueryAttention, Relu2MLP
from paddle2_tpu.serving.block_cache import audit_kv_ledger
from paddle2_tpu.serving.model_runner import PagedRunner
from paddle2_tpu.serving.spec import SpeculativeConfig
# the comparison of served logits and the drive to idle are Falcon-H1's
# (same fixture keys, same tolerance); the logits tap and the tiny
# engine are LFM2's
from test_falcon_h1 import check_against_reference, run_to_idle
from test_lfm2_moe import logit_tap, serve, tiny_engine  # noqa: F401

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
LOGIT_TOL = 5e-5
STATE_TOL = 2e-5
CUT = "MEMEM*EME"          # the benchmark's cut, at the rehearsal's widths
PATTERN = CUT[:6]          # what the engine tests serve: every kind, 6 layers


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules and the tiny (rehearsal) configuration."""
    added = [p for p in (BENCH,) if p not in sys.path]
    sys.path[:0] = added
    import run as harness
    from common import load_module
    from drivers import program, serve_staged_dense
    from weights import make_weights
    with open(os.path.join(BENCH, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        published = json.load(f)
    whole = harness.merge(published, published["rehearsal"])
    whole["name"] = "nemotron-3-nano-30b-a3b"
    assert whole["hybrid_override_pattern"] == CUT
    # the layout names a leaf by its layer's index: a prefix of the
    # pattern is served through the same file
    cfg = dict(whole, hybrid_override_pattern=PATTERN,
               num_hidden_layers=len(PATTERN))
    ref = load_module("reference", cfg["reference"])
    yield {"cfg": cfg, "whole": whole, "published": published, "ref": ref,
           "program": program, "driver": serve_staged_dense,
           "make_weights": make_weights}
    for p in added:
        sys.path.remove(p)


def build(bench, seed, cfg=None, **overrides):
    """(model with the seed's weights, its config, the reference's
    float32 leaves of the same seed)."""
    cfg = cfg or bench["cfg"]
    model, mcfg = bench["program"].build_model(cfg, overrides)
    model.eval()
    bench["driver"].place_weights(model, cfg, "per_layer", bench["ref"],
                                  seed)
    params = bench["make_weights"](bench["ref"].leaf_specs(cfg), seed,
                                   jnp.float32)
    return model, mcfg, params


def ref_logits(bench, params, seq, cfg=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(bench["ref"].logits(
            params, jnp.asarray([seq], jnp.int32), cfg or bench["cfg"])[0])


# ------------------------------------------------------------- the pieces
def test_ungated_experts_have_two_matrices_and_no_w3():
    paddle.seed(0)
    layer = DroplessExperts(32, 40, 8, 2, gated=False, activation="relu2",
                            width_align=16)
    names = [n for n, _ in layer.named_parameters()]
    assert sorted(names) == ["expert_bias", "gate_weight", "w1", "w2"]
    assert not hasattr(layer, "w3")
    assert tuple(layer.w1.shape) == (8, 32, 48)
    assert tuple(layer.w2.shape) == (8, 48, 32)
    # the lanes past the width are zeros: what makes the padding exact
    assert not np.asarray(layer.w1._data[..., 40:]).any()
    assert not np.asarray(layer.w2._data[:, 40:]).any()
    assert np.asarray(layer.w1._data[..., :40]).any()
    gated = DroplessExperts(32, 40, 8, 2)
    assert tuple(gated.w3.shape) == (8, 32, 40) == tuple(gated.w1.shape)


@pytest.mark.parametrize("bad", [dict(gated=True, activation="relu2"),
                                 dict(gated=False, activation="gelu")])
def test_experts_refuse_forms_they_do_not_have(bad):
    with pytest.raises(ValueError, match="activation"):
        DroplessExperts(32, 40, 8, 2, **bad)


def test_ungated_experts_are_the_plain_loop():
    """``sum_e p_e W2_e relu(W1_e a)^2`` over the chosen experts, by a
    loop in numpy, with and without the padded storage."""
    paddle.seed(1)
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((11, 32)), jnp.float32)
    for align in (1, 16):
        paddle.seed(1)
        layer = DroplessExperts(32, 40, 8, 2, gated=False,
                                activation="relu2", scale=2.5, std=0.3,
                                norm_eps=1e-20, width_align=align)
        out, record = layer.route_and_run(a, interpret=True)
        ids = np.asarray(record[len(layer.COUNT_NAMES):]).reshape(11, 2)
        _, w = sigmoid_topk_route(a, layer.gate_weight._data,
                                  layer.expert_bias._data, 2, True, 2.5,
                                  1e-20)
        w1, w2 = np.asarray(layer.w1._data), np.asarray(layer.w2._data)
        want = np.zeros((11, 32), np.float32)
        for t in range(11):
            for j, e in enumerate(ids[t]):
                h = np.maximum(np.asarray(a[t]) @ w1[e], 0.0) ** 2
                want[t] += float(w[t, j]) * (h @ w2[e])
        assert np.abs(np.asarray(out) - want).max() <= 1e-4
        assert np.abs(want).max() > 1e-3


def test_router_epsilon_is_an_argument():
    """A row whose chosen scores underflow: LFM2's 1e-6 leaves a
    quotient of 0, the published 1e-20 does not change it."""
    a = jnp.ones((1, 4), jnp.float32)
    gate = jnp.full((4, 3), -6.0)              # sigmoid(-24) ~ 3.8e-11
    _, w6 = sigmoid_topk_route(a, gate, None, 2)
    _, w20 = sigmoid_topk_route(a, gate, None, 2, norm_eps=1e-20)
    assert float(w6.sum()) < 1e-3
    assert float(w20.sum()) == pytest.approx(1.0, abs=1e-6)


def test_attention_without_rotary_ignores_positions():
    paddle.seed(2)
    plain = GroupedQueryAttention(32, 4, 2, 8, 1e-5, 1e4, 0.2,
                                  qk_norm=False, rotary=False)
    u = jnp.asarray(np.random.default_rng(2).standard_normal((1, 5, 32)),
                    jnp.float32)
    q0, k0, _ = plain.qkv(u, jnp.arange(5)[None])
    q1, k1, _ = plain.qkv(u, jnp.arange(5)[None] + 17)
    assert bool((q0 == q1).all()) and bool((k0 == k1).all())
    paddle.seed(2)
    turned = GroupedQueryAttention(32, 4, 2, 8, 1e-5, 1e4, 0.2,
                                   qk_norm=False)
    q2, _, _ = turned.qkv(u, jnp.arange(5)[None] + 17)
    assert float(jnp.abs(q2 - q1).max()) > 1e-3


def test_relu2_mlp_is_its_formula():
    paddle.seed(3)
    mlp = Relu2MLP(16, 24, 0.3)
    a = jnp.asarray(np.random.default_rng(3).standard_normal((7, 16)),
                    jnp.float32)
    up = np.maximum(np.asarray(a) @ np.asarray(mlp.up_proj.weight._data), 0)
    want = (up * up) @ np.asarray(mlp.down_proj.weight._data)
    assert np.abs(np.asarray(mlp.run(a)) - want).max() <= 1e-5


# ------------------------------------------------------------- the config
def test_config_takes_the_published_keys(bench):
    """Every published key of the configuration file is an argument of
    the config class under its own name, and the published values are
    its defaults."""
    pub = bench["published"]
    kwargs = pub["program"]["config_kwargs"]
    cfg = NemotronHConfig()
    assert len(cfg.hybrid_override_pattern) == cfg.num_hidden_layers == 52
    assert [cfg.layer_kinds.count(k) for k in ("ssm", "moe", "attn")] \
        == [23, 23, 6]
    assert cfg.conv_dim == 6144
    changed = set(pub["reduced"]) | {"held_experts", "n_routed_experts"}
    for arg, key in kwargs.items():
        assert hasattr(cfg, arg), arg
        if arg not in changed:
            assert getattr(cfg, arg) == pub[key], arg
    assert cfg.n_routed_experts == pub["router_experts"] == 128
    assert cfg.vocab_size == pub["published_vocab_size"]
    assert cfg.hybrid_override_pattern \
        == pub["published_hybrid_override_pattern"]
    cut = NemotronHConfig(**{a: pub[k] for a, k in kwargs.items()})
    assert cut.hybrid_override_pattern == CUT
    assert cut.held_experts == (0, 64)


@pytest.mark.parametrize("bad", [
    dict(hybrid_override_pattern="ME-*", num_hidden_layers=4),
    dict(hybrid_override_pattern="MEM", num_hidden_layers=4),
    dict(n_group=2), dict(topk_group=2), dict(attention_bias=True),
    dict(mlp_bias=True), dict(use_bias=True), dict(mamba_proj_bias=True),
    dict(use_conv_bias=False), dict(tie_word_embeddings=True),
    dict(residual_in_fp32=True), dict(mamba_hidden_act="gelu"),
    dict(mlp_hidden_act="silu"), dict(moe_latent_size=512),
    dict(num_nextn_predict_layers=1), dict(sliding_window=128),
    dict(norm_eps=1e-6), dict(held_experts=(96, 64)), dict(n_groups=3)])
def test_config_refuses_what_is_not_implemented(bad):
    with pytest.raises(ValueError):
        NemotronHConfig(**bad)


def test_the_cut_counts_its_parameters(bench):
    """3,166,244,352 at the published widths, from the reference's leaf
    shapes (nothing is built)."""
    pub = bench["published"]
    specs = bench["ref"].leaf_specs(pub)
    assert sum(int(np.prod(s[0])) for s in specs.values()) \
        == pub["parameters"] == 3166244352
    per = lambda i: sum(int(np.prod(s[0])) for k, s in specs.items()  # noqa
                        if k.startswith(f"l{i}_"))
    assert (per(0), per(1), per(5)) == (38744896, 658885376, 23399040)


# ------------------------------------------------------------- the model
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_forward_logits_match_reference(bench, seed):
    model, _, params = build(bench, seed, bench["whole"])
    ids = np.random.default_rng(seed).integers(1, 503, (2, 37))
    got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._data)
    for b in range(2):
        want = ref_logits(bench, params, ids[b].tolist(), bench["whole"])
        assert np.abs(got[b] - want).max() <= LOGIT_TOL


def test_tolerance_rejects_bf16(bench):
    """The control of LOGIT_TOL: the reference itself with bf16 (and
    int8) matmul operands lies far outside it."""
    from reference import common as rc
    _, _, params = build(bench, 3)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 503, (1, 37)))
    ref, cfg = bench["ref"], bench["cfg"]
    with jax.default_matmul_precision("highest"):
        want, used, _ = ref.forward(params, ids, cfg)
        for prec in ("bfloat16", "int8"):
            low = ref.forward(params, ids, cfg, rc.MATMULS[prec], used)[0]
            assert float(jnp.abs(low - want).max()) > 20 * LOGIT_TOL, prec


def test_every_kind_of_layer_weighs_in_the_stream(bench):
    """The seeded scales are not vacuous: with any one mixer's output
    matrix zeroed the logits move by far more than the tolerance, and
    the selection bias changes some rows' experts."""
    _, _, params = build(bench, 4)
    ids = np.random.default_rng(4).integers(1, 503, 29).tolist()
    want = ref_logits(bench, params, ids)
    for leaf in ("l0_m_out", "l1_e_down", "l1_s_down", "l5_o"):   # M E E *
        damaged = dict(params, **{leaf: params[leaf] * 0})
        assert np.abs(ref_logits(bench, damaged, ids) - want).max() \
            > 100 * LOGIT_TOL, leaf
    ref, cfg = bench["ref"], bench["cfg"]
    with jax.default_matmul_precision("highest"):
        _, used, _ = ref.forward(params, jnp.asarray([ids]), cfg)
        unbiased = {k: v * 0 if k.endswith("_bias") else v
                    for k, v in params.items()}
        _, plain, _ = ref.forward(unbiased, jnp.asarray([ids]), cfg)
    assert bool((jnp.sort(used, -1) != jnp.sort(plain, -1)).any())


def test_leaf_at_a_time_placement_gives_make_weights_values(bench):
    """``place_weights`` = ``weights.make_weights``' values, leaf for
    leaf — but ``dt_bias``, which stands around its stated mean, and the
    experts' matrices, which stand in the lanes the program stores."""
    cfg, ref = bench["whole"], bench["ref"]
    model, _, params = build(bench, 2 ** 31 + 5, cfg)
    where = bench["program"].leaf_of_param(cfg, "per_layer")
    seen = 0
    for name, p in model.named_parameters():
        leaf = where[name][0]
        want = params[leaf]
        if leaf.endswith("_m_dtb"):
            want = ref.dt_bias(want, cfg)
            assert abs(float(want.mean()) - cfg["dt_bias_mean"]) < 1.5
        got = np.asarray(p._data, np.float32)
        if leaf.endswith("_e_up"):
            assert got.shape[-1] == 48 and not got[..., 40:].any()
            got = got[..., :40]
        if leaf.endswith("_e_down"):
            assert got.shape[1] == 48 and not got[:, 40:].any()
            got = got[:, :40]
        assert np.array_equal(got, np.asarray(want)), name
        seen += 1
    kinds = [CUT.count(c) for c in "ME*"]
    assert seen == 3 + 9 * kinds[0] + 7 * kinds[1] + 5 * kinds[2]
    assert seen == len(where)


# ----------------------------------------------------------- the two shares
def test_two_shares_add_up_to_the_uncut_layer(bench):
    """The guide's share test: one expert layer held as experts 0-3 and
    as experts 4-7 — the routed parts of the two shares and the shared
    expert, counted once, add up to the uncut reference's layer (all 8
    experts' leaves)."""
    ref = bench["ref"]
    whole = dict(bench["cfg"], n_routed_experts=8, held_experts=[0, 8],
                 hybrid_override_pattern="E", num_hidden_layers=1)
    specs = ref.leaf_specs(whole)
    params = bench["make_weights"](specs, 21, jnp.float32)
    rng = np.random.default_rng(21)
    x = jnp.asarray(rng.standard_normal((1, 13, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, used, _ = ref.layer(params, 0, x, whole)
        u = ref.rms(x, params["l0_norm"], whole["layer_norm_epsilon"])
        shared = ref.relu2_mlp(u, params["l0_s_up"], params["l0_s_down"],
                               jnp.matmul)
    parts = []
    for first in (0, 4):
        paddle.seed(0)
        mcfg = nemotron_h_tiny(hybrid_override_pattern="E",
                               num_hidden_layers=1,
                               held_experts=(first, 4))
        layer = NemotronHForCausalLM(mcfg).model.layers[0]
        layer.eval()
        moe = layer.mixer
        sl = slice(first, first + 4)
        for p, leaf in ((layer.norm.weight, params["l0_norm"]),
                        (moe.experts.gate_weight, params["l0_gate"]),
                        (moe.experts.expert_bias, params["l0_bias"]),
                        (moe.experts.w1, ref.pad_up(params["l0_e_up"][sl],
                                                    whole)),
                        (moe.experts.w2, ref.pad_down(
                            params["l0_e_down"][sl], whole)),
                        (moe.shared_experts.up_proj.weight,
                         params["l0_s_up"]),
                        (moe.shared_experts.down_proj.weight,
                         params["l0_s_down"])):
            p.set_value(paddle.Tensor(leaf))
        y, record = layer.feed(x, interpret=True)
        parts.append(np.asarray(y - x) - np.asarray(shared))
        # the record's "rows with an expert here" is this share's
        here = int(record[DroplessExperts.COUNT_NAMES.index(
            "moe_rows_routed_here")])
        held = np.isin(np.asarray(used[0]), range(first, first + 4))
        assert here == int(held.any(-1).sum())
    total = parts[0] + parts[1] + np.asarray(shared)
    assert np.abs(total - np.asarray(want - x)).max() <= LOGIT_TOL
    # each share alone is NOT the layer
    assert np.abs(parts[0] + np.asarray(shared)
                  - np.asarray(want - x)).max() > 100 * LOGIT_TOL


# ------------------------------------------------- prefill + paged decode
def test_prefill_then_paged_decode_logits(bench, logit_tap):
    """Prompts that are no multiples of 16 (nor of the block size or
    the chunk, 8), three sequences in one batch: every step's logits
    against the reference's full forward over prompt + generated."""
    model, _, params = build(bench, 5)
    engine = tiny_engine(model)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 503, n).tolist() for n in (5, 21, 37)]
    rids, rows = serve(engine, prompts, 7, logit_tap)
    check_against_reference(bench, params, engine, rids, rows)
    # blocks and slots are back with the manager
    assert engine.allocator.used_count == 0
    assert engine.allocator.state_slots_used == 0
    audit_kv_ledger(engine.allocator, [], state_pools=engine.cache.states)


def test_single_token_prompt_state_is_zero_padded(bench, logit_tap):
    model, _, params = build(bench, 6)
    engine = tiny_engine(model)
    rids, rows = serve(engine, [[17]], 5, logit_tap)
    check_against_reference(bench, params, engine, rids, rows)


def test_prefill_state_is_the_state_at_the_last_real_position(bench):
    """A 21-token prompt is padded to 32: the states handed to the slot
    are those of an unpadded pass over the 21 tokens, and the padded
    tail is not routed."""
    model, _, _ = build(bench, 7)
    runner = PagedRunner(model, interpret=True)
    ids = np.random.default_rng(7).integers(1, 503, 21).tolist()
    _, _, _, conv, ssm_state = runner.prefill(ids)
    with runner.bound():
        _, _, states, records = model.model.full(
            jnp.asarray([ids], jnp.int32), interpret=True)
    assert len(states) == PATTERN.count("M") == conv.shape[0]
    for li, (xbc, H) in enumerate(states):
        assert float(jnp.abs(ssm_state[li] - H[0]).max()) <= STATE_TOL
        assert float(jnp.abs(conv[li] - xbc[0, -3:]).max()) <= STATE_TOL
    with runner.bound():
        padded = jnp.asarray([ids + [0] * 11], jnp.int32)
        _, _, at_end, _ = model.model.full(padded, interpret=True)
        valid = (jnp.arange(32) <= 20)[None]
        _, _, _, routed = model.model.full(padded, valid, interpret=True)
    assert float(jnp.abs(at_end[0][1][0] - ssm_state[0]).max()) \
        > 100 * STATE_TOL
    rows = DroplessExperts.COUNT_NAMES.index("moe_rows")
    assert [int(r[rows]) for r in routed] == [21] * PATTERN.count("E")
    assert [int(r[rows]) for r in records] == [21] * PATTERN.count("E")


@pytest.mark.parametrize("n,m", [(5, 6), (16, 3), (23, 9)])
def test_prefill_plus_decode_is_a_longer_prefill(bench, n, m):
    """A prefill of n tokens + m decode steps leaves the slot's states,
    and yields the tokens, of a prefill of n + m tokens."""
    model, _, _ = build(bench, 8)
    prompt = np.random.default_rng(n).integers(1, 503, n).tolist()
    engine = tiny_engine(model, max_batch=1)
    rid = engine.submit(prompt, m + 1)
    now = 0.0
    while len(engine.sequence(rid).generated) < m + 1:
        now += 1.0
        engine.tick(now)
        if engine.sequence(rid).done:
            break
    gen = list(engine.sequence(rid).generated)
    # the slot after m decode steps (the last token is not fed)
    conv = np.asarray(engine.cache.states["conv"][:, 1])
    ssm_state = np.asarray(engine.cache.states["ssm"][:, 1])
    runner = PagedRunner(model, interpret=True)
    first, _, _, conv2, ssm2 = runner.prefill(prompt + gen[:m])
    assert first == gen[m]
    assert np.abs(conv - np.asarray(conv2)).max() <= STATE_TOL
    assert np.abs(ssm_state - np.asarray(ssm2)).max() <= STATE_TOL


def test_eviction_and_readmission_give_same_logits(bench, logit_tap):
    """A pool too small for the batch: sequences are evicted (blocks
    AND slot freed) and re-prefilled from their token logs; every
    logits row still matches the reference."""
    model, _, params = build(bench, 9)
    engine = tiny_engine(model, num_blocks=12, max_batch=3)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 503, n).tolist() for n in (19, 23, 27)]
    rids, rows = serve(engine, prompts, 12, logit_tap)
    assert engine.scheduler.total_evictions > 0
    check_against_reference(bench, params, engine, rids, rows)
    assert engine.allocator.state_slots_used == 0


@pytest.mark.parametrize("fault", ["drop_decode_step:2",
                                   "drop_decode_step:3,drop_decode_step:5"])
def test_dropped_step_leaves_the_served_tokens(bench, fault, monkeypatch):
    """ROADMAP D13: a discarded step has already moved the states its
    repeat would read. Its rows are re-prefilled, and the served tokens
    are those of an undisturbed run."""
    model, _, _ = build(bench, 10)
    rng = np.random.default_rng(10)
    prompts = [rng.integers(1, 503, n).tolist() for n in (9, 14, 20)]
    want = run_to_idle(tiny_engine(model), prompts, 10)
    monkeypatch.setattr(chaos, "_ACTIVE", chaos.ChaosInjector(fault))
    engine = tiny_engine(model)
    got = run_to_idle(engine, prompts, 10)
    assert engine.state_reprefills >= 3
    assert got == want
    assert engine.allocator.state_slots_used == 0


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
    conf.enable_continuous_batching(block_size=8, num_blocks=64,
                                    max_batch=4, max_model_len=96,
                                    kv_dtype="float32", interpret=True)
    engine = conf.create_serving_engine(gpt_config=mcfg)
    assert isinstance(engine.model, NemotronHForCausalLM)
    assert run_to_idle(engine, [prompt], 5) == want
