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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.incubate.moe import DroplessExperts, sigmoid_topk_route
from paddle2_tpu.models import (NemotronHConfig, NemotronHForCausalLM,
                                nemotron_h_tiny)
from paddle2_tpu.models._decoder import GroupedQueryAttention, Relu2MLP
from served import (LOGIT_TOL, build, shared_programs,  # noqa: F401
                    ref_logits_highest as ref_logits)
from served import nemotron_h_bench as bench, NEMOTRON_CUT as CUT

pytestmark = pytest.mark.usefixtures("shared_programs")


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
    assert bench["whole"]["hybrid_override_pattern"] == CUT
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
