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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.incubate.moe import DroplessExperts, sigmoid_topk_route
from paddle2_tpu.kernels.moe_gmm import gmm_reference, moe_gmm
from paddle2_tpu.models import Lfm2MoeConfig
from paddle2_tpu.serving.paged_attention import (paged_attention_decode,
                                                 paged_attention_reference)
from served import (LOGIT_TOL, build, shared_programs,  # noqa: F401
                    visits_by_hand)
from served import lfm2_bench as bench

pytestmark = pytest.mark.usefixtures("shared_programs")


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
    n_counts = len(DroplessExperts.COUNT_NAMES)
    tm = 32 if k == 4 else 64
    sizes = np.bincount(np.asarray(used).ravel(), minlength=E)
    assert n_counts == 7 \
        and int(counts[5]) == tm * visits_by_hand(sizes, 0, E, tm)
    # holding every expert, every assignment is moved there and back
    assert int(counts[6]) == 2 * 40 * k
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
