"""EXAONE-MoE's own pieces, apart from the engine: the band attention of
a sliding layer over a whole sequence (chunks of a window against two)
against the dense band mask, and its operation count, linear in the
sequence; the 8 shares of an expert layer, which add up to the uncut
reference's layer with the shared expert counted once; the ring a padded
prefill hands over, which is the ring at ``last_idx`` and as large at
any context; the config class's refusals. Harness: ``served.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.models import (ExaoneMoeConfig, ExaoneMoeForCausalLM,
                                exaone_moe_tiny)
from paddle2_tpu.models.exaone_moe import (ExaoneMoeSparseBlock,
                                           local_window_attention)
from paddle2_tpu.serving.exaone_moe_family import ring_rows
from paddle2_tpu.serving.model_runner import PagedRunner
from served import build  # noqa: F401
from served import exaone_moe_bench as bench


# ------------------------------------------------------------------ the band
def dense_band(q, k, v, window):
    """The definition: the ``[S, S]`` mask ``0 <= i - j < window``."""
    B, S, nh, hd = q.shape
    g = nh // k.shape[2]
    kk, vv = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(hd)
    ago = jnp.arange(S)[:, None] - jnp.arange(S)[None]
    s = jnp.where((ago >= 0) & (ago < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)


@pytest.mark.parametrize("seq,window", [(5, 8), (8, 8), (9, 8), (64, 8),
                                        (37, 16), (256, 128), (300, 128)])
def test_chunked_band_equals_the_dense_band_mask(seq, window):
    rng = np.random.default_rng(seq)
    q = jnp.asarray(rng.normal(size=(2, seq, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, seq, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, seq, 2, 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = local_window_attention(q, k, v, window)
        want = dense_band(q, k, v, window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_band_operations_are_linear_in_the_sequence():
    """The lowered band at S and 4 S: four times the operations (a
    causal square would be sixteen times)."""
    def flops(seq):
        x = jax.ShapeDtypeStruct((1, seq, 4, 16), jnp.float32)
        kv = jax.ShapeDtypeStruct((1, seq, 2, 16), jnp.float32)
        return jax.jit(lambda q, k, v: local_window_attention(
            q, k, v, 128)).lower(x, kv, kv).cost_analysis()["flops"]
    assert flops(8192) / flops(2048) == pytest.approx(4.0, rel=0.02)


# ------------------------------------------------- the shares of a layer
def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(bench):
    """Each of 8 chips holds one of the tiny router's 8 experts and the
    shared expert: the routed parts summed, the shared expert ONCE, are
    the uncut reference's expert layer."""
    ref = bench["ref"]
    cfg = dict(bench["cfg"], num_experts=8, router_experts=8,
               held_experts=None)
    params = bench["make_weights"](ref.leaf_specs(cfg), 4, jnp.float32)
    a = jnp.asarray(np.random.default_rng(4).normal(size=(11, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.experts_op(a, params, 1, cfg, ref.matmul_f32)

        def share(first):
            paddle.seed(0)
            block = ExaoneMoeSparseBlock(exaone_moe_tiny(
                held_experts=(first, 1)))
            e, s = block.experts, block.shared_experts
            for p, leaf in ((e.gate_weight, "gate"), (e.expert_bias, "bias"),
                            (s.w1.weight, "s_gate"), (s.w3.weight, "s_up"),
                            (s.w2.weight, "s_down")):
                p.set_value(paddle.Tensor(params[f"l1_{leaf}"]))
            for p, leaf in ((e.w1, "e_gate"), (e.w3, "e_up"),
                            (e.w2, "e_down")):
                p.set_value(paddle.Tensor(
                    params[f"l1_{leaf}"][first:first + 1]))
            return block.run(a, interpret=True)[0], s.run(a)

        outs = [share(first) for first in range(8)]
    shared = outs[0][1]
    total = sum(out - shared for out, _ in outs) + shared
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    # a share alone is NOT the layer: nothing stands in for the rest
    assert float(jnp.abs(outs[0][0] - want).max()) > 1e-3


# ------------------------------------------------------- the ring handed over
def test_ring_rows_hold_the_last_window_positions_each_at_its_row():
    x = jnp.arange(40, dtype=jnp.float32)[:, None] * jnp.ones((1, 3))
    # 21 positions (last_idx 20), window 8: rows 0..4 hold 16..20, rows
    # 5..7 hold 13..15
    ring = np.asarray(ring_rows(x, jnp.int32(20), 8))[:, 0]
    assert ring.tolist() == [16, 17, 18, 19, 20, 13, 14, 15]
    # shorter than the window: rows past the sequence are zero
    ring = np.asarray(ring_rows(x + 1, jnp.int32(2), 8))[:, 0]
    assert ring.tolist() == [1, 2, 3, 0, 0, 0, 0, 0]


def family_prefill(model, ids, last_idx):
    """The family's prefill as ONE compiled program a length."""
    runner = PagedRunner(model, interpret=True)

    def prefill(weights, ids, last_idx):
        with runner.bound(weights):
            return runner.family.prefill(ids, last_idx, True)
    return jax.jit(prefill)(runner._weights(),
                            jnp.asarray([ids], jnp.int32),
                            jnp.int32(last_idx))


def test_ring_after_a_padded_prefill_is_the_ring_at_last_idx(bench):
    """The padded tail is neither routed nor written: a prompt of 21
    tokens padded to 32 with other tokens hands over the logits, the
    ring and the routing of the 21."""
    model, _, _ = build(bench, 8)
    rng = np.random.default_rng(8)
    prompt = rng.integers(1, 503, 21).tolist()
    exact = family_prefill(model, prompt, 20)
    padded = family_prefill(model, prompt + rng.integers(1, 503, 11).tolist(),
                            20)
    np.testing.assert_allclose(padded[0], exact[0], rtol=1e-5, atol=1e-5)
    for kind in (0, 1):
        assert exact[3][kind].shape == (3, 8, 2 * 16)
        np.testing.assert_allclose(padded[3][kind], exact[3][kind],
                                   rtol=1e-5, atol=1e-5)
    # the routing counts ride first in each layer's record; the last two
    # count the tiles' rows and the rows moved, which the padded length
    # sets
    names = PagedRunner(model).family.count_names
    assert names[-2:] == ("moe_tile_rows", "moe_rows_moved")
    counted = len(names) - 2
    np.testing.assert_array_equal(padded[4][:, :counted],
                                  exact[4][:, :counted])


@pytest.mark.parametrize("context", [64, 128, 1024])
def test_a_sliding_layers_bytes_do_not_grow_with_the_context(context):
    """The ring a prefill hands over is ``[sliding layers, window, key/
    value heads x head_dim]`` at a context of half the window, the window
    and eight windows, while the global layer's keys grow with it."""
    paddle.seed(0)
    model = ExaoneMoeForCausalLM(exaone_moe_tiny(
        sliding_window=128, max_position_embeddings=2048))
    model.eval()
    # shapes only: nothing is computed
    runner = PagedRunner(model, interpret=True)
    family = runner.family
    with runner.bound():
        _, k_stack, _, state, _ = jax.eval_shape(
            lambda ids: family.prefill(ids, jnp.int32(context - 1), True),
            jax.ShapeDtypeStruct((1, context), jnp.int32))
    assert [s.shape for s in state] == [(4, 128, 32)] * 2
    assert k_stack.shape == (1, context, 2, 16)
    assert family.state_kinds["ring_k"] == ((4, 128, 32), None)
    assert family.decode_counts(np.array([context - 1]))["window_tokens"] \
        == min(context, 128)


# ------------------------------------------------------------- the config
def test_config_takes_the_published_keys_and_refuses_the_rest():
    cfg = ExaoneMoeConfig()
    assert cfg.layer_types[:5] == ["sliding_attention"] * 3 \
        + ["full_attention", "sliding_attention"]
    assert cfg.mlp_layer_types[:2] == ["dense", "sparse"]
    assert (cfg.window_of(0), cfg.window_of(3)) == (128, None)
    for bad in (dict(num_nextn_predict_layers=1),
                dict(tie_word_embeddings=True), dict(scoring_func="softmax"),
                dict(n_group=8), dict(num_shared_experts=2),
                dict(hidden_act="gelu")):
        with pytest.raises(ValueError, match="not implemented"):
            exaone_moe_tiny(**bad)
    with pytest.raises(ValueError, match="held_experts"):
        exaone_moe_tiny(held_experts=(6, 4))
    with pytest.raises(ValueError, match="layer_types names"):
        exaone_moe_tiny(layer_types=["full_attention"])
