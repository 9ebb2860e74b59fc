"""SDAR-MoE (``models/sdar.py``: block diffusion over softmax-routed
dropless experts) against the benchmark's plain reference
(``benchmark/reference/sdar_moe.py``), tiny sizes, float32 on the CPU,
Pallas kernels interpreted, seeded random weights placed through the
benchmark's own layout (``benchmark/configs/sdar-30b-a3b-chat.json``).

Tolerances. Model and reference compute the same float32 mathematics in
another order (grouped matmul over sorted rows against a scan over
experts, paged softmax per page block against one row), so logits of
scale ~1 agree to a few 1e-6; ``LOGIT_TOL`` = 5e-5 leaves an order of
magnitude of room and is two orders under what a bf16-for-f32
substitution gives (``test_tolerance_rejects_*``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.incubate.moe import softmax_topk_route
from paddle2_tpu.kernels import pallas_flash
from paddle2_tpu.kernels.attention import _sdpa_xla
from paddle2_tpu.models import SdarMoeConfig
from paddle2_tpu.serving import blockdiff
from paddle2_tpu.serving.paged_attention import (paged_attention_decode,
                                                 paged_attention_reference)
from served import LOGIT_TOL, build_as_read, shared_programs  # noqa: F401
from served import sdar_bench as bench

VOCAB = 503
pytestmark = pytest.mark.usefixtures("shared_programs")


# ------------------------------------------------------------- the model
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_forward_logits_match_reference(bench, seed):
    model, cfg, params = build_as_read(bench, seed)
    ids = np.random.default_rng(seed).integers(1, VOCAB, (2, 37))
    got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._data)
    want = np.asarray(bench["ref"].logits(params, jnp.asarray(ids), cfg))
    assert np.abs(got - want).max() <= LOGIT_TOL


@pytest.mark.parametrize("control", ["causal", "bfloat16", "int8"])
def test_tolerance_rejects(bench, control):
    """The controls of LOGIT_TOL: the reference itself under a plain
    causal mask (a position blind to the rest of its block), and with
    bf16 or int8 matmul operands, lies far outside it."""
    from reference import common as rc
    ref = bench["ref"]
    _, cfg, params = build_as_read(bench, 3)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, VOCAB, (1, 37)))
    want = ref.logits(params, ids, cfg)
    if control == "causal":
        low = ref.forward(params, ids, cfg,
                          mask=ref.block_causal_mask(37, 1))[0]
    else:
        low = ref.logits(params, ids, cfg, rc.MATMULS[control])
    assert float(jnp.abs(low - want).max()) > 20 * LOGIT_TOL


def test_config_takes_published_keys():
    cfg = SdarMoeConfig()           # the published 48-layer defaults
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.head_dim,
            cfg.num_key_value_heads, cfg.vocab_size) == (
        128, 8, 128, 4, 151936)
    with pytest.raises(ValueError):
        SdarMoeConfig(tie_word_embeddings=True)
    with pytest.raises(ValueError):
        SdarMoeConfig(mlp_only_layers=[0])
    with pytest.raises(ValueError):
        SdarMoeConfig(mask_token_id=151936)


def test_softmax_router_renormalises_its_top_k():
    a = jnp.eye(4, dtype=jnp.float32)[:1]           # picks row 0 of W_g
    gate = jnp.asarray([[2.0, 1.0, 0.0, -1.0, 3.0, -3.0]]
                       + [[0.0] * 6] * 3, jnp.float32)
    p = np.asarray(jax.nn.softmax(gate[0]))
    ids, w = softmax_topk_route(a, gate, 2)
    assert ids.tolist() == [[4, 0]]
    np.testing.assert_allclose(np.asarray(w[0]), p[[4, 0]] / p[[4, 0]].sum(),
                               rtol=1e-6)
    _, raw = softmax_topk_route(a, gate, 2, norm_topk=False)
    np.testing.assert_allclose(np.asarray(raw[0]), p[[4, 0]], rtol=1e-6)


def test_clean_noisy_mask_is_the_published_training_mask(bench):
    """[clean ; noisy]: clean rows block-causal and blind to the noisy
    copy; a noisy block sees the clean blocks BEFORE it and itself."""
    pos, sees = bench["ref"].clean_noisy(12, 4, 4)
    assert pos.tolist() == list(range(12)) + list(range(4, 12))
    np.testing.assert_array_equal(sees[:12, :12],
                                  bench["ref"].block_causal_mask(12, 4))
    assert not sees[:12, 12:].any()
    # noisy block of positions 8..11: clean 0..7, not clean 8..11
    assert sees[16:, :8].all() and not sees[16:, 8:12].any()
    assert sees[16:, 16:].all() and not sees[16:, 12:16].any()
    assert sees[12:16, :4].all() and not sees[12:16, 4:12].any()


# --------------------------------------------------------- the schedule
def test_fix_plan_is_known_without_reading_anything():
    assert blockdiff.fix_plan(4, 4, 4) == (1, 1, 1, 1)
    assert blockdiff.fix_plan(4, 2, 4) == (2, 2)
    assert blockdiff.fix_plan(4, 1, 4) == (4,)
    assert blockdiff.fix_plan(8, 2, 8) == (4, 4)
    # a block that opens with prompt tokens takes fewer, smaller passes
    assert blockdiff.fix_plan(4, 2, 3) == (2, 1)
    assert blockdiff.fix_plan(4, 4, 1) == (1,)
    assert blockdiff.fix_plan(8, 2, 3) == (3,)


def test_unmask_takes_the_most_confident_masked_positions():
    lg = np.full((2, 4, 6), -4.0, np.float32)
    # row 0: confidences rise with the position; position 3 is fixed
    for j, top in enumerate((1.0, 2.0, 3.0, 9.0)):
        lg[0, j, j + 1] = top
    lg[1, :, 5] = 2.0                               # row 1: all tie
    ids = jnp.asarray([[7, 7, 7, 42], [7, 7, 7, 7]], jnp.int32)
    masked = jnp.asarray([[1, 1, 1, 0], [1, 1, 1, 1]], bool)
    out, left = blockdiff.unmask_low_confidence(
        jnp.asarray(lg), ids, masked, jnp.asarray([2, 1], jnp.int32))
    assert out.tolist() == [[7, 2, 3, 42], [5, 7, 7, 7]]
    assert left.tolist() == [[True, False, False, False],
                             [False, True, True, True]]
    # a commit pass (nothing to fix) comes back as it went in
    out, left = blockdiff.unmask_low_confidence(
        jnp.asarray(lg), ids, masked, jnp.zeros(2, jnp.int32))
    assert out.tolist() == ids.tolist() and left.tolist() == masked.tolist()


# ------------------------------------------------------------- kernels
@pytest.mark.parametrize("block", [1, 4, 8])
def test_flash_block_causal_fwd_bwd(block):
    """The flash kernel under the block-causal mask, forward and
    backward, several tiles a side, against the dense masked softmax."""
    rng = np.random.default_rng(block)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 64, 2, 16)), jnp.float32)
               for _ in range(3))

    def flash(q, k, v):
        return pallas_flash.flash_attention_bshd(
            q, k, v, causal=True, causal_block=block, block_q=16,
            block_k=16, interpret=True)

    def dense(q, k, v):
        return _sdpa_xla(q, k, v, causal=True, causal_block=block)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)), atol=2e-5)
    if block > 1:
        # and it is not the plain causal mask
        assert float(jnp.abs(dense(q, k, v) - _sdpa_xla(
            q, k, v, causal=True)).max()) > 1e-2
    w = jnp.asarray(rng.standard_normal((2, 64, 2, 16)), jnp.float32)
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (dense(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for g, e in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), atol=5e-5)


def test_flash_refuses_a_block_it_cannot_mask():
    q = jnp.zeros((1, 64, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="power of two"):
        pallas_flash.flash_attention_bshd(q, q, q, causal=True,
                                          causal_block=6, interpret=True)


@pytest.mark.parametrize("Q", [1, 4, 8])
def test_paged_kernel_block_of_positions(Q):
    """Q query positions a sequence over ONE walk of its pages, 32 query
    heads over 4 key/value heads, every position's context ending at the
    block's end: each position against the reference's single row."""
    H, Hkv, D, bs, pages = 32, 4, 16, 16, 5
    rng = np.random.default_rng(Q)
    B, N = 3, 24
    kp, vp = (jnp.asarray(rng.standard_normal((2, N, bs, Hkv * D)),
                          jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, Q, H, D)), jnp.float32)
    bt = rng.permutation(np.arange(1, N))[:B * pages].reshape(
        B, pages).astype(np.int32)
    ctx = np.asarray([pages * bs - 8, 8, bs + 16], np.int32)
    got = paged_attention_decode(q, kp, vp, bt, ctx, layer=1,
                                 interpret=True)
    assert got.shape == (B, Q, H, D)
    for j in range(Q):
        want = paged_attention_reference(q[:, j:j + 1], kp[1], vp[1], bt,
                                         ctx)
        np.testing.assert_allclose(np.asarray(got[:, j:j + 1]),
                                   np.asarray(want), rtol=2e-6, atol=2e-6)
