"""Round-5 nn surface completion: pooling (unpool/fractional/lp/mask),
hierarchical + adaptive + transducer losses, beam-search decode,
flashmask/sparse attention. Reference files cited per test."""

import os

import numpy as np
import pytest

import paddle2_tpu as paddle
import paddle2_tpu.nn as nn
import paddle2_tpu.nn.functional as F


REF = "/root/reference/python/paddle"


def test_nn_namespace_parity_is_complete():
    """Every name in the reference's nn / nn.functional __all__ exists."""
    import re
    for mod_name, path in [
            ("paddle2_tpu.nn", f"{REF}/nn/__init__.py"),
            ("paddle2_tpu.nn.functional",
             f"{REF}/nn/functional/__init__.py")]:
        if not os.path.isfile(path):
            pytest.skip(f"the reference's {path} is not on this host")
        ref = open(path).read()
        m = re.search(r"__all__ = \[(.*?)\]", ref, re.S)
        names = set(re.findall(r"['\"](\w+)['\"]", m.group(1)))
        import importlib
        ours = set(dir(importlib.import_module(mod_name)))
        assert names - ours == set(), f"{mod_name} missing {names - ours}"


def test_max_pool_mask_points_at_argmax_and_unpool_roundtrips():
    x = np.random.RandomState(0).randn(2, 3, 8, 8).astype(np.float32)
    out, mask = F.max_pool2d(paddle.to_tensor(x), 2, 2, return_mask=True)
    o, m = out.numpy(), mask.numpy()
    for n in range(2):
        for c in range(3):
            for i in range(4):
                for j in range(4):
                    win = x[n, c, 2 * i:2 * i + 2, 2 * j:2 * j + 2]
                    assert np.isclose(o[n, c, i, j], win.max())
                    fi = m[n, c, i, j]
                    assert np.isclose(x[n, c, fi // 8, fi % 8], win.max())
    up = F.max_unpool2d(out, mask, 2, 2)
    assert tuple(up.shape) == (2, 3, 8, 8)
    nz = up.numpy()
    # unpool scatters exactly the pooled values, zeros elsewhere
    assert np.isclose(np.sort(nz[nz != 0].ravel()),
                      np.sort(o.ravel())).all()
    layer = nn.MaxUnPool2D(2, 2)
    np.testing.assert_allclose(layer(out, mask).numpy(), up.numpy())


def test_max_pool1d_3d_masks():
    x = np.random.RandomState(1).randn(1, 2, 12).astype(np.float32)
    out, mask = F.max_pool1d(paddle.to_tensor(x), 3, 3, return_mask=True)
    for c in range(2):
        for i in range(4):
            assert x[0, c, mask.numpy()[0, c, i]] == out.numpy()[0, c, i]
    x3 = np.random.RandomState(2).randn(1, 1, 4, 4, 4).astype(np.float32)
    out3, mask3 = F.max_pool3d(paddle.to_tensor(x3), 2, 2,
                               return_mask=True)
    flat = x3[0, 0].ravel()
    assert np.allclose(flat[mask3.numpy()[0, 0].ravel()],
                       out3.numpy()[0, 0].ravel())
    up3 = F.max_unpool3d(out3, mask3, 2, 2)
    assert tuple(up3.shape) == (1, 1, 4, 4, 4)


def test_fractional_max_pool_reference_doc_example():
    """pooling.py:2119 worked example: len 7 -> 5 bins at u=0.3."""
    seq = np.array([2, 4, 3, 1, 5, 2, 3], np.float32).reshape(1, 1, 1, 7)
    out = F.fractional_max_pool2d(paddle.to_tensor(seq), (1, 5),
                                  random_u=0.3)
    np.testing.assert_allclose(out.numpy().ravel(), [2, 4, 1, 5, 3])
    out2, mask = F.fractional_max_pool2d(paddle.to_tensor(seq), (1, 5),
                                         random_u=0.3, return_mask=True)
    # mask holds flat indices of each bin's max
    np.testing.assert_array_equal(mask.numpy().ravel(), [0, 1, 3, 4, 6])
    layer = nn.FractionalMaxPool3D((1, 1, 3), random_u=0.5)
    y = layer(paddle.randn([1, 1, 2, 2, 9]))
    assert tuple(y.shape) == (1, 1, 1, 1, 3)


def test_lp_pool_is_p_norm_over_windows():
    x1 = np.arange(8, dtype=np.float32).reshape(1, 1, 8)
    lp = F.lp_pool1d(paddle.to_tensor(x1), 2, 2, 2)
    exp = np.sqrt((x1.reshape(1, 1, 4, 2) ** 2).sum(-1))
    np.testing.assert_allclose(lp.numpy(), exp, rtol=1e-5)
    layer = nn.LPPool2D(3, 2, 2)
    x2 = paddle.randn([1, 2, 4, 4])
    y = layer(x2)
    exp2 = ((np.abs(x2.numpy()).reshape(1, 2, 2, 2, 2, 2) ** 3)
            .transpose(0, 1, 2, 4, 3, 5).reshape(1, 2, 2, 2, 4)
            .sum(-1)) ** (1 / 3)
    np.testing.assert_allclose(y.numpy(), exp2, rtol=1e-4)


def test_hsigmoid_matches_bit_code_walk():
    """matrix_bit_code.h SimpleCode: row (c>>(j+1))-1, bit (c>>j)&1."""
    rng = np.random.RandomState(0)
    NC, D, N = 6, 4, 3
    x = rng.randn(N, D).astype(np.float32)
    w = rng.randn(NC - 1, D).astype(np.float32)
    b = rng.randn(NC - 1).astype(np.float32)
    lab = np.array([0, 3, 5])
    loss = F.hsigmoid_loss(paddle.to_tensor(x), paddle.to_tensor(lab), NC,
                           paddle.to_tensor(w), paddle.to_tensor(b))

    def ref_one(xi, l):
        c = l + NC
        tot, j = 0.0, 0
        while (c >> (j + 1)) > 0:
            row = (c >> (j + 1)) - 1
            bit = (c >> j) & 1
            z = np.clip(w[row] @ xi + b[row], -40, 40)
            tot += np.log1p(np.exp(z)) - bit * z
            j += 1
        return tot

    exp = np.array([[ref_one(x[i], lab[i])] for i in range(N)])
    np.testing.assert_allclose(loss.numpy(), exp, rtol=1e-4)


def test_hsigmoid_layer_trains():
    paddle.seed(0)
    import paddle2_tpu.optimizer as opt
    m = nn.HSigmoidLoss(8, 4)
    o = opt.Adam(learning_rate=0.1, parameters=m.parameters())
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(16, 8).astype(np.float32))
    lab = paddle.to_tensor(np.arange(16) % 4)
    first = last = None
    for _ in range(30):
        loss = m(x, lab).mean()
        loss.backward()
        o.step()
        o.clear_grad()
        last = float(loss.numpy())
        first = first if first is not None else last
    assert last < 0.5 * first


def test_adaptive_log_softmax_normalizes_and_custom_path():
    rng = np.random.RandomState(1)
    D, short = 5, 3
    cutoffs = [3, 7]
    hw = paddle.to_tensor(rng.randn(D, short + 2).astype(np.float32))
    hb = paddle.to_tensor(rng.randn(short + 2).astype(np.float32))
    tails = [[paddle.to_tensor(rng.randn(D, 3).astype(np.float32)),
              paddle.to_tensor(rng.randn(3, 4).astype(np.float32))],
             [paddle.to_tensor(rng.randn(D, 2).astype(np.float32)),
              paddle.to_tensor(rng.randn(2, 3).astype(np.float32))]]
    xq = paddle.to_tensor(rng.randn(1, D).astype(np.float32))
    tot = 0.0
    for c in range(10):
        out, _ = F.adaptive_log_softmax_with_loss(
            xq, paddle.to_tensor(np.array([c])), hw, tails, cutoffs, hb)
        tot += np.exp(out.numpy()[0])
    np.testing.assert_allclose(tot, 1.0, rtol=1e-4)
    layer = nn.AdaptiveLogSoftmaxWithLoss(6, 12, [4, 8], head_bias=True)
    lp = layer.log_prob(paddle.randn([3, 6]))
    np.testing.assert_allclose(np.exp(lp.numpy()).sum(1), 1.0, rtol=1e-4)
    pred = layer.predict(paddle.randn([3, 6]))
    assert tuple(pred.shape) == (3,)


def test_rnnt_loss_matches_alignment_enumeration():
    rng = np.random.RandomState(0)
    B, T, U1, V = 1, 3, 2, 3
    logits = rng.randn(B, T, U1, V).astype(np.float32)
    labels = np.array([[1]], np.int32)
    loss = F.rnnt_loss(paddle.to_tensor(logits), paddle.to_tensor(labels),
                       paddle.to_tensor(np.array([3])),
                       paddle.to_tensor(np.array([1])),
                       blank=0, fastemit_lambda=0.0, reduction="none")
    lp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    total = -np.inf
    for emit_t in range(T):
        s = sum(lp[0, t, 0, 0] for t in range(emit_t))
        s += lp[0, emit_t, 0, 1]
        s += sum(lp[0, t, 1, 0] for t in range(emit_t, T))
        total = np.logaddexp(total, s)
    np.testing.assert_allclose(loss.numpy()[0], -total, rtol=1e-4)


def test_rnnt_loss_grad_and_fastemit_value_invariance():
    import jax
    rng = np.random.RandomState(1)
    logits = paddle.to_tensor(rng.randn(2, 4, 3, 5).astype(np.float32),
                              stop_gradient=False)
    labels = paddle.to_tensor(np.array([[1, 2], [3, 0]], np.int32))
    tl = paddle.to_tensor(np.array([4, 3]))
    ul = paddle.to_tensor(np.array([2, 1]))
    l0 = F.rnnt_loss(logits.detach(), labels, tl, ul, fastemit_lambda=0.0)
    l1 = F.rnnt_loss(logits.detach(), labels, tl, ul,
                     fastemit_lambda=0.01)
    # fastemit scales gradients, not the loss value
    np.testing.assert_allclose(l0.numpy(), l1.numpy(), rtol=1e-5)
    loss = F.rnnt_loss(logits, labels, tl, ul)
    loss.backward()
    g = logits.grad.numpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0
    r = nn.RNNTLoss(reduction="sum")
    s = r(logits.detach(), labels, tl, ul)
    assert s.shape == []


def test_beam_search_decoder_prefers_high_prob_tokens():
    paddle.seed(0)
    V, H, B, beam = 6, 4, 2, 3

    class Biased(nn.Layer):
        """Cell whose logits strongly favor token 4 then end (1)."""

        def __init__(self):
            super().__init__()
            self.lin = nn.Linear(H, H)

        def __call__(self, inputs, states):
            out = self.lin(states)
            return out, out

        def get_initial_states(self, ref):
            return paddle.zeros([B * beam, H]) if False else \
                paddle.zeros([B, H])

    bias = np.full((V,), -5.0, np.float32)
    bias[4] = 5.0
    proj_w = paddle.to_tensor(np.zeros((H, V), np.float32))
    proj_b = paddle.to_tensor(bias)

    def output_fn(cell_out):
        return cell_out @ paddle.to_tensor(np.zeros((H, V), np.float32)) \
            + proj_b

    emb = nn.Embedding(V, H)
    cell_obj = Biased()
    dec = nn.BeamSearchDecoder(cell_obj, start_token=0, end_token=1,
                               beam_size=beam, embedding_fn=emb,
                               output_fn=output_fn)
    ids = nn.dynamic_decode(dec, paddle.zeros([B, H]), max_step_num=4)
    assert tuple(ids.shape) == (B, 4, beam)
    # the top beam repeats the dominant token
    assert (ids.numpy()[:, :, 0] == 4).all()


def test_flashmask_attention_document_mask():
    rng = np.random.RandomState(0)
    B, S, H, D = 1, 6, 2, 4
    q = rng.randn(B, S, H, D).astype(np.float32)
    k = rng.randn(B, S, H, D).astype(np.float32)
    v = rng.randn(B, S, H, D).astype(np.float32)
    starts = np.array([3, 3, 3, 6, 6, 6], np.int32).reshape(1, 1, S, 1)
    out = F.flashmask_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                paddle.to_tensor(v),
                                paddle.to_tensor(starts), causal=True)
    i = np.arange(S)[:, None]
    j = np.arange(S)[None, :]
    mask = (i < j) | (i >= starts[0, 0, :, 0][None, :])
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    s = np.where(mask[None, None], -np.inf, s)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    exp = np.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(out.numpy(), exp, rtol=1e-4, atol=1e-5)
    # document masking == per-document causal attention
    doc0 = F.flash_attention.flash_attention(
        paddle.to_tensor(q[:, :3]), paddle.to_tensor(k[:, :3]),
        paddle.to_tensor(v[:, :3]), causal=True)
    if isinstance(doc0, tuple):
        doc0 = doc0[0]
    np.testing.assert_allclose(out.numpy()[:, :3], doc0.numpy(),
                               rtol=1e-3, atol=1e-4)


def test_sparse_attention_csr_pattern():
    rng = np.random.RandomState(2)
    qs = rng.randn(1, 1, 4, 4).astype(np.float32)
    ks = rng.randn(1, 1, 4, 4).astype(np.float32)
    vs = rng.randn(1, 1, 4, 4).astype(np.float32)
    offset = np.array([0, 1, 3, 5, 7], np.int32).reshape(1, 1, 5)
    cols = np.array([0, 0, 1, 0, 2, 0, 3], np.int32).reshape(1, 1, 7)
    o = F.sparse_attention(paddle.to_tensor(qs), paddle.to_tensor(ks),
                           paddle.to_tensor(vs), paddle.to_tensor(offset),
                           paddle.to_tensor(cols))
    allow = np.zeros((4, 4), bool)
    for r in range(4):
        for e in range(offset[0, 0, r], offset[0, 0, r + 1]):
            allow[r, cols[0, 0, e]] = True
    s = np.einsum("bhqd,bhkd->bhqk", qs, ks) / 2.0
    s = np.where(allow[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    exp = np.einsum("bhqk,bhkd->bhqd", p, vs)
    np.testing.assert_allclose(o.numpy(), exp, rtol=1e-4, atol=1e-5)
