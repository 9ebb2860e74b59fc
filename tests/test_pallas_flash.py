"""Pallas flash-attention kernel vs the XLA attention path (OpTest-style
numerics; interpret mode on the CPU mesh). Parity target:
phi flash_attn_kernel.cu capability (causal, fwd+bwd)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle2_tpu  # noqa: F401  (sets matmul precision; kernels must cope)
from paddle2_tpu.kernels.attention import _sdpa_xla
from paddle2_tpu.kernels.pallas_flash import (flash_attention_bshd,
                                              supported)


def _rand(shape, dtype=jnp.float32, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_xla(causal):
    B, S, H, D = 2, 256, 4, 64
    q, k, v = (_rand((B, S, H, D), seed=i) for i in range(3))
    o1 = flash_attention_bshd(q, k, v, causal=causal, block_q=64,
                              block_k=64, interpret=True)
    o2 = _sdpa_xla(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_xla(causal):
    B, S, H, D = 1, 128, 2, 64
    q, k, v = (_rand((B, S, H, D), seed=i) for i in range(3))

    def loss_fl(q, k, v):
        o = flash_attention_bshd(q, k, v, causal=causal, block_q=64,
                                 block_k=64, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_xla(q, k, v):
        return jnp.sum(jnp.sin(_sdpa_xla(q, k, v, causal=causal)))

    g1 = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_flash_causal_rectangular_bottom_right():
    """Sq < Sk causal (chunked decode): diagonal is bottom-right aligned so
    every query sees the whole prefix — must match the XLA path."""
    B, Sq, Sk, H, D = 1, 64, 256, 2, 32
    q = _rand((B, Sq, H, D), seed=0)
    k = _rand((B, Sk, H, D), seed=1)
    v = _rand((B, Sk, H, D), seed=2)
    o1 = flash_attention_bshd(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
    o2 = _sdpa_xla(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)

    def loss_fl(q, k, v):
        o = flash_attention_bshd(q, k, v, causal=True, block_q=64,
                                 block_k=64, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_xla(q, k, v):
        return jnp.sum(jnp.sin(_sdpa_xla(q, k, v, causal=True)))

    g1 = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_flash_rectangular_and_blocks():
    # Sq != Sk (cross attention shape) with uneven block split
    B, Sq, Sk, H, D = 1, 128, 256, 2, 32
    q = _rand((B, Sq, H, D), seed=0)
    k = _rand((B, Sk, H, D), seed=1)
    v = _rand((B, Sk, H, D), seed=2)
    o1 = flash_attention_bshd(q, k, v, block_q=64, block_k=64,
                              interpret=True)
    o2 = _sdpa_xla(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_flash_bf16():
    B, S, H, D = 1, 128, 2, 64
    q, k, v = (_rand((B, S, H, D), jnp.bfloat16, seed=i) for i in range(3))
    o1 = flash_attention_bshd(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
    o2 = _sdpa_xla(q, k, v, causal=True)
    assert o1.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=3e-2)


def test_flash_unsupported_falls_back():
    # seq not divisible by the block -> silently uses the XLA path
    B, S, H, D = 1, 100, 2, 64
    q, k, v = (_rand((B, S, H, D), seed=i) for i in range(3))
    assert not supported(q.shape, k.shape, 64, 64)
    o1 = flash_attention_bshd(q, k, v, block_q=64, block_k=64)
    o2 = _sdpa_xla(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_sdpa_api_routes_and_grads():
    """paddle F.scaled_dot_product_attention stays differentiable through
    the kernel-selection wrapper."""
    import paddle2_tpu as paddle
    import paddle2_tpu.nn.functional as F
    q = paddle.to_tensor(np.random.RandomState(0)
                         .randn(1, 64, 2, 32).astype("float32"))
    q.stop_gradient = False
    out = F.scaled_dot_product_attention(q, q, q, is_causal=True)
    out.sum().backward()
    assert q.grad is not None and np.isfinite(q.grad.numpy()).all()


def test_functional_flash_attention_api():
    """F.flash_attention / qkvpacked / unpadded (reference
    flash_attention.py:195/:593 surface)."""
    import types
    import paddle2_tpu as paddle
    import paddle2_tpu.nn.functional as F
    # like the reference, F.flash_attention is the SUBMODULE; the function
    # lives inside it (PaddleNLP idiom: F.flash_attention.flash_attention)
    assert isinstance(F.flash_attention, types.ModuleType)
    fa = F.flash_attention.flash_attention
    rs = np.random.RandomState(0)
    q = paddle.to_tensor(rs.randn(2, 16, 2, 8).astype("float32"))
    out, sm = fa(q, q, q, causal=True)
    assert tuple(out.shape) == (2, 16, 2, 8) and sm is None
    out2, sm2 = fa(q, q, q, causal=True,
                   return_softmax=True)
    assert tuple(sm2.shape) == (2, 2, 16, 16)
    np.testing.assert_allclose(sm2.numpy().sum(-1), 1.0, rtol=1e-5)

    qkv = paddle.to_tensor(rs.randn(2, 16, 3, 2, 8).astype("float32"))
    o3, _ = F.flash_attn_qkvpacked(qkv, causal=True)
    assert tuple(o3.shape) == (2, 16, 2, 8)

    # varlen: two sequences of lengths 5 and 9 packed into 14 rows —
    # must equal per-sequence dense attention
    lens = [5, 9]
    total = sum(lens)
    packed = paddle.to_tensor(rs.randn(total, 2, 8).astype("float32"))
    cu = paddle.to_tensor(np.array([0, 5, 14], "int32"))
    out_v, _ = F.flash_attn_unpadded(packed, packed, packed, cu, cu,
                                     max_seqlen_q=9, max_seqlen_k=9,
                                     scale=1.0 / np.sqrt(8), causal=True)
    assert tuple(out_v.shape) == (total, 2, 8)
    from paddle2_tpu.kernels.attention import _sdpa_xla
    start = 0
    for L in lens:
        seq = packed._data[start:start + L][None]
        ref = _sdpa_xla(seq, seq, seq, causal=True)[0]
        np.testing.assert_allclose(
            np.asarray(out_v._data[start:start + L]), np.asarray(ref),
            rtol=1e-5, atol=1e-5)
        start += L

    with F.sdp_kernel(enable_flash=False):
        pass


def test_flash_unpadded_per_sequence_causal():
    """Regression: causal masking must use each sequence's OWN lengths,
    not the padded maxima (q/k length deltas differ per row)."""
    import paddle2_tpu as paddle
    import paddle2_tpu.nn.functional as F
    rs = np.random.RandomState(1)
    # seq0: len_q=2,len_k=2 (delta 0); seq1: len_q=2,len_k=5 (delta 3)
    q = paddle.to_tensor(rs.randn(4, 2, 8).astype("float32"))
    kv = paddle.to_tensor(rs.randn(7, 2, 8).astype("float32"))
    cu_q = paddle.to_tensor(np.array([0, 2, 4], "int32"))
    cu_k = paddle.to_tensor(np.array([0, 2, 7], "int32"))
    out, _ = F.flash_attn_unpadded(q, kv, kv, cu_q, cu_k, 2, 5,
                                   scale=1.0 / np.sqrt(8), causal=True)
    starts_q, starts_k, lens_q, lens_k = [0, 2], [0, 2], [2, 2], [2, 5]
    for i in range(2):
        qs = q._data[starts_q[i]:starts_q[i] + lens_q[i]][None]
        ks = kv._data[starts_k[i]:starts_k[i] + lens_k[i]][None]
        ref = _sdpa_xla(qs, ks, ks, causal=True)[0]
        np.testing.assert_allclose(
            np.asarray(out._data[starts_q[i]:starts_q[i] + lens_q[i]]),
            np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_sdp_kernel_disables_flash():
    import paddle2_tpu.nn.functional as F
    from paddle2_tpu.kernels import attention as att
    assert att.flash_enabled()
    with F.sdp_kernel(enable_flash=False):
        assert not att.use_pallas((1, 4096, 8, 64))
    assert att.flash_enabled()
    import pytest as _pytest
    with _pytest.raises(ValueError):
        F.sdp_kernel(enable_math=False)


def test_block_sizes_self_fit_to_sequence():
    """Requested blocks are preferences: any 8-row-divisible S tiles
    correctly even when the default/bwd-override block does not divide it
    (regression: silent wrong-grid grads with bwd env overrides)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle2_tpu.kernels import pallas_flash as pf
    from paddle2_tpu.kernels.attention import _sdpa_xla

    assert pf._fit_block(1536, 1024) == 512
    assert pf._fit_block(384, 1024) == 128
    assert pf._fit_block(136, 512) == 8
    assert pf._fit_block(135, 512) is None

    rs = np.random.RandomState(0)
    S = 384
    q = jnp.asarray(rs.randn(1, S, 2, 64) * 0.1, jnp.float32)
    k = jnp.asarray(rs.randn(1, S, 2, 64) * 0.1, jnp.float32)
    v = jnp.asarray(rs.randn(1, S, 2, 64) * 0.1, jnp.float32)
    assert pf.supported(q.shape, k.shape, block_q=1024, block_k=1024)
    o = pf.flash_attention_bshd(q, k, v, causal=True,
                                block_q=1024, block_k=1024)
    ref = _sdpa_xla(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=5e-3, atol=5e-3)
    g = jax.grad(lambda q: pf.flash_attention_bshd(
        q, k, v, causal=True, block_q=1024, block_k=1024).sum())(q)
    gref = jax.grad(lambda q: _sdpa_xla(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                               rtol=5e-3, atol=5e-3)


class TestVarlenPacked:
    """flash_attention_varlen_packed: segment-masked packed kernel vs the
    per-sequence dense reference, and the flash_attn_unpadded packed
    dispatch vs the densify path."""

    def _packed_case(self, lens, causal, seed=0):
        import jax
        import jax.numpy as jnp
        from paddle2_tpu.kernels.pallas_flash import (
            flash_attention_varlen_packed)
        from paddle2_tpu.kernels.attention import _sdpa_xla
        rs = np.random.RandomState(seed)
        H, D = 2, 16
        T = sum(lens)
        q = jnp.asarray(rs.randn(T, H, D) * 0.2, jnp.float32)
        k = jnp.asarray(rs.randn(T, H, D) * 0.2, jnp.float32)
        v = jnp.asarray(rs.randn(T, H, D) * 0.2, jnp.float32)
        cu = np.concatenate([[0], np.cumsum(lens)])
        seg = np.concatenate([np.full(n, i, np.int32)
                              for i, n in enumerate(lens)])
        off = np.concatenate([np.arange(n, dtype=np.int32) for n in lens])
        Tp = -(-T // 8) * 8
        seg_q = np.concatenate([seg, np.full(Tp - T, -1, np.int32)])
        seg_k = np.concatenate([seg, np.full(Tp - T, -2, np.int32)])
        off_p = np.concatenate([off, np.zeros(Tp - T, np.int32)])
        off_q = off_p if causal else np.full_like(off_p, 2 ** 30)

        def pad(a):
            return jnp.concatenate(
                [a, jnp.zeros((Tp - T, H, D), a.dtype)], axis=0)

        def f(q, k, v):
            return flash_attention_varlen_packed(
                pad(q), pad(k), pad(v), seg_q, off_q, seg_k, off_p,
                interpret=True)[:T]

        out = f(q, k, v)
        refs = [
            _sdpa_xla(q[None, int(cu[i]):int(cu[i + 1])],
                      k[None, int(cu[i]):int(cu[i + 1])],
                      v[None, int(cu[i]):int(cu[i + 1])],
                      causal=causal)[0]
            for i in range(len(lens))]
        ref = jnp.concatenate(refs, axis=0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-3, atol=5e-3)
        g = jax.grad(lambda q: f(q, k, v).astype(jnp.float32).sum())(q)
        gref = jax.grad(lambda q: jnp.concatenate([
            _sdpa_xla(q[None, int(cu[i]):int(cu[i + 1])],
                      k[None, int(cu[i]):int(cu[i + 1])],
                      v[None, int(cu[i]):int(cu[i + 1])],
                      causal=causal)[0]
            for i in range(len(lens))], axis=0).astype(jnp.float32).sum())(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                                   rtol=5e-3, atol=5e-3)

    def test_causal_ragged(self):
        self._packed_case([5, 12, 3, 8], causal=True)

    def test_noncausal_ragged(self):
        self._packed_case([7, 2, 15], causal=False)

    def test_unpadded_packed_matches_densify(self):
        """flash_attn_unpadded's packed dispatch == its densify path."""
        import jax.numpy as jnp
        import paddle2_tpu as paddle
        import paddle2_tpu.nn.functional as F
        from paddle2_tpu.nn.functional import flash_attention as fa_mod
        rs = np.random.RandomState(1)
        lens = [6, 10, 4]
        T, H, D = sum(lens), 2, 16
        cu = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
        q = paddle.to_tensor(rs.randn(T, H, D).astype(np.float32) * 0.3)
        k = paddle.to_tensor(rs.randn(T, H, D).astype(np.float32) * 0.3)
        v = paddle.to_tensor(rs.randn(T, H, D).astype(np.float32) * 0.3)
        cu_t = paddle.to_tensor(cu)
        dense, _ = F.flash_attn_unpadded(
            q, k, v, cu_t, cu_t, max(lens), max(lens),
            scale=1.0 / np.sqrt(D), causal=True)
        packed = fa_mod._unpadded_packed(
            q, k, v, cu.astype(np.int64), cu.astype(np.int64),
            np.diff(cu).astype(np.int64), np.diff(cu).astype(np.int64),
            1.0 / np.sqrt(D), True)
        np.testing.assert_allclose(np.asarray(packed._data),
                                   np.asarray(dense._data),
                                   rtol=5e-3, atol=5e-3)


# ------------------------------------------------------------ the walk
# One grid step a (batch row, head), the tiles walked inside it: the
# forward by q tiles against row strips, the backward by k tiles against
# column strips (kernels/pallas_flash.py, `_row_strips` / `_col_strips`).

def _staircase_entries(Sq, Sk, tq, tk, block):
    """(entries of the tiles holding a seen entry, entries of the tiles
    the staircase crosses, tiles of the square, of the first kind), by
    brute force over the dense mask the XLA path builds."""
    rows = np.arange(Sq)[:, None] + (Sk - Sq)
    keep = (rows | (block - 1)) >= np.arange(Sk)[None, :] if block \
        else np.ones((Sq, Sk), bool)
    t = keep.reshape(Sq // tq, tq, Sk // tk, tk)
    some, every = t.any((1, 3)), t.all((1, 3))
    return (int(some.sum()) * tq * tk, int((some & ~every).sum()) * tq * tk,
            some.size, int(some.sum()))


WALK_CASES = {
    # name: (B, Sq, Sk, H, D, causal, causal_block, tile)
    "causal_s512_tile128": (1, 512, 512, 2, 64, True, 1, 128),
    "cell_s1024_d64": (1, 1024, 1024, 2, 64, True, 1, 1024),
    "block4_d128": (1, 512, 512, 2, 128, True, 4, 128),
    "sq_lt_sk_bottom_right": (1, 256, 512, 2, 64, True, 1, 128),
    "non_causal": (1, 256, 512, 2, 64, False, 1, 128),
    "one_tile": (1, 128, 128, 2, 64, True, 1, 1024),
}


@pytest.mark.parametrize("name", sorted(WALK_CASES))
def test_walk_matches_xla_forward_and_gradients(name, monkeypatch):
    from paddle2_tpu.kernels import pallas_flash as pf
    B, Sq, Sk, H, D, causal, cb, tile = WALK_CASES[name]
    block = cb if causal else 0
    tiles = {back: pf._walks(Sq, Sk, D, jnp.float32, block, tile, tile, back)
             for back in (False, True)}
    assert all(tiles.values()), "not the walk's shape"
    if name == "cell_s1024_d64":    # the shape rule's own tiles, several
        assert tiles == {False: (pf.FWD_WALK_TILE,) * 2,
                         True: (pf.BWD_WALK_TILE,) * 2}
        assert Sq // pf.FWD_WALK_TILE > 1
    # what the two bodies trace: the entries of every product of a score
    # tile's shape (QK^T; in the backward dO V^T too) and of every mask
    seen = {"scored": 0, "masked": 0}
    dot, masked = pf._dot, pf._masked

    def counting_dot(a, b, contract):
        if contract == pf._NT and a.shape[1] == D and b.shape[1] == D:
            seen["scored"] += a.shape[0] * b.shape[0]
        return dot(a, b, contract)

    def counting_masked(s, *a):
        seen["masked"] += s.size
        return masked(s, *a)

    monkeypatch.setattr(pf, "_dot", counting_dot)
    monkeypatch.setattr(pf, "_masked", counting_masked)
    pf._JIT_CACHE.clear()
    q = _rand((B, Sq, H, D), seed=0)
    k, v = _rand((B, Sk, H, D), seed=1), _rand((B, Sk, H, D), seed=2)
    w = _rand((B, Sq, H, D), seed=3)

    def flash(q, k, v):
        return flash_attention_bshd(q, k, v, causal=causal, block_q=tile,
                                    block_k=tile, causal_block=cb,
                                    interpret=True)

    def dense(q, k, v):
        return _sdpa_xla(q, k, v, causal=causal, causal_block=cb)

    try:
        got = flash(q, k, v)
        fwd_seen = dict(seen)
        g1 = jax.grad(lambda *a: (flash(*a) * w).sum(), (0, 1, 2))(q, k, v)
    finally:
        pf._JIT_CACHE.clear()
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense(q, k, v)),
                               atol=2e-5)
    g2 = jax.grad(lambda *a: (dense(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)
    # the mechanism's counter: the forward's strips cover the tiles on
    # or under the staircase and no other, and only the crossed ones
    # build a mask. The gradient ran the forward once more (for the
    # residuals) and the backward's own walk over ITS tiles, with two
    # products of a score tile's shape each and one mask a crossed tile
    f_scored, f_masked, f_square, f_run = _staircase_entries(
        Sq, Sk, *tiles[False], block)
    b_scored, b_masked, _, _ = _staircase_entries(Sq, Sk, *tiles[True], block)
    assert fwd_seen == {"scored": f_scored, "masked": f_masked}
    assert seen["scored"] - 2 * f_scored == 2 * b_scored
    assert seen["masked"] - 2 * f_masked == b_masked
    if causal and f_square > 1:
        assert f_masked < f_scored < Sq * Sk
    if name == "causal_s512_tile128":
        assert (f_run, f_masked // 128 ** 2, f_square) == (10, 4, 16)


def test_walk_gives_way_to_the_grid_when_a_head_does_not_fit(monkeypatch):
    """The shape rule: the walk while a head fits VMEM by its own
    reckoning, the grid kernels beyond — and wherever rows see nothing
    (causal, Sq > Sk) or a forced tile is not lane-aligned."""
    from paddle2_tpu.kernels import pallas_flash as pf
    bf16 = jnp.bfloat16
    # the four cells' shapes, forward (and the trainer's backward)
    assert pf._walks(1024, 1024, 64, bf16, 1, 1024, 1024, True)
    assert pf._walks(3072, 3072, 64, bf16, 1, 1024, 1024, False)
    assert pf._walks(2048, 2048, 128, bf16, 4, 1024, 1024, False)
    assert pf._walks(2048, 2048, 128, bf16, 4, 1024, 1024, True)
    assert pf._walks(8192, 8192, 64, bf16, 1, 1024, 1024, False) is None
    assert pf._walks(4096, 4096, 64, bf16, 1, 1024, 1024, True) is None
    assert pf._walks(512, 256, 64, bf16, 1, 1024, 1024, False) is None
    assert pf._walks(512, 256, 64, bf16, 0, 1024, 1024, False)
    assert pf._walks(256, 256, 64, bf16, 1, 64, 64, False) is None
    # the forward's tile streams its own rows past each K/V tile and is
    # the larger; the backward's streams whole strips
    assert pf._walks(1024, 1024, 64, bf16, 1, 1024, 1024, False) == (512, 512)
    assert pf._walks(1024, 1024, 64, bf16, 1, 1024, 1024, True) == (256, 256)
    # and the grid path still answers: same numbers, other kernels
    monkeypatch.setattr(pf, "WALK_VMEM_BYTES", 0)
    pf._JIT_CACHE.clear()
    q, k, v = (_rand((1, 256, 2, 64), seed=i) for i in range(3))
    try:
        o = flash_attention_bshd(q, k, v, causal=True, block_q=128,
                                 block_k=128, interpret=True)
        g = jax.grad(lambda q: flash_attention_bshd(
            q, k, v, causal=True, block_q=128, block_k=128,
            interpret=True).sum())(q)
    finally:
        pf._JIT_CACHE.clear()
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(_sdpa_xla(q, k, v, causal=True)),
        atol=2e-5)
    gref = jax.grad(lambda q: _sdpa_xla(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref), atol=5e-5)
