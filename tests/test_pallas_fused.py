"""Fused pallas kernels (kernels/pallas_fused.py) vs XLA references.

Microbench results recorded on v5e (see module docstrings): rope wins
2.23x in the [B,S,H,D] layout; XLA's own fusion wins for adamw (2.3x)
and rmsnorm (1.2x) — those kernels exist for reference parity and are
not wired into default paths.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.kernels import pallas_fused as pf


def test_fused_adamw_matches_reference():
    rs = np.random.RandomState(0)
    p = jnp.asarray(rs.randn(10, 100) * 0.1, jnp.bfloat16)
    mst = p.astype(jnp.float32)
    g = jnp.asarray(rs.randn(10, 100) * 0.01, jnp.bfloat16)
    m = jnp.asarray(rs.randn(10, 100) * 0.001, jnp.float32)
    v = jnp.abs(jnp.asarray(rs.randn(10, 100) * 1e-4, jnp.float32))
    po, mo, vo, wo = pf.fused_adamw(p, g, m, v, mst, lr=1e-3, step=3,
                                    interpret=True)
    g32 = g.astype(jnp.float32)
    m_ref = 0.9 * m + 0.1 * g32
    v_ref = 0.999 * v + 0.001 * g32 * g32
    mh = m_ref / (1 - 0.9 ** 3)
    vh = v_ref / (1 - 0.999 ** 3)
    w_ref = mst - 1e-3 * (mh / (jnp.sqrt(vh) + 1e-8) + 0.01 * mst)
    np.testing.assert_allclose(np.asarray(wo), np.asarray(w_ref),
                               rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(np.asarray(mo), np.asarray(m_ref),
                               rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(v_ref),
                               rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(np.asarray(po, np.float32),
                               np.asarray(w_ref.astype(jnp.bfloat16),
                                          np.float32))


def test_fused_rms_norm_fwd_bwd():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(64, 128) * 0.5, jnp.float32)
    w = jnp.asarray(rs.randn(128) * 0.1 + 1.0, jnp.float32)

    def ref(x, w):
        ms = jnp.mean(x * x, -1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6) * w

    o = pf.fused_rms_norm(x, w, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref(x, w)),
                               rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda x, w: pf.fused_rms_norm(
        x, w, interpret=True).sum(), argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: ref(x, w).sum(), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(g1[0]), np.asarray(g2[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g1[1]), np.asarray(g2[1]),
                               rtol=1e-4, atol=1e-5)


def _angles(S, D, neox):
    inv = 1.0 / (10000 ** (np.arange(0, D, 2) / D))
    ang = np.arange(S)[:, None] * inv[None]
    if neox:
        return np.repeat(ang, 2, axis=1)
    return np.concatenate([ang, ang], -1)


def test_fused_rope_kernel_and_vjp():
    rs = np.random.RandomState(0)
    B, S, H, D = 2, 16, 4, 32
    x = jnp.asarray(rs.randn(B, S, H, D) * 0.3, jnp.float32)
    full = _angles(S, D, neox=False)
    cos = jnp.asarray(np.cos(full), jnp.float32)
    sin = jnp.asarray(np.sin(full), jnp.float32)

    def xla_rope(x):
        rot = jnp.concatenate([-x[..., D // 2:], x[..., : D // 2]], -1)
        return (x * cos[None, :, None, :]
                + rot * sin[None, :, None, :])

    o = pf.fused_rope(x, cos, sin, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(xla_rope(x)),
                               rtol=1e-5, atol=1e-6)
    g1 = jax.grad(lambda x: (pf.fused_rope(
        x, cos, sin, interpret=True) ** 2).sum())(x)
    g2 = jax.grad(lambda x: (xla_rope(x) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-5)


class TestFusedRopeAPI:
    def test_half_split_and_neox(self):
        rs = np.random.RandomState(0)
        from paddle2_tpu.incubate.nn.functional import (
            fused_rotary_position_embedding)
        B, S, H, D = 2, 16, 4, 32
        q = paddle.to_tensor(rs.randn(B, S, H, D).astype(np.float32))
        k = paddle.to_tensor(rs.randn(B, S, H, D).astype(np.float32))
        x = np.asarray(q._data)

        qo, ko, vo = fused_rotary_position_embedding(
            q, k, use_neox_rotary_style=False)
        assert vo is None
        full = _angles(S, D, neox=False)
        cos = full * 0 + np.cos(full)
        sin = np.sin(full)
        ref = (x * cos[None, :, None, :]
               + np.concatenate([-x[..., D // 2:], x[..., : D // 2]], -1)
               * sin[None, :, None, :])
        np.testing.assert_allclose(np.asarray(qo._data), ref,
                                   rtol=1e-4, atol=1e-5)

        qo2, _, _ = fused_rotary_position_embedding(
            q, use_neox_rotary_style=True)
        full2 = _angles(S, D, neox=True)
        x1, x2 = x[..., 0::2], x[..., 1::2]
        rot = np.stack([-x2, x1], -1).reshape(x.shape)
        ref2 = (x * np.cos(full2)[None, :, None, :]
                + rot * np.sin(full2)[None, :, None, :])
        np.testing.assert_allclose(np.asarray(qo2._data), ref2,
                                   rtol=1e-4, atol=1e-5)

    def test_position_ids_and_grad(self):
        rs = np.random.RandomState(1)
        from paddle2_tpu.incubate.nn.functional import (
            fused_rotary_position_embedding)
        B, S, H, D = 2, 8, 2, 16
        q = paddle.to_tensor(rs.randn(B, S, H, D).astype(np.float32))
        q.stop_gradient = False
        pos = paddle.to_tensor(
            np.tile(np.arange(S)[::-1], (B, 1)).astype(np.int32))
        qo, _, _ = fused_rotary_position_embedding(
            q, position_ids=pos, use_neox_rotary_style=False)
        qo.sum().backward()
        assert q.grad is not None
        assert np.isfinite(q.grad.numpy()).all()

    def test_position_ids_beyond_seq_len(self):
        """ADVICE r3: positions >= seq_len (decode-loop use) must index a
        table sized to max(position_ids)+1 — with an S-row table JAX's
        clamped gather silently reuses the last row's rotation."""
        rs = np.random.RandomState(2)
        from paddle2_tpu.incubate.nn.functional import (
            fused_rotary_position_embedding)
        B, S, H, D = 1, 4, 2, 16
        offset = 100  # absolute positions far past seq_len
        q = paddle.to_tensor(rs.randn(B, S, H, D).astype(np.float32))
        pos = paddle.to_tensor(
            (np.arange(S)[None] + offset).astype(np.int64))
        qo, _, _ = fused_rotary_position_embedding(
            q, position_ids=pos, use_neox_rotary_style=False)
        # reference: rotate a longer sequence and slice the same window
        big_S = offset + S
        qbig = paddle.to_tensor(np.concatenate(
            [np.zeros((B, offset, H, D), np.float32), np.asarray(q._data)],
            axis=1))
        ref, _, _ = fused_rotary_position_embedding(
            qbig, use_neox_rotary_style=False)
        np.testing.assert_allclose(np.asarray(qo._data),
                                   np.asarray(ref._data)[:, offset:],
                                   rtol=1e-4, atol=1e-5)


def test_fused_adamw_step_eager_order_twin():
    """fused_adamw_step (the ISSUE-10 STEP kernel, distinct from the
    fuse-everything fused_adamw above) replicates the eager op ORDER:
    bitwise vs a jitted twin, including the decoupled-decay subtract
    against the pre-update param."""
    rs = np.random.RandomState(0)
    p = jnp.asarray(rs.randn(1000), jnp.float32)
    g = jnp.asarray(rs.randn(1000), jnp.float32)
    m = jnp.asarray(rs.rand(1000), jnp.float32)
    v = jnp.asarray(rs.rand(1000), jnp.float32)
    lr, step = jnp.float32(1e-3), jnp.int32(5)
    b1, b2, eps, wd = 0.9, 0.999, 1e-8, 0.01

    @jax.jit
    def twin(p, g, m, v, lr, step):
        t = step.astype(jnp.float32)
        em = b1 * m + (1 - b1) * g
        ev = b2 * v + (1 - b2) * jnp.square(g)
        ep = p - lr * (em / (1 - b1 ** t)) / (
            jnp.sqrt(ev / (1 - b2 ** t)) + eps)
        return ep - lr * wd * p, em, ev
    ref = [np.asarray(a).copy() for a in twin(p, g, m, v, lr, step)]
    out = pf.fused_adamw_step(p, g, m, v, lr, step, beta1=b1, beta2=b2,
                              eps=eps, weight_decay=wd)
    for o, r in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(o), r)


def test_fused_momentum_step_nesterov_twin():
    rs = np.random.RandomState(1)
    p = jnp.asarray(rs.randn(513), jnp.float32)   # forces padding
    g = jnp.asarray(rs.randn(513), jnp.float32)
    vel = jnp.asarray(rs.randn(513), jnp.float32)
    lr = jnp.float32(1e-2)
    mom, wd = 0.9, 0.01

    @jax.jit
    def twin(p, g, vel, lr):
        g2 = g + wd * p
        v = mom * vel + g2
        return p - lr * (g2 + mom * v), v
    ref = [np.asarray(a).copy() for a in twin(p, g, vel, lr)]
    out = pf.fused_momentum_step(p, g, vel, lr, momentum=mom,
                                 nesterov=True, weight_decay=wd)
    for o, r in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(o), r)


def test_fused_step_kernels_preserve_shape_and_dtype():
    rs = np.random.RandomState(2)
    p = jnp.asarray(rs.randn(7, 33), jnp.float32)   # 2-D, ragged
    g = jnp.asarray(rs.randn(7, 33), jnp.float32)
    m = jnp.zeros((7, 33), jnp.float32)
    v = jnp.zeros((7, 33), jnp.float32)
    np_, nm, nv = pf.fused_adamw_step(p, g, m, v, jnp.float32(1e-3),
                                      jnp.int32(1))
    assert np_.shape == (7, 33) and np_.dtype == jnp.float32
    assert nm.shape == (7, 33) and nv.shape == (7, 33)
