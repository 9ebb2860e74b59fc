"""The sliding layers' band as a kernel (``kernels/pallas_band.py``,
``window_fwd``), interpreted: its values against the definition (the
dense ``[S, S]`` band mask of ``test_exaone_moe.py``) over sequences
below, at and above the window and off the tile, 1 / 2 / 8 query heads a
key/value head, in float32 and in bfloat16 (as far from the float32 band
as the einsum form is, and no further); a window one short fails the
same comparison; the gradient through the model's ``custom_vjp`` is the
einsum form's; which form a sliding layer takes, from what it sees; a
sliding layer's ``full`` through the kernel against the einsums."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle2_tpu.kernels import _platform, pallas_band
from paddle2_tpu.models import exaone_moe as em
from paddle2_tpu.models import exaone_moe_tiny
from paddle2_tpu.models._decoder import (rms_head, rope_tables,
                                         rotate_half_rope)
from test_exaone_moe import dense_band

F32 = jnp.float32


def draw(seq, heads, kv_heads, hd=16, batch=2, dtype=F32):
    rng = np.random.default_rng(seq * 64 + heads)
    return [jnp.asarray(rng.normal(size=(batch, seq, h, hd)), dtype)
            for h in (heads, kv_heads, kv_heads)]


def gap(a, b):
    return float(jnp.abs(a.astype(F32) - b.astype(F32)).max())


def band(q, k, v, window, **kw):
    """The kernel on ``[B, S, heads, hd]`` arrays: it takes and gives
    the heads side by side in the lanes."""
    B, S, _, hd = q.shape
    flat = [x.reshape(B, S, -1) for x in (q, k, v)]
    return pallas_band.band_attention(*flat, window, hd, **kw).reshape(
        q.shape)


# seq, window, query heads, key/value heads, rows of a q block
CASES = [(5, 8, 4, 2, None), (8, 8, 4, 2, None), (9, 8, 4, 2, None),
         (64, 8, 2, 2, None), (37, 16, 4, 2, None), (128, 128, 4, 2, None),
         (256, 128, 8, 1, None), (300, 128, 4, 2, None),
         (300, 128, 16, 2, 256), (640, 128, 4, 2, 512),
         (700, 100, 8, 8, 256), (513, 200, 4, 4, 512)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seq,window,heads,kv_heads,block", CASES)
def test_band_kernel_equals_the_dense_band_mask(seq, window, heads,
                                                kv_heads, block, dtype):
    q, k, v = draw(seq, heads, kv_heads, dtype=dtype)
    exact = [x.astype(F32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        got = band(q, k, v, window, block_rows=block)
        short = band(q, k, v, window - 1, block_rows=block)
        want = dense_band(*exact, window)
        einsums = em.local_window_attention(q, k, v, window)
    assert got.shape == q.shape and got.dtype == q.dtype
    # float32: the einsum form's own tolerance; bfloat16: as far from the
    # float32 band as the einsum form lies, and half as far again
    limit = 2e-5 if dtype == "float32" else 1.5 * gap(einsums, want)
    assert gap(got, want) <= limit
    # below the window no position sees ``window`` keys; one key of 100
    # and more weighs less than a bfloat16 rounding
    if seq >= window and (dtype == "float32" or window <= 16):
        assert gap(short, want) > 10 * limit


def test_tiles_follow_the_window_the_sequence_the_group_and_vmem():
    tiles = pallas_band.band_tiles
    # the cell's shapes: a tile of 128, eight heads a step, no padding,
    # the step within the budget
    for seq in (512, 2048, 8192):
        assert tiles(seq, 64, 8, 128, 128, 2) == (
            128, pallas_band.BLOCK_ROWS, 8, seq)
    assert pallas_band._band_bytes(pallas_band.BLOCK_ROWS, 128, 8, 128, 2) \
        <= pallas_band.VMEM_BYTES
    # a window off the lanes is rounded up, a sequence off the tile
    # padded, a block is whole tiles that divide the padded sequence
    assert tiles(300, 4, 2, 100, 16, 4) == (128, 384, 2, 384)
    assert tiles(1280, 4, 2, 200, 16, 4) == (256, 256, 2, 1280)
    assert tiles(1280, 4, 2, 200, 16, 4, 128) == (256, 256, 2, 1280)
    # a group too large for one product is cut into steps of whole heads
    sub, tq, heads, _ = tiles(1024, 64, 1, 128, 128, 2)
    assert heads * sub == pallas_band.STACK_ROWS and 64 % heads == 0
    # heads twice as wide in float32: the block shrinks to fit
    assert tiles(8192, 64, 8, 128, 256, 4)[1] == 128


def sliding_inputs(seq, heads=4, kv_heads=2, hd=16, dtype=F32):
    """(q as projected, gain, k, v, cos, sin) of a sliding layer."""
    q, k, v = draw(seq, heads, kv_heads, hd, dtype=dtype)
    rng = np.random.default_rng(seq)
    gain = jnp.asarray(1 + 0.1 * rng.normal(size=(hd,)), F32)
    cos, sin = rope_tables(jnp.arange(seq), hd, 10000.0)
    return q.reshape(2, seq, -1), gain, k, v, cos, sin


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seq,window,block", [(5, 8, None), (40, 8, None),
                                              (300, 128, 256)])
def test_norm_and_rotation_in_the_step_equal_plain_xla(seq, window, block,
                                                       dtype):
    """q normed and rotated inside the kernel against ``rms_head`` and
    ``rotate_half_rope`` before the dense band."""
    q, gain, k, v, cos, sin = sliding_inputs(seq, dtype=dtype)
    B, S = k.shape[:2]
    with jax.default_matmul_precision("highest"):
        got = pallas_band.band_attention(
            q, k.reshape(B, S, -1), v.reshape(B, S, -1), window, 16,
            q_gain=gain, eps=1e-5, rope=(cos, pallas_band.signed_sin(sin)),
            block_rows=block)
        einsums = em.sliding_attention(q, gain, k, v, cos, sin, window, 1e-5)
        turned = rotate_half_rope(
            rms_head(q.astype(F32).reshape(B, S, -1, 16), gain, 1e-5),
            cos[:, None], sin[:, None])
        want = dense_band(turned, k.astype(F32), v.astype(F32),
                          window).reshape(B, S, -1)
    limit = 3e-5 if dtype == "float32" else 1.5 * gap(einsums, want)
    assert gap(got, want) <= limit


def test_gradient_through_the_kernel_is_the_einsum_forms():
    args = sliding_inputs(21)
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[0].shape), F32)

    def loss(attend):
        return lambda *a: jnp.sum(attend(*a, 8, 1e-5) * w)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(em.sliding_attention_kernel), range(6))(*args)
        want = jax.grad(loss(em.sliding_attention), range(6))(*args)
        value = loss(em.sliding_attention_kernel)(*args)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(float(value))


def takes_the_kernel(layer, u):
    # a function of its own a call: a trace is kept by its function
    return "name=window_fwd" in str(jax.make_jaxpr(
        lambda u: layer.full(u)[0])(u))


def test_form_is_chosen_from_device_and_head_width(monkeypatch):
    """No argument, no variable: a sliding layer on a TPU with heads of
    whole lanes takes the kernel; on a CPU, or with heads the kernel
    cannot slice by lanes, the einsums; a global layer never."""
    u = jnp.zeros((1, 16, 64), F32)
    wide = em.ExaoneMoeAttention(exaone_moe_tiny(head_dim=128), 8)
    narrow = em.ExaoneMoeAttention(exaone_moe_tiny(), 8)
    assert not takes_the_kernel(wide, u)
    monkeypatch.setattr(_platform, "device_platform", lambda: "tpu")
    monkeypatch.setattr(pallas_band, "interpret_default", lambda: True)
    assert takes_the_kernel(wide, u)
    assert not takes_the_kernel(narrow, u)
    assert not takes_the_kernel(
        em.ExaoneMoeAttention(exaone_moe_tiny(head_dim=128), None), u)


@pytest.mark.parametrize("seq", [5, 21, 40])
def test_sliding_layer_through_the_kernel_keeps_k_v_and_the_output(
        monkeypatch, seq):
    """``ExaoneMoeAttention.full`` of the rehearsal's sliding layer
    (window 8, 4 query over 2 key/value heads of 16) with the band as the
    kernel: the same k and v bit for bit, the output within float32
    rounding of the einsum form's — which is the parent's: the base's
    ``qkv`` (projections, norms), rotated, through the einsums."""
    layer = em.ExaoneMoeAttention(exaone_moe_tiny(), 8)
    u = jnp.asarray(np.random.default_rng(seq).normal(size=(2, seq, 64)),
                    F32)
    with jax.default_matmul_precision("highest"):
        q, k, v = layer.qkv(u, jnp.broadcast_to(jnp.arange(seq), (2, seq)))
        parent = layer.project(em.local_window_attention(
            q, k, v, 8).reshape(2, seq, -1)), k, v
        want = layer.full(u)
        monkeypatch.setattr(em, "sliding_attention",
                            em.sliding_attention_kernel)
        got = layer.full(u)
    for a, b, c in zip(got, want, parent):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(c))
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
