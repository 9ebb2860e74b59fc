"""Operation and byte counts against hand-worked numbers at the cells'
own shapes."""

import pytest

import roofline
from roofline import flash, model_ops, paged_decode

GPT2M = {"n_embd": 1024, "n_layer": 24, "n_head": 16, "vocab_size": 50257}
GPT2L = {"n_embd": 1280, "n_layer": 36, "n_head": 20, "vocab_size": 50304}
ERNIE = {"hidden_size": 768, "num_hidden_layers": 12,
         "intermediate_size": 3072, "num_classes": 2}


def test_flash_forward_at_the_pretraining_shape():
    # 8 rows x 16 heads, S 1024, D 64, causal:
    # 4 * 1024^2 * 64 = 268,435,456 per head, halved, x 128 heads
    flops, nbytes = flash.flash_fwd(8, 16, 1024, 64, causal=True)
    assert flops == 268_435_456 / 2 * 128 == 17_179_869_184
    # Q, K, V read and O written: 4 * 1024 * 64 * 2 B x 128 heads
    assert nbytes == 4 * 1024 * 64 * 2 * 128 == 67_108_864


def test_flash_backward_is_twice_the_forward():
    f, b = flash.flash_fwd(8, 16, 1024, 64, True)
    f2, b2 = flash.flash_bwd(8, 16, 1024, 64, True)
    assert (f2, b2) == (2 * f, 2 * b)
    assert flash.flash_fwd(8, 16, 1024, 64, False)[0] == 2 * f


def test_flash_forward_sits_at_the_ridge_on_a_v5e():
    peaks = roofline.peaks_for("TPU v5 lite")
    t, bound = roofline.roofline_seconds(
        *flash.flash_fwd(8, 16, 1024, 64, True), peaks)
    # 17.18 GFLOP / 197 T = 87.2 us; 67.1 MB / 819 G = 81.9 us
    assert bound == "compute" and t == pytest.approx(87.2e-6, rel=1e-3)


def test_paged_decode_reads_k_and_v_once():
    # 64 rows of 500 tokens, 24 layers, 16 heads x 64, bf16:
    # 32000 tokens x 1024 x 24 x 2 (K, V) x 2 B = 3.15 GB
    flops, nbytes = paged_decode.paged_decode(64 * 500, 24, 16, 64)
    assert nbytes == 32000 * 1024 * 24 * 2 * 2 == 3_145_728_000
    assert flops == 4 * 32000 * 1024 * 24
    peaks = roofline.peaks_for("TPU v5 lite")
    assert roofline.roofline_seconds(flops, nbytes, peaks)[1] == "memory"


def test_gpt2_medium_ops_per_token():
    # per layer: 2 * (3 + 1 + 8) * 1024^2 = 25,165,824 of matmuls and
    # 2 * 1024 * 1024 = 2,097,152 of causal attention; x 24 layers;
    # head 2 * 1024 * 50257 = 102,926,336; forward + backward = x 3
    want = 3 * (24 * (25_165_824 + 2_097_152) + 102_926_336)
    assert model_ops.gpt2(GPT2M, 1024) == want == 2_271_713_280


def test_gpt2_large_ops_per_token():
    per_layer = 2 * 12 * 1280 * 1280 + 2 * 1024 * 1280
    want = 3 * (36 * per_layer + 2 * 1280 * 50304)
    assert model_ops.gpt2(GPT2L, 1024) == want


def test_ernie_leaves_the_embedding_tables_out():
    """A classifier LOOKS UP its word, position and type tables and
    never multiplies by them: (40000 + 2048 + 4) x 768 = 32.3 M of the
    118 M parameters. ``6 x parameters`` per token, the usual
    shorthand, would count them and read ~35 % too high."""
    got = model_ops.ernie(ERNIE, 512)
    per_layer = 2 * 12 * 768 * 768 + 4 * 512 * 768
    head = 2 * (768 * 768 + 768 * 2) / 512
    assert got == pytest.approx(3 * (12 * per_layer + head))
    shorthand = 6 * 117_946_370 + 12 * 12 * 512 * 768
    assert shorthand / got > 1.25


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks_for("_source")
    assert roofline.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
