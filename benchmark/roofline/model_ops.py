"""Operations per token that forward + backward REQUIRE, per
configuration family. Backward costs twice the forward's matmuls;
recomputation is not counted; an embedding lookup multiplies nothing."""

from __future__ import annotations


def gpt2(cfg: dict, seq: int) -> float:
    """Decoder LM: every position goes through the blocks and the tied
    head. Causal attention needs half of QK^T and PV: 2 S H per token
    and layer."""
    H, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    F = cfg.get("n_inner") or 4 * H
    per_layer = 2.0 * (3 * H * H + H * H + 2 * H * F) + 2.0 * seq * H
    return 3.0 * (L * per_layer + 2.0 * H * V)


def ernie(cfg: dict, seq: int) -> float:
    """Encoder classifier. The word, position and type tables (about
    32 M of the 118 M parameters) are looked up, never multiplied, so
    ``6 x parameters`` would count ~35 % too much; they are left out.
    Attention is full: 4 S H per token and layer. The pooler and the
    classifier see one position per sequence."""
    H, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    F, C = cfg["intermediate_size"], cfg["num_classes"]
    per_layer = 2.0 * (3 * H * H + H * H + 2 * H * F) + 4.0 * seq * H
    return 3.0 * (L * per_layer + 2.0 * (H * H + H * C) / seq)
