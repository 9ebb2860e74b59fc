"""What LFM2-MoE's new pieces REQUIRE, from shapes: the grouped matmul
of the dropless expert layer, and the model's operations per token.

The grouped matmul (kernel ``moe_gmm``) must read the weights of every
expert that at least one row is routed to ONCE per projection (three
projections a layer: two of ``hidden x width`` up, one down), read each
assignment's row in and write it out, and multiply: ``6 x hidden x
width`` operations an assignment. Experts no row hits are not counted,
and a weight tile streamed again for a second row tile is not counted
twice, so the share cannot be raised by moving more."""

from __future__ import annotations


def moe_gmm(assignments, experts_hit, hidden, width, itemsize=2):
    """``assignments``: (token, expert) pairs computed, ``experts_hit``:
    distinct experts with at least one, both summed over the expert
    layers counted. Returns (flops, bytes)."""
    weights = 3.0 * experts_hit * hidden * width * itemsize
    # rows: the two up projections read a hidden row and write a width
    # row each, the down projection reads a width row and writes hidden
    rows = assignments * (3.0 * hidden + 3.0 * width) * itemsize
    return 6.0 * assignments * hidden * width, weights + rows


def ops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward operations a token requires (3 x forward):
    per layer the operator (conv: in and out projections and the taps;
    attention: q, k, v, o and causal scores over half the sequence) and
    the feed-forward (dense SwiGLU, or the experts a token is routed to
    and the router), then the tied head. Embedding rows are looked up."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or H // nh
    total = 2.0 * H * V
    for i, kind in enumerate(cfg["layer_types"]):
        if kind == "conv":
            total += 2.0 * (3 * H * H + H * H) + 2.0 * cfg["conv_L_cache"] * H
        else:
            total += 2.0 * (2 * H * nh * hd + 2 * H * nkv * hd) \
                + 2.0 * seq * nh * hd
        if i < cfg["num_dense_layers"]:
            total += 6.0 * H * cfg["intermediate_size"]
        else:
            total += 6.0 * H * cfg["moe_intermediate_size"] \
                * cfg["num_experts_per_tok"] + 2.0 * H * cfg["num_experts"]
    return 3.0 * total
