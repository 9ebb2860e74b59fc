"""What Nemotron-H's pieces REQUIRE, from shapes: the grouped matmul of
TWO-matrix experts (kernel ``moe_gmm``), the state-space recurrence's
one decode step at this mixer's shape (kernel ``ssm_state_step``), and
the model's operations per token.

Two-matrix experts. ``W_down relu(W_up a)^2``: per expert HIT the call
must read its two ``hidden x width`` matrices once, at the PUBLISHED
width (the lanes a program pads its storage with are not required: they
show as cost); per assignment a row of ``hidden`` goes into the up
product and comes out of the down product, and a row of ``width``
comes out of the first and goes into the second; ``4 x hidden x width``
operations an assignment."""

from __future__ import annotations

from roofline import falcon_h1


def moe_gmm2(assignments, experts_hit, hidden, width, itemsize=2):
    """One expert layer's two grouped products. Returns (flops, bytes)."""
    weights = 2.0 * experts_hit * hidden * width * itemsize
    rows = 2.0 * assignments * (hidden + width) * itemsize
    return 4.0 * assignments * hidden * width, weights + rows


def ssm_state_step(rows, layers, cfg: dict):
    """``roofline/falcon_h1.ssm_state_step`` at the mixer's published
    shape; ``layers`` the state-space layers (off the program's span)."""
    return falcon_h1.ssm_state_step(
        rows, layers, cfg["mamba_num_heads"], cfg["mamba_head_dim"],
        cfg["n_groups"], cfg["ssm_state_size"])


def ops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward operations a token requires (3 x forward) on
    THIS chip's share, by the pattern's layer kinds: a state-space layer
    (input and output projections, the taps, the recurrence's 6
    operations a state element); an attention layer (q, k, v, o and
    causal scores over half the sequence); an expert layer (the shared
    expert, the router over all its outputs, and the part of the
    ``num_experts_per_tok`` experts that is held here); then the head
    over the vocabulary held. Embedding rows are looked up."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    mh, N = cfg["mamba_num_heads"], cfg["ssm_state_size"]
    d = mh * cfg["mamba_head_dim"]
    conv_dim = d + 2 * cfg["n_groups"] * N
    router = cfg.get("router_experts") or cfg["n_routed_experts"]
    per_kind = {
        "M": 2.0 * H * (d + conv_dim + mh) + 2.0 * cfg["conv_kernel"]
        * conv_dim + 6.0 * d * N + 2.0 * d * H,
        "*": 2.0 * (2 * H * nh * hd + 2 * H * nkv * hd) + 2.0 * seq * nh * hd,
        "E": 4.0 * H * cfg["moe_shared_expert_intermediate_size"]
        + 2.0 * H * router + 4.0 * H * cfg["moe_intermediate_size"]
        * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / router}
    return 3.0 * (sum(per_kind[c] for c in cfg["hybrid_override_pattern"])
                  + 2.0 * H * V)
