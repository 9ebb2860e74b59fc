"""What Falcon-H1's new pieces REQUIRE, from shapes: the state-space
recurrence's one decode step against a pool of per-sequence states
(kernel ``ssm_state_step``), and the model's operations per token.

The step must read every REAL row's recurrent state once and write it
once (heads x d_head x d_state float32 a row and layer, twice; the
convolution state, 0.7 % of ``decode.dispatch``'s ``state_bytes``, is
not the kernel's and is left out), and move the row's small operands:
``x`` and ``y`` (heads x d_head each), ``B`` and ``C``
(groups x d_state each) and ``dt`` (heads), in float32 as the kernel
takes them. Per state element it multiplies by the decay, adds the
outer product and multiplies by ``C`` for the sum: 2 operations a state
BYTE, far under the ridge (240 operations a byte), so the bound is
memory. Padded rows (the garbage slot) are not counted: they show as
cost."""

from __future__ import annotations


def ssm_state_step(rows, layers, heads, d_head, groups, d_state, itemsize=4):
    """``rows``: the real rows of all counted decode steps, added up.
    Returns (flops, bytes)."""
    state = 2.0 * rows * layers * heads * d_head * d_state * itemsize
    operands = rows * layers * (2 * heads * d_head + 2 * groups * d_state
                                + heads) * itemsize
    return 2.0 * state, state + operands


def ops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward operations a token requires (3 x forward):
    per layer attention (q, k, v, o and causal scores over half the
    sequence), the mixer (input and output projections, the taps, the
    recurrence's 6 operations a state element) and the SwiGLU; then the
    untied head. Embedding rows are looked up."""
    H, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    d = cfg["mamba_d_ssm"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    conv_dim = d + 2 * gn
    attn = 2.0 * (2 * H * nh * hd + 2 * H * nkv * hd) + 2.0 * seq * nh * hd
    mixer = 2.0 * H * (d + conv_dim + cfg["mamba_n_heads"]) \
        + 2.0 * cfg["mamba_d_conv"] * conv_dim \
        + 6.0 * d * cfg["mamba_d_state"] + 2.0 * d * H
    per_layer = attn + mixer + 6.0 * H * F
    return 3.0 * (cfg["num_hidden_layers"] * per_layer + 2.0 * H * V)
