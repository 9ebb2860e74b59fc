"""What EXAONE-MoE's new pieces REQUIRE, from shapes: the sliding
layers' ring walk at decode (kernel ``window_decode``), their band
attention at prefill, and the model's operations per token.

The ring walk. A decode row of context ``c`` must read ``min(c, window)``
keys and as many values of its ring, ``key/value heads x head_dim``
elements each, per sliding layer: ``window_tokens`` (the sum of
``min(c, window)`` over the rows, off ``p2t:decode.dispatch``) x layers x
4,096 B at the published shape. Operations (4 x head_dim x query heads a
key) are far under the ridge: the bound is memory.

The band. Position i of a prompt sees ``min(i + 1, window)`` keys in a
sliding layer: ``band_keys(n, window)`` query-key pairs a sequence,
LINEAR in n past the window, 4 x head_dim operations a pair and query
head (what ``ops_per_token`` counts a sliding layer's scores as). The
band is plain XLA, not a kernel, so it has no roofline share of its own:
``prefill_window_device_pct.serve`` shows its cost."""

from __future__ import annotations

from roofline import paged_decode


def window_decode(window_tokens, layers, kv_heads, head_dim, itemsize=2):
    """``window_tokens``: min(context, window) over the rows of all
    counted decode steps, added up. Returns (flops, bytes)."""
    return paged_decode.paged_decode(window_tokens, layers, kv_heads,
                                     head_dim, itemsize)


def band_keys(tokens: int, window: int) -> int:
    """Query-key pairs of one sequence under the band."""
    full = max(tokens - window, 0)
    ramp = min(tokens, window)
    return full * window + ramp * (ramp + 1) // 2


def ops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward operations a token requires (3 x forward) on
    THIS chip's share: per layer q, k, v, o and the scores (a sliding
    layer: ``min(seq, window)`` keys; a global layer: causal, half the
    sequence), then a dense SwiGLU or the shared expert, the router over
    all its outputs and the part of the ``num_experts_per_tok`` experts
    that is held here; then the head over the vocabulary held. Embedding
    rows are looked up."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    router = cfg.get("router_experts") or cfg["num_experts"]
    held = (cfg.get("held_experts") or (0, cfg["num_experts"]))[1]
    total = 2.0 * H * V
    for attn, ff in zip(cfg["layer_types"], cfg["mlp_layer_types"]):
        keys = min(seq, cfg["sliding_window"]) \
            if attn == "sliding_attention" else seq / 2.0
        total += 2.0 * (2 * H * nh * hd + 2 * H * nkv * hd) \
            + 4.0 * keys * nh * hd
        if ff == "dense":
            total += 6.0 * H * cfg["intermediate_size"]
        else:
            Fe = cfg["moe_intermediate_size"]
            total += 6.0 * H * Fe * cfg["num_shared_experts"] \
                + 2.0 * H * router \
                + 6.0 * H * Fe * cfg["num_experts_per_tok"] * held / router
    return 3.0 * total
