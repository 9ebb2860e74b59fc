"""What SDAR-MoE's new piece REQUIRES, from shapes: paged attention of
a BLOCK of query positions per sequence, and the model's operations per
token. (Its grouped matmul is ``roofline/lfm2_moe.moe_gmm``'s.)

A pass carries B positions of a sequence, all with the same context
(the cache before the block, and the block). The kernel must read that
context's K and V ONCE a pass and layer — ``context x kv_heads x
head_dim`` elements each — whatever B is: a body that walked the pages
once per position would move B times as much and read B times lower.
Operations: every position's every query head against the whole
context, ``4 x context x B x heads x head_dim`` a layer."""

from __future__ import annotations


def paged_block(ctx_sum, layers, heads, kv_heads, head_dim, positions,
                itemsize=2):
    """``ctx_sum``: the contexts (block included) of all sequences of all
    counted passes, added up, each counted ONCE a pass. Returns (flops,
    bytes)."""
    return (4.0 * ctx_sum * positions * heads * head_dim * layers,
            2.0 * ctx_sum * kv_heads * head_dim * layers * itemsize)


def ops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward operations a token requires (3 x forward) in
    ONE forward over a sequence under the block-causal mask: per layer
    q, k, v, o, scores over half the sequence, the router and the
    experts a token is routed to; then the untied head. Embedding rows
    are looked up. (Generation costs S + 1 such passes a block.)"""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    per_layer = 2.0 * (2 * H * nh * hd + 2 * H * nkv * hd) \
        + 2.0 * seq * nh * hd \
        + 6.0 * H * cfg["moe_intermediate_size"] \
        * cfg["num_experts_per_tok"] + 2.0 * H * cfg["num_experts"]
    return 3.0 * (cfg["num_hidden_layers"] * per_layer + 2.0 * H * V)
