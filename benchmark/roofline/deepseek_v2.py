"""What DeepSeek-V2's new pieces REQUIRE, from shapes: paged decode
attention over the LATENT cache, the expanded prefill's flash forward
with value heads narrower than the query/key heads, and the model's
operations per token. (Its grouped matmul is ``roofline/lfm2_moe.
moe_gmm``'s, over the experts HELD.)

Paged latent decode (kernel ``paged_mla_decode``). A row's context is
one vector of ``rank + rope`` numbers a token and layer, which the
kernel must read ONCE — it is the keys and, its first ``rank`` lanes,
the values; the zero padding of the pool's rows is not required and not
counted. Every one of the ``heads`` query heads scores it (``rank +
rope`` multiply-adds) and weighs it (``rank``): ``2 x heads x (2 rank +
rope)`` operations a context token and layer. At 128 heads, 512 + 64:
278,528 operations against 1,152 bytes = 242 operations a byte, ON a
v5e's ridge (197 TFLOP/s / 819 GB/s = 240.5): the bound is taken per
step and the reader says which.

Expanded prefill (kernel ``flash_fwd``, widths ``d_qk`` != ``d_v``).
Per head the causal triangle's ``S (S + 1) / 2`` score entries each
cost ``2 d_qk`` (QK^T) + ``2 d_v`` (PV) operations; Q, K cross HBM at
``d_qk`` lanes a head and token, V and O at ``d_v``."""

from __future__ import annotations


def paged_mla(ctx_sum, layers, heads, rank, rope_dim, itemsize=2):
    """``ctx_sum``: the contexts (the new token included) of all rows of
    the counted decode steps, added up. Returns (flops, bytes)."""
    return (2.0 * ctx_sum * layers * heads * (2 * rank + rope_dim),
            1.0 * ctx_sum * layers * (rank + rope_dim) * itemsize)


def flash_mla_prefill(seq, layers, heads, d_qk, d_v, itemsize=2):
    """One prompt of ``seq`` (padded) tokens, causal. Returns (flops,
    bytes)."""
    triangle = seq * (seq + 1) / 2.0
    return (triangle * heads * (2.0 * d_qk + 2.0 * d_v) * layers,
            2.0 * seq * heads * (d_qk + d_v) * itemsize * layers)


def ops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward operations a token requires (3 x forward) on
    THIS chip's share: per layer the latent attention's projections
    (q_a, q_b, kv_a, kv_b, o), causal scores over half the sequence at
    ``d_qk`` + ``d_v`` a head, and the feed-forward — dense SwiGLU, or
    the shared experts, the router over all its outputs and the part of
    the ``num_experts_per_tok`` experts that is held here —, then the
    head over the vocabulary held. Embedding rows are looked up."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, rq, rkv = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                   cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    Fe = cfg["moe_intermediate_size"]
    router = cfg.get("router_experts") or cfg["n_routed_experts"]
    attn = 2.0 * (H * rq + rq * nh * (dn + dr) + H * (rkv + dr)
                  + rkv * nh * (dn + dv) + nh * dv * H) \
        + seq * nh * (dn + dr + dv)
    dense = 6.0 * H * cfg["intermediate_size"]
    moe = 6.0 * H * Fe * cfg["n_shared_experts"] + 2.0 * H * router \
        + 6.0 * H * Fe * cfg["num_experts_per_tok"] \
        * cfg["n_routed_experts"] / router
    n_dense = cfg["first_k_dense_replace"]
    layers = cfg["num_hidden_layers"]
    return 3.0 * (layers * attn + n_dense * dense
                  + (layers - n_dense) * moe + 2.0 * H * V)
