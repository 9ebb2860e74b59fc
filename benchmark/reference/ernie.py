"""Plain reference of the ERNIE 3.0 base encoder with a sequence
classification head (PaddleNLP ``ernie-3.0-base-zh``; BERT's layer
equations): word + position (+ token type, when given) embeddings, LN,
post-LN blocks (fused qkv -> full softmax attention -> out -> residual
-> LN; up -> GELU (erf) -> down -> residual -> LN), tanh pooler over
the first position, linear classifier, mean cross-entropy.

The fused qkv output is laid out ``[(q|k|v), heads, d]`` as the program
lays it out; with seeded random weights a relabelling of columns. The
fine-tuning job passes no token types and no padding mask (every row is
full length), so neither enters.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import layer_norm, matmul_f32

STACKED = ("qkv_w", "qkv_b", "out_w", "out_b", "ln1_g", "ln1_b",
           "up_w", "up_b", "down_w", "down_b", "ln2_g", "ln2_b")


def leaf_specs(cfg: dict) -> dict:
    V, H, L, P = (cfg["vocab_size"], cfg["hidden_size"],
                  cfg["num_hidden_layers"], cfg["max_position_embeddings"])
    F, T, C = (cfg["intermediate_size"], cfg["type_vocab_size"],
               cfg["num_classes"])
    std = cfg["initializer_range"]
    return {
        "word": ((V, H), "normal", std), "pos": ((P, H), "normal", std),
        "type": ((T, H), "normal", std),
        "embln_g": ((H,), "gain", std), "embln_b": ((H,), "normal", std),
        "qkv_w": ((L, H, 3 * H), "normal", std),
        "qkv_b": ((L, 3 * H), "normal", std),
        "out_w": ((L, H, H), "normal", std), "out_b": ((L, H), "normal", std),
        "ln1_g": ((L, H), "gain", std), "ln1_b": ((L, H), "normal", std),
        "up_w": ((L, H, F), "normal", std), "up_b": ((L, F), "normal", std),
        "down_w": ((L, F, H), "normal", std),
        "down_b": ((L, H), "normal", std),
        "ln2_g": ((L, H), "gain", std), "ln2_b": ((L, H), "normal", std),
        "pool_w": ((H, H), "normal", std), "pool_b": ((H,), "normal", std),
        "cls_w": ((H, C), "normal", std), "cls_b": ((C,), "normal", std),
    }


def _block(x, w, cfg, mm):
    B, S, H = x.shape
    nh = cfg["num_attention_heads"]
    hd = H // nh
    eps = cfg["layer_norm_eps"]
    qkv = (mm(x, w["qkv_w"]) + w["qkv_b"]).reshape(B, S, 3, nh, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H)
    x = layer_norm(x + mm(a, w["out_w"]) + w["out_b"],
                   w["ln1_g"], w["ln1_b"], eps)
    h = jax.nn.gelu(mm(x, w["up_w"]) + w["up_b"], approximate=False)
    return layer_norm(x + mm(h, w["down_w"]) + w["down_b"],
                      w["ln2_g"], w["ln2_b"], eps)


def logits(params, ids, cfg, mm=matmul_f32):
    """[B, classes] float32 logits."""
    S = ids.shape[1]
    x = params["word"][ids] + params["pos"][:S][None]
    x = layer_norm(x, params["embln_g"], params["embln_b"],
                   cfg["layer_norm_eps"])
    stack = {k: params[k] for k in STACKED}

    @jax.checkpoint
    def body(x, w):
        return _block(x, w, cfg, mm), None

    x, _ = jax.lax.scan(body, x, stack)
    pooled = jnp.tanh(mm(x[:, 0], params["pool_w"]) + params["pool_b"])
    return mm(pooled, params["cls_w"]) + params["cls_b"]


def loss(params, batch, cfg, mm=matmul_f32):
    ids, labels = batch
    lg = logits(params, ids, cfg, mm)
    return jnp.mean(jax.nn.logsumexp(lg, -1)
                    - jnp.take_along_axis(lg, labels[:, None], -1)[:, 0])
