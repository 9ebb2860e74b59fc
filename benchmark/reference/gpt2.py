"""Plain reference of GPT-2 (Radford et al. 2019; the layer equations of
``openai-community/gpt2-*``): learned token and position embeddings,
pre-LN blocks (LN -> fused qkv -> causal softmax attention -> proj ->
residual; LN -> up -> GELU -> down -> residual), final LN, head tied to
the token table, mean cross-entropy over every position.

Departures from the publication, each because the program under test
makes the same choice and a reference of another function would compare
nothing:
* GELU is the exact erf form, not ``gelu_new`` (tanh): the program's
  ``F.gelu`` default.
* The fused qkv output is laid out head-major ``[heads, (q|k|v), d]``;
  with seeded random weights this is a relabelling of columns.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import layer_norm, matmul_f32

STACKED = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
           "ln2_g", "ln2_b", "up_w", "up_b", "down_w", "down_b")


def leaf_specs(cfg: dict) -> dict:
    """{leaf: (shape, init, scale)}; init 'normal' = N(0, scale),
    'gain' = 1 + N(0, scale).

    Every matrix is drawn at ``initializer_range``. The published
    scheme shrinks the two residual projections by 1/sqrt(2 L); with
    SEEDED RANDOM weights that leaves the residual stream so small that
    the tied head reads back the input token's own embedding, greedy
    decoding repeats one token with a margin of 1-2 logits, and no
    precision could flip a served token (read on the chip in PR 22:
    fp8 moved none of 575). At one scale the top logits lie within
    hundredths of each other, as a served-token check needs."""
    V, H, L, P = (cfg["vocab_size"], cfg["n_embd"], cfg["n_layer"],
                  cfg["n_positions"])
    F = cfg.get("n_inner") or 4 * H
    std = cfg["initializer_range"]
    pstd = std          # see the note below
    return {
        "wte": ((V, H), "normal", std), "wpe": ((P, H), "normal", std),
        "ln1_g": ((L, H), "gain", std), "ln1_b": ((L, H), "normal", std),
        "qkv_w": ((L, H, 3 * H), "normal", std),
        "qkv_b": ((L, 3 * H), "normal", std),
        "proj_w": ((L, H, H), "normal", pstd),
        "proj_b": ((L, H), "normal", std),
        "ln2_g": ((L, H), "gain", std), "ln2_b": ((L, H), "normal", std),
        "up_w": ((L, H, F), "normal", std), "up_b": ((L, F), "normal", std),
        "down_w": ((L, F, H), "normal", pstd),
        "down_b": ((L, H), "normal", std),
        "lnf_g": ((H,), "gain", std), "lnf_b": ((H,), "normal", std),
    }


def _block(x, w, cfg, mm):
    B, S, H = x.shape
    nh = cfg["n_head"]
    hd = H // nh
    eps = cfg["layer_norm_epsilon"]
    h = layer_norm(x, w["ln1_g"], w["ln1_b"], eps)
    qkv = (mm(h, w["qkv_w"]) + w["qkv_b"]).reshape(B, S, nh, 3, hd)
    q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H)
    x = x + mm(a, w["proj_w"]) + w["proj_b"]
    h = layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
    h = jax.nn.gelu(mm(h, w["up_w"]) + w["up_b"], approximate=False)
    return x + mm(h, w["down_w"]) + w["down_b"]


def hidden(params, ids, cfg, mm=matmul_f32):
    S = ids.shape[1]
    x = params["wte"][ids] + params["wpe"][:S][None]
    stack = {k: params[k] for k in STACKED}

    @jax.checkpoint
    def body(x, w):
        return _block(x, w, cfg, mm), None

    x, _ = jax.lax.scan(body, x, stack)
    return layer_norm(x, params["lnf_g"], params["lnf_b"],
                      cfg["layer_norm_epsilon"])


def logits(params, ids, cfg, mm=matmul_f32):
    """[B, S, V] float32 logits of a full causal forward."""
    return mm(hidden(params, ids, cfg, mm), params["wte"].T)


def loss(params, batch, cfg, mm=matmul_f32):
    """Mean cross-entropy of ``labels`` under the logits at every
    position; one sequence's logits at a time, so [tokens, V] never
    exists whole."""
    ids, labels = batch
    h = hidden(params, ids, cfg, mm)

    @jax.checkpoint
    def row(args):
        h_row, y = args
        lg = mm(h_row, params["wte"].T)
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, y[:, None], -1)[:, 0])

    return jnp.sum(jax.lax.map(row, (h, labels))) / labels.size
