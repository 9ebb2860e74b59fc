"""Plain reference of LFM2-MoE (``LiquidAI/LFM2-24B-A2B``, model type
``lfm2_moe``): token embedding, no learned positions; every layer is
``h = x + Op(RMS(x))``, ``y = h + FF(RMS(h))`` with ``Op`` a gated
short convolution or grouped-query attention (``layer_types``) and
``FF`` a dense SwiGLU (the first ``num_dense_layers`` layers) or 64
sigmoid-routed experts, 4 a token, none dropped; final RMSNorm, head
tied to the token table.

    RMS(x; g)  = x / sqrt(mean(x^2) + eps) * g
    conv       [B, C, X] = split3(u W_in);  z = B * X;
               c_t = sum_j w_j * z_{t-2+j}  (z zero before the sequence);
               Op = (C * c) W_out
    attention  q, k, v = u W_q, u W_k, u W_v; per-head RMS of q and k;
               rotary (rotate-half) on q, k; causal softmax(q k^T / 8),
               query head h on key/value head h // 4; Op = heads W_o
    dense      (silu(a W1) * (a W3)) W2
    experts    s = sigmoid(a W_g); S = top4(s + b);
               p_e = s_e / (sum_{S} s + 1e-6) * routed_scaling_factor;
               FF = sum_{e in S} p_e (silu(a W1e) * (a W3e)) W2e

Every matmul goes through ``mm`` (the lower-precision control swaps it);
the experts run one after another over ALL tokens, each weighted by its
(mostly zero) routing weight, so nothing ``[tokens, experts, width]``
is ever live; attention runs one key/value head at a time.

A layer's leaves are named by the layer's index (``conv0_in``,
``attn1_q``, ``moe2_w1`` ...) because the layers differ in kind; only the
two pre-norms exist in every layer and are stacked.

Departures from the publication are in the configuration file
(``assumed``): tied head, head size 64, the initialisation scales, the
selection bias drawn from the seed."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import matmul_f32

STACKED = ("op_norm", "ffn_norm")


def _dims(cfg):
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // nh
    return nh, nkv, hd


def leaf_specs(cfg: dict) -> dict:
    """{leaf: (shape, init, scale)}; 'normal' = N(0, scale), 'gain' =
    1 + N(0, scale)."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    F, Fe, E = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["num_experts"])
    nh, nkv, hd = _dims(cfg)
    K = cfg["conv_L_cache"]
    kinds = cfg["layer_types"]
    L = len(kinds)
    std = cfg["initializer_range"]
    out = {"embed": ((V, H), "normal", std),
           "op_norm": ((L, H), "gain", std),
           "ffn_norm": ((L, H), "gain", std),
           "out_norm": ((H,), "gain", std)}
    for i, kind in enumerate(kinds):
        if kind == "conv":
            out[f"conv{i}_in"] = ((H, 3 * H), "normal", std)
            out[f"conv{i}_w"] = ((K, H), "normal", cfg["conv_init_std"])
            out[f"conv{i}_out"] = ((H, H), "normal", std)
        else:
            out[f"attn{i}_q"] = ((H, nh * hd), "normal", std)
            out[f"attn{i}_k"] = ((H, nkv * hd), "normal", std)
            out[f"attn{i}_v"] = ((H, nkv * hd), "normal", std)
            out[f"attn{i}_o"] = ((nh * hd, H), "normal", std)
            out[f"attn{i}_qnorm"] = ((hd,), "gain", std)
            out[f"attn{i}_knorm"] = ((hd,), "gain", std)
        if i < cfg["num_dense_layers"]:
            out[f"ffn{i}_w1"] = ((H, F), "normal", std)
            out[f"ffn{i}_w3"] = ((H, F), "normal", std)
            out[f"ffn{i}_w2"] = ((F, H), "normal", std)
        else:
            out[f"moe{i}_gate"] = ((H, E), "normal", std)
            out[f"moe{i}_bias"] = ((E,), "normal", cfg["expert_bias_std"])
            out[f"moe{i}_w1"] = ((E, H, Fe), "normal", std)
            out[f"moe{i}_w3"] = ((E, H, Fe), "normal", std)
            out[f"moe{i}_w2"] = ((E, Fe, H), "normal", std)
    return out


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def conv_op(u, p, i, cfg, mm):
    """u [B, S, H] -> (Op [B, S, H], z [B, S, H]): ``z`` is what a
    decoder keeps the last ``conv_L_cache - 1`` positions of."""
    K = cfg["conv_L_cache"]
    b, c, x = jnp.split(mm(u, p[f"conv{i}_in"]), 3, axis=-1)
    z = b * x
    zp = jnp.pad(z, ((0, 0), (K - 1, 0), (0, 0)))
    S = z.shape[1]
    w = p[f"conv{i}_w"]
    conv = sum(w[j] * zp[:, j:j + S] for j in range(K))
    return mm(c * conv, p[f"conv{i}_out"]), z


def rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], -1)


def rope_tables(positions, hd, theta):
    """cos, sin ``[..., hd]`` (the half tables repeated)."""
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[..., None] * inv
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def attn_op(u, p, i, cfg, mm):
    B, S, _ = u.shape
    nh, nkv, hd = _dims(cfg)
    g = nh // nkv
    eps = cfg["norm_eps"]
    q = mm(u, p[f"attn{i}_q"]).reshape(B, S, nh, hd)
    k = mm(u, p[f"attn{i}_k"]).reshape(B, S, nkv, hd)
    v = mm(u, p[f"attn{i}_v"]).reshape(B, S, nkv, hd)
    q = rms(q, p[f"attn{i}_qnorm"], eps)
    k = rms(k, p[f"attn{i}_knorm"], eps)
    cos, sin = rope_tables(jnp.arange(S), hd,
                           cfg["rope_parameters"]["rope_theta"])
    cos, sin = cos[None, :, None], sin[None, :, None]
    q = q * cos + rotate_half(q) * sin
    k = k * cos + rotate_half(k) * sin
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one_kv_head(args):
        qg, kh, vh = args            # [B,S,g,hd], [B,S,hd], [B,S,hd]
        s = jnp.einsum("bqgd,bkd->bgqk", qg, kh) / (hd ** 0.5)
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("bgqk,bkd->bqgd", jax.nn.softmax(s, -1), vh)

    qg = jnp.moveaxis(q.reshape(B, S, nkv, g, hd), 2, 0)
    a = jax.lax.map(one_kv_head, (qg, jnp.moveaxis(k, 2, 0),
                                  jnp.moveaxis(v, 2, 0)))
    a = jnp.moveaxis(a, 0, 2).reshape(B, S, nh * hd)
    return mm(a, p[f"attn{i}_o"])


def swiglu(a, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(a, w1)) * mm(a, w3), w2)


def route(a, gate, bias, cfg, mm, forced=None):
    """(expert ids [.., k], weights [.., k], deficit [..]) in float32:
    the bias selects, the unbiased scores weigh. ``forced`` [.., k]
    hands in the experts another implementation chose (a row of -1
    leaves that row to its own top k); the weights are then the
    reference's scores AT those experts, and ``deficit`` says by how
    much, in the reference's own biased scores, the best expert left
    out beats the worst one taken (0 for the reference's own top k).
    Top k is discontinuous: two sound implementations part where a 4th
    and a 5th score tie to within rounding, so a comparison of what
    follows needs the same experts on both sides, and the deficit is
    the measure of how sound the other side's choice was."""
    s = jax.nn.sigmoid(mm(a.astype(jnp.float32),
                          gate.astype(jnp.float32)))
    pick = s + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(pick, cfg["num_experts_per_tok"])
    if forced is not None:
        idx = jnp.where(forced >= 0, forced, idx)
    taken = jnp.sum(jax.nn.one_hot(idx, pick.shape[-1]), -2) > 0
    deficit = jnp.maximum(
        jnp.max(jnp.where(taken, -jnp.inf, pick), -1)
        - jnp.min(jnp.take_along_axis(pick, idx, -1), -1), 0.0)
    w = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return idx, w * cfg["routed_scaling_factor"], deficit


def experts_ff(a, p, i, cfg, mm, held=None, forced=None):
    """(the expert layer's output, the experts used [.., k], the
    deficit of that choice [..]); ``held`` = (first, count) keeps only
    that contiguous share of the experts' parts (routing is over all)."""
    E = cfg["num_experts"]
    idx, w, deficit = route(a, p[f"moe{i}_gate"], p[f"moe{i}_bias"], cfg,
                            mm, forced)
    # [.., E] combine weights, zero where the expert is not chosen
    dense = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                    * w[..., None], -2)
    lo, n = held or (0, E)

    def one(acc, xs):
        w1, w3, w2, col = xs
        return acc + col[..., None] * swiglu(a, w1, w3, w2, mm), None

    cols = jnp.moveaxis(dense, -1, 0)[lo:lo + n]
    out, _ = jax.lax.scan(one, jnp.zeros_like(a), (
        p[f"moe{i}_w1"][lo:lo + n], p[f"moe{i}_w3"][lo:lo + n],
        p[f"moe{i}_w2"][lo:lo + n], cols))
    return out, idx, deficit


def layer(x, p, i, cfg, mm, forced=None):
    """(y, experts used or None, deficit or None) of layer ``i``."""
    eps = cfg["norm_eps"]
    u = rms(x, p["op_norm"][i], eps)
    if cfg["layer_types"][i] == "conv":
        op, _ = conv_op(u, p, i, cfg, mm)
    else:
        op = attn_op(u, p, i, cfg, mm)
    h = x + op
    a = rms(h, p["ffn_norm"][i], eps)
    if i < cfg["num_dense_layers"]:
        return h + swiglu(a, p[f"ffn{i}_w1"], p[f"ffn{i}_w3"],
                          p[f"ffn{i}_w2"], mm), None, None
    ff, idx, deficit = experts_ff(a, p, i, cfg, mm, forced=forced)
    return h + ff, idx, deficit


def forward(params, ids, cfg, mm=matmul_f32, forced=None):
    """A full causal forward: (logits [B, S, V] float32, the experts
    used [B, S, expert layers, k], the deficit of that choice [B, S,
    expert layers]). ``forced`` [B, S, expert layers, k] hands in
    another implementation's experts (:func:`route`)."""
    x = params["embed"][ids]
    used, deficits = [], []
    for i in range(len(cfg["layer_types"])):
        x, idx, deficit = layer(
            x, params, i, cfg, mm,
            None if forced is None else forced[:, :, len(used)])
        if idx is not None:
            used.append(idx)
            deficits.append(deficit)
    x = rms(x, params["out_norm"], cfg["norm_eps"])
    return (mm(x, params["embed"].T), jnp.stack(used, 2),
            jnp.stack(deficits, 2))


def logits(params, ids, cfg, mm=matmul_f32):
    """[B, S, V] float32 logits of a full causal forward."""
    return forward(params, ids, cfg, mm)[0]
