"""Plain reference of Falcon-H1 (``tiiuae/Falcon-H1-34B-Instruct``, model
type ``falcon_h1``): a PARALLEL hybrid block. No cache, no kernel, no
chunking: float32 ``jax.numpy`` (the caller holds
``jax.default_matmul_precision("highest")``), the state-space recurrence
as its DEFINITION, a ``lax.scan`` over tokens. For ``x`` the residual
stream ``[T, H]`` and every norm an RMSNorm with ``rms_norm_eps``::

    stream     x0 = Embed(ids) * embedding_multiplier
               u  = RMS_in(x)
               x  = x + ssm_out_multiplier * SSM(u)
                      + attention_out_multiplier
                        * Attn(u * attention_in_multiplier)
               x  = x + FF(RMS_ff(x))
               logits = (RMS_final(x) W_head) * lm_head_multiplier   (untied)
    attention  q = u' W_q (heads x head_dim);  k = (u' W_k) * key_multiplier;
               v = u' W_v;  no q/k norm, no bias; rotary (rotate-half, all
               head_dim lanes, base rope_theta) on q and k; causal
               softmax(q k^T / sqrt(head_dim)); query head h on key/value
               head h // (heads / kv heads);  Attn = concat_h(a_h) W_o
    mixer      p = ((u * ssm_in_multiplier) W_in) * m,  m = ssm_multipliers
               [0..4] on the lanes of z | x | B | C | dt in that order
               z | xBC | dt = split(p)
               xBC'_t = silu(b + sum_j w[j] * xBC_{t-(K-1)+j})   (depthwise,
               K = mamba_d_conv taps, zeros before the sequence)
               x [n_heads, d_head] | B [n_groups, d_state] | C [..] = xBC'
               head h uses group h // (n_heads / n_groups)
    recurrence dt_t = softplus(dt_t + dt_bias)   (no clamp);  A = -exp(A_log)
               H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t
                                                     [d_head, d_state]
               y_t = H_t C_t + D x_t
    gate, norm g = y * silu(z);  RMS over each GROUP's d_ssm / n_groups lanes,
               times the weight [d_ssm];  SSM = g W_out
    FF         ((a W_up) * silu((a W_gate) * mlp_multipliers[0])) W_down
               * mlp_multipliers[1]

Every projection goes through ``mm`` (the lower-precision control swaps
it); the recurrence, the convolution, the norms and every multiplier
are plain float32 arithmetic. Attention runs one key/value head at a
time. The model is computed STAGE BY STAGE (:func:`stage_leaves`: the
embedding, each layer, the head), each needing only its own leaves, so
that a caller can draw and free the float32 weights a stage at a time
(``drivers/serve_staged_dense.py``: the head stage is 5.35 GB, a layer
1.72 GB); :func:`forward` runs them all with every leaf at hand.

Departures from the publication are in the configuration file
(``assumed``): each leaf's scale (``init_scales``), ``dt_bias`` drawn
around ``dt_bias_mean`` (:func:`dt_bias`), the rotary's lane pairing,
``mamba_d_ssm`` in the place of ``mamba_expand x hidden_size``.

Pieces a control replaces are module attributes (``gated_norm``,
``conv_bias``, ``skip``, ``dt_bias``, ``mup_vector``, ``scale_keys``):
``control_staged_dense.py`` patches them one at a time."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import matmul_f32
from .lfm2_moe import rms, rope_tables, rotate_half

STACKED = ()

_LAYER = ("in_norm", "ff_norm", "q", "k", "v", "o", "m_in", "m_conv",
          "m_convb", "m_dtb", "m_alog", "m_d", "m_norm", "m_out",
          "w_gate", "w_up", "w_down")
_GAINS = ("in_norm", "ff_norm", "m_norm", "m_d", "out_norm")


def _dims(cfg):
    d = cfg.get("mamba_d_ssm") or cfg["mamba_expand"] * cfg["hidden_size"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return d, gn, d + 2 * gn, cfg["mamba_n_heads"]


def leaf_specs(cfg: dict) -> dict:
    """{leaf: (shape, init, scale)}; 'normal' = N(0, scale), 'gain' =
    1 + N(0, scale). Every leaf has its own scale (``init_scales``):
    under the µP multipliers one scale for all would leave the three
    branches a few per cent of the stream."""
    H, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    d, _, conv_dim, mh = _dims(cfg)
    K = cfg["mamba_d_conv"]
    shapes = {"in_norm": (H,), "ff_norm": (H,), "q": (H, nh * hd),
              "k": (H, nkv * hd), "v": (H, nkv * hd), "o": (nh * hd, H),
              "m_in": (H, d + conv_dim + mh), "m_conv": (K, conv_dim),
              "m_convb": (conv_dim,), "m_dtb": (mh,), "m_alog": (mh,),
              "m_d": (mh,), "m_norm": (d,), "m_out": (d, H),
              "w_gate": (H, F), "w_up": (H, F), "w_down": (F, H)}
    s = cfg["init_scales"]

    def spec(name, shape):
        return (shape, "gain" if name in _GAINS else "normal", s[name])

    out = {"embed": spec("embed", (V, H)),
           "out_norm": spec("out_norm", (H,)),
           "head": spec("head", (H, V))}
    for i in range(cfg["num_hidden_layers"]):
        for name in _LAYER:
            out[f"l{i}_{name}"] = spec(name, shapes[name])
    return out


def stage_leaves(cfg: dict) -> list:
    """[(stage, the leaves it reads)]: ``"embed"``, each layer's index,
    ``"head"``."""
    return [("embed", ["embed"])] + [
        (i, [f"l{i}_{name}" for name in _LAYER])
        for i in range(cfg["num_hidden_layers"])] \
        + [("head", ["out_norm", "head"])]


# -------------------------------------------------------- the controls' seams
def skip(name: str) -> bool:
    """Is the piece ``name`` (``ssm``, ``d_skip``) left out? Never, in
    the reference; a control says yes to one."""
    return False


def conv_bias(b):
    return b


def dt_bias(leaf, cfg):
    """The seeded ``dt_bias``: the leaf is drawn N(0, scale) and stands
    around ``dt_bias_mean`` (the published initialisation puts
    softplus(dt_bias) between 0.001 and 0.1, far from 0: ``assumed``),
    rounded to bfloat16 like every weight both sides start from."""
    return jax.lax.reduce_precision(cfg["dt_bias_mean"] + leaf, 8, 7)


# leaf (by its name's ending) -> what the program's parameter holds
PLACED = {"_m_dtb": dt_bias}


def mup_vector(cfg):
    d, gn, _, mh = _dims(cfg)
    mz, mx, mb, mc, mdt = cfg["ssm_multipliers"]
    return jnp.concatenate([
        jnp.full((d,), mz), jnp.full((d,), mx), jnp.full((gn,), mb),
        jnp.full((gn,), mc), jnp.full((mh,), mdt)]).astype(jnp.float32)


def scale_keys(k, cfg):
    return k * cfg["key_multiplier"]


def gated_norm(y, z, weight, cfg):
    """``RMS_grouped(y * silu(z)) * weight``: the statistic per group of
    ``d_ssm / n_groups`` lanes."""
    g = y * jax.nn.silu(z)
    grouped = g.reshape(g.shape[:-1] + (cfg["mamba_n_groups"], -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + cfg["rms_norm_eps"])
    return grouped.reshape(g.shape) * weight


# ---------------------------------------------------------------- the pieces
def attn_op(u, p, i, cfg, mm):
    B, S, _ = u.shape
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    g = nh // nkv
    w = lambda k: p[f"l{i}_{k}"]            # noqa: E731
    q = mm(u, w("q")).reshape(B, S, nh, hd)
    k = scale_keys(mm(u, w("k")), cfg).reshape(B, S, nkv, hd)
    v = mm(u, w("v")).reshape(B, S, nkv, hd)
    cos, sin = rope_tables(jnp.arange(S), hd, float(cfg["rope_theta"]))
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
    return mm(a, w("o"))


def recurrence(x, dt, A, Bm, Cm, D):
    """The definition, token by token, one sequence: x ``[S, heads,
    P]``, dt ``[S, heads]``, Bm, Cm ``[S, groups, N]`` -> y ``[S, heads,
    P]``."""
    heads, P = x.shape[1:]
    per = heads // Bm.shape[1]

    def step(H, t):
        xt, dtt, Bt, Ct = t
        Bh, Ch = jnp.repeat(Bt, per, 0), jnp.repeat(Ct, per, 0)
        H = jnp.exp(dtt * A)[:, None, None] * H \
            + (dtt[:, None] * xt)[:, :, None] * Bh[:, None, :]
        y = jnp.sum(H * Ch[:, None, :], -1)
        return H, y if skip("d_skip") else y + D[:, None] * xt

    h0 = jnp.zeros((heads, P, Bm.shape[-1]), jnp.float32)
    return jax.lax.scan(step, h0, (x, dt, Bm, Cm))[1]


def mixer_op(u, p, i, cfg, mm):
    B, S, _ = u.shape
    d, gn, conv_dim, mh = _dims(cfg)
    K = cfg["mamba_d_conv"]
    w = lambda k: p[f"l{i}_{k}"]            # noqa: E731
    proj = mm(u * cfg["ssm_in_multiplier"], w("m_in")) * mup_vector(cfg)
    z, xbc, dt = (proj[..., :d], proj[..., d:d + conv_dim],
                  proj[..., d + conv_dim:])
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = conv_bias(w("m_convb")) + sum(
        w("m_conv")[j] * padded[:, j:j + S] for j in range(K))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d].reshape(B, S, mh, cfg["mamba_d_head"])
    Bm = xbc[..., d:d + gn].reshape(B, S, cfg["mamba_n_groups"], -1)
    Cm = xbc[..., d + gn:].reshape(B, S, cfg["mamba_n_groups"], -1)
    dt = jax.nn.softplus(dt + dt_bias(w("m_dtb"), cfg))
    A = -jnp.exp(w("m_alog"))
    y = jax.vmap(lambda xs, dts, bs, cs: recurrence(
        xs, dts, A, bs, cs, w("m_d")))(x, dt, Bm, Cm)
    return mm(gated_norm(y.reshape(B, S, d), z, w("m_norm"), cfg),
              w("m_out"))


def feed_forward(a, p, i, cfg, mm):
    gate_m, down_m = cfg["mlp_multipliers"]
    w = lambda k: p[f"l{i}_{k}"]            # noqa: E731
    return mm(mm(a, w("w_up")) * jax.nn.silu(mm(a, w("w_gate")) * gate_m),
              w("w_down")) * down_m


# ---------------------------------------------------------------- stages
def embed(p, ids, cfg):
    return p["embed"][ids] * cfg["embedding_multiplier"]


def layer(p, i, x, cfg, mm=matmul_f32):
    """One layer on ``x [B, S, H]``: both mixers on the same normed
    input, one residual add; then the feed-forward."""
    eps = cfg["rms_norm_eps"]
    u = rms(x, p[f"l{i}_in_norm"], eps)
    attn = attn_op(u * cfg["attention_in_multiplier"], p, i, cfg, mm)
    x = x + cfg["attention_out_multiplier"] * attn
    if not skip("ssm"):
        x = x + cfg["ssm_out_multiplier"] * mixer_op(u, p, i, cfg, mm)
    a = rms(x, p[f"l{i}_ff_norm"], eps)
    return x + feed_forward(a, p, i, cfg, mm)


def head(p, x, cfg, mm=matmul_f32):
    return mm(rms(x, p["out_norm"], cfg["rms_norm_eps"]), p["head"]) \
        * cfg["lm_head_multiplier"]


def forward(params, ids, cfg, mm=matmul_f32):
    """[B, S, V] float32 logits of a full causal forward."""
    x = embed(params, ids, cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(params, i, x, cfg, mm)
    return head(params, x, cfg, mm)


logits = forward
