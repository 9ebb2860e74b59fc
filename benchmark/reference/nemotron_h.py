"""Plain reference of Nemotron-H (``nvidia/NVIDIA-Nemotron-3-Nano-30B-
A3B-BF16``, model type ``nemotron_h``): ONE mixer a layer. No cache, no
kernel, no chunking, no batching: float32 ``jax.numpy`` (the caller
holds ``jax.default_matmul_precision("highest")``), the state-space
recurrence as its DEFINITION, a ``lax.scan`` over tokens. For ``x`` the
residual stream ``[T, H]`` and every norm an RMSNorm with
``layer_norm_epsilon``::

    stream     x_0 = Embed(ids)          (no multiplier, no positional table)
               for each character c of hybrid_override_pattern:
                   x = x + Mixer_c(RMS(x))
               logits = RMS_f(x) W_head                       (untied)
    M  mixer   z | xBC | dt = u W_in  [d_inner | d_inner + 2 n_groups N | heads]
               d_inner = mamba_num_heads x mamba_head_dim
               xBC'_t = silu(b + sum_j w[j] * xBC_{t-(K-1)+j})   (depthwise,
               K = conv_kernel taps, zeros before the sequence)
               x [heads, head_dim] | B [n_groups, N] | C [n_groups, N] = xBC'
               head h uses group h // (heads / n_groups)
               dt_t = softplus(dt_t + dt_bias)   (no clamp);  A = -exp(A_log)
               H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t   [head_dim, N]
               y_t = H_t C_t + D x_t
               g = y * silu(z);  RMS over each GROUP's d_inner / n_groups
               lanes, times the weight [d_inner];  Mixer = g W_out
    E  experts s = sigmoid(u W_g) in float32;  S = top-k of (s + bias)  (the
               bias, e_score_correction_bias, selects only)
               p_e = s_e / (sum_S s + 1e-20) x routed_scaling_factor
               Mixer = sum_{e in S} p_e W_down,e relu(W_up,e u)^2
                       + W_down,sh relu(W_up,sh u)^2
    *  attn    q = u W_q (heads x head_dim);  k = u W_k;  v = u W_v;  NO
               positional embedding, no q/k norm, no bias; causal
               softmax(q k^T / sqrt(head_dim)); query head h on key/value
               head h // (heads / kv heads);  Mixer = concat_h(a_h) W_o

A chip's SHARE (``held_experts`` = [first, count] with ``router_experts``
the router's published width and ``n_routed_experts`` the experts held):
the router keeps all its outputs and its k a token; only the held
experts' parts are added (their weights are the leaves), the shared
expert in full, and that partial sum goes on — nothing stands in for
the other chips.

Every projection goes through ``mm`` (the lower-precision control swaps
it); the recurrence, the convolution, the norms, the router's sigmoid
and the square are plain float32 arithmetic. Attention runs in blocks of
query rows; the experts run one after another over ALL tokens, each
weighted by its (mostly zero) routing weight. The model is computed
STAGE BY STAGE (:func:`stage_leaves`: the embedding, each layer, the
head), each needing only its own leaves, so that a caller can draw and
free the float32 weights a stage at a time
(``drivers/serve_routed_kinds.py``); :func:`forward` runs them all with
every leaf at hand.

Departures from the publication are in the configuration file
(``assumed``): no rotary embedding in the attention layers (the
published modelling code applies none; ``rope_theta`` and
``partial_rotary_factor`` are keys it does not read), each leaf's scale
(``init_scales``), ``dt_bias`` drawn around ``dt_bias_mean``
(:func:`dt_bias`), the selection bias drawn from the seed, ``d_inner``
as heads x head_dim (not ``expand`` x hidden), and the lanes the program
stores an expert's width in (:func:`pad_up`, :func:`pad_down`: zeros,
which ``relu(0)^2 = 0`` makes exact).

Pieces a control replaces are module attributes (``act``, ``gated_norm``,
``conv_bias``, ``skip``, ``selection_bias``, ``positions``,
``kept_state``): ``control_kinds.py`` patches them one at a time."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import matmul_f32
from .lfm2_moe import rms, rope_tables, rotate_half

STACKED = ()

KINDS = {"M": "ssm", "E": "moe", "*": "attn"}
_LEAVES = {
    "ssm": ("norm", "m_in", "m_conv", "m_convb", "m_dtb", "m_alog", "m_d",
            "m_norm", "m_out"),
    "moe": ("norm", "gate", "bias", "e_up", "e_down", "s_up", "s_down"),
    "attn": ("norm", "q", "k", "v", "o")}
_GAINS = ("norm", "m_norm", "m_d", "out_norm")


def kind(cfg: dict, i: int) -> str:
    """``ssm`` / ``moe`` / ``attn``: layer i's character of the pattern."""
    return KINDS[cfg["hybrid_override_pattern"][i]]


def router_width(cfg: dict) -> int:
    return cfg.get("router_experts") or cfg["n_routed_experts"]


def held(cfg: dict):
    """(first, count) of the experts whose weights are the leaves."""
    return tuple(cfg.get("held_experts") or (0, cfg["n_routed_experts"]))


def _dims(cfg):
    heads = cfg["mamba_num_heads"]
    d = heads * cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return d, gn, d + 2 * gn, heads


def leaf_specs(cfg: dict) -> dict:
    """{leaf: (shape, init, scale)}; 'normal' = N(0, scale), 'gain' =
    1 + N(0, scale). Every leaf has its own scale (``init_scales``), and
    every layer's matrices are leaves of their own (``l0_m_in`` ...): a
    stage draws only what it needs."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    d, _, conv_dim, mh = _dims(cfg)
    K = cfg["conv_kernel"]
    E, R = held(cfg)[1], router_width(cfg)
    Fe, Fs = (cfg["moe_intermediate_size"],
              cfg["moe_shared_expert_intermediate_size"])
    shapes = {"norm": (H,), "m_in": (H, d + conv_dim + mh),
              "m_conv": (K, conv_dim), "m_convb": (conv_dim,),
              "m_dtb": (mh,), "m_alog": (mh,), "m_d": (mh,),
              "m_norm": (d,), "m_out": (d, H),
              "gate": (H, R), "bias": (R,), "e_up": (E, H, Fe),
              "e_down": (E, Fe, H), "s_up": (H, Fs), "s_down": (Fs, H),
              "q": (H, nh * hd), "k": (H, nkv * hd), "v": (H, nkv * hd),
              "o": (nh * hd, H)}
    s = cfg["init_scales"]

    def spec(name, shape):
        return (shape, "gain" if name in _GAINS else "normal", s[name])

    out = {"embed": spec("embed", (V, H)),
           "out_norm": spec("out_norm", (H,)),
           "head": spec("head", (H, V))}
    for i in range(cfg["num_hidden_layers"]):
        for name in _LEAVES[kind(cfg, i)]:
            out[f"l{i}_{name}"] = spec(name, shapes[name])
    return out


def stage_leaves(cfg: dict) -> list:
    """[(stage, the leaves it reads)]: ``"embed"``, each layer's index,
    ``"head"``."""
    return [("embed", ["embed"])] + [
        (i, [f"l{i}_{name}" for name in _LEAVES[kind(cfg, i)]])
        for i in range(cfg["num_hidden_layers"])] \
        + [("head", ["out_norm", "head"])]


# -------------------------------------------------------- the controls' seams
def skip(name: str) -> bool:
    """Is the piece ``name`` (``d_skip``) left out? Never, in the
    reference; a control says yes to one."""
    return False


def act(x):
    """The experts' activation, ``relu(x)^2``."""
    return jnp.square(jax.nn.relu(x))


def conv_bias(b):
    return b


def selection_bias(b):
    return b


def positions(q, k, cfg):
    """What the attention layers do to q and k ``[B, S, heads, hd]`` for
    position's sake: nothing (positions come from the state-space
    layers). A control rotates them (:func:`rotated`)."""
    return q, k


def rotated(q, k, cfg):
    """The control's rotary embedding: rotate-half over all ``head_dim``
    lanes, base ``rope_theta`` — what the unread keys would describe."""
    S = q.shape[1]
    cos, sin = rope_tables(jnp.arange(S), q.shape[-1],
                           float(cfg["rope_theta"]))
    cos, sin = cos[None, :, None], sin[None, :, None]
    return (q * cos + rotate_half(q) * sin, k * cos + rotate_half(k) * sin)


def kept_state(H):
    """The recurrent state as it is carried from token to token: float32
    as computed. A control rounds it to bfloat16 at every token."""
    return H


def dt_bias(leaf, cfg):
    """The seeded ``dt_bias``: the leaf is drawn N(0, scale) and stands
    around ``dt_bias_mean`` (the published initialisation puts
    softplus(dt_bias) between ``time_step_min`` and ``time_step_max``,
    far from 0: ``assumed``), rounded to bfloat16 like every weight both
    sides start from."""
    return jax.lax.reduce_precision(cfg["dt_bias_mean"] + leaf, 8, 7)


def _pad_lanes(cfg) -> int:
    a = cfg.get("expert_width_align", 1)
    return -(-cfg["moe_intermediate_size"] // a) * a - \
        cfg["moe_intermediate_size"]


def pad_up(leaf, cfg):
    """``W_up [E, H, F]`` as the program stores it: zero columns up to
    whole ``expert_width_align`` lanes."""
    return jnp.pad(leaf, ((0, 0), (0, 0), (0, _pad_lanes(cfg))))


def pad_down(leaf, cfg):
    """``W_down [E, F, H]`` as the program stores it: zero rows."""
    return jnp.pad(leaf, ((0, 0), (0, _pad_lanes(cfg)), (0, 0)))


# leaf (by its name's ending) -> what the program's parameter holds
PLACED = {"_m_dtb": dt_bias, "_e_up": pad_up, "_e_down": pad_down}


def gated_norm(y, z, weight, cfg):
    """``RMS_grouped(y * silu(z)) * weight``: the statistic per group of
    ``d_inner / n_groups`` lanes."""
    g = y * jax.nn.silu(z)
    grouped = g.reshape(g.shape[:-1] + (cfg["n_groups"], -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True)
        + cfg["layer_norm_epsilon"])
    return grouped.reshape(g.shape) * weight


# ---------------------------------------------------------------- the pieces
def attn_op(u, p, i, cfg, mm):
    """u ``[B, S, H]`` -> Attn ``[B, S, H]``, causal, in blocks of query
    rows (16 query heads a key/value head of a 4,096-token sequence's
    scores never exist whole)."""
    B, S, _ = u.shape
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    g = nh // nkv
    w = lambda k: p[f"l{i}_{k}"]            # noqa: E731
    q = mm(u, w("q")).reshape(B, S, nh, hd)
    k = mm(u, w("k")).reshape(B, S, nkv, hd)
    v = mm(u, w("v")).reshape(B, S, nkv, hd)
    q, k = positions(q, k, cfg)
    qg = q.reshape(B, S, nkv, g, hd)
    rows = math.gcd(S, 256)

    def block(j):
        qb = jax.lax.dynamic_slice_in_dim(qg, j * rows, rows, 1)
        s = jnp.einsum("bqngd,bknd->bngqk", qb, k) / (hd ** 0.5)
        sees = (j * rows + jnp.arange(rows))[:, None] >= jnp.arange(S)
        return jnp.einsum("bngqk,bknd->bqngd",
                          jax.nn.softmax(jnp.where(sees, s, -jnp.inf), -1), v)

    a = jax.lax.map(block, jnp.arange(S // rows))    # [blocks, B, rows, ..]
    a = jnp.moveaxis(a, 0, 1).reshape(B, S, nh * hd)
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
        H = kept_state(jnp.exp(dtt * A)[:, None, None] * H
                       + (dtt[:, None] * xt)[:, :, None] * Bh[:, None, :])
        y = jnp.sum(H * Ch[:, None, :], -1)
        return H, y if skip("d_skip") else y + D[:, None] * xt

    h0 = jnp.zeros((heads, P, Bm.shape[-1]), jnp.float32)
    return jax.lax.scan(step, h0, (x, dt, Bm, Cm))[1]


def mixer_op(u, p, i, cfg, mm):
    B, S, _ = u.shape
    d, gn, conv_dim, mh = _dims(cfg)
    K = cfg["conv_kernel"]
    w = lambda k: p[f"l{i}_{k}"]            # noqa: E731
    proj = mm(u, w("m_in"))
    z, xbc, dt = (proj[..., :d], proj[..., d:d + conv_dim],
                  proj[..., d + conv_dim:])
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = conv_bias(w("m_convb")) + sum(
        w("m_conv")[j] * padded[:, j:j + S] for j in range(K))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d].reshape(B, S, mh, cfg["mamba_head_dim"])
    Bm = xbc[..., d:d + gn].reshape(B, S, cfg["n_groups"], -1)
    Cm = xbc[..., d + gn:].reshape(B, S, cfg["n_groups"], -1)
    dt = jax.nn.softplus(dt + dt_bias(w("m_dtb"), cfg))
    A = -jnp.exp(w("m_alog"))
    y = jax.vmap(lambda xs, dts, bs, cs: recurrence(
        xs, dts, A, bs, cs, w("m_d")))(x, dt, Bm, Cm)
    return mm(gated_norm(y.reshape(B, S, d), z, w("m_norm"), cfg),
              w("m_out"))


def relu2_mlp(a, up, down, mm):
    return mm(act(mm(a, up)), down)


def route(a, gate, bias, cfg, mm, forced=None):
    """(expert ids [.., k], weights [.., k], deficit [..]) in float32:
    the bias selects, the unbiased scores weigh. ``forced`` [.., k]
    hands in the experts another implementation chose (a row of -1
    leaves that row to its own top k); the weights are then the
    reference's scores AT those experts, and ``deficit`` says by how
    much, in the reference's own biased scores, the best expert left
    out beats the worst one taken (0 for the reference's own top k:
    ``reference/lfm2_moe.route``)."""
    s = jax.nn.sigmoid(mm(a.astype(jnp.float32), gate.astype(jnp.float32)))
    pick = s + selection_bias(bias.astype(jnp.float32))
    _, idx = jax.lax.top_k(pick, cfg["num_experts_per_tok"])
    if forced is not None:
        idx = jnp.where(forced >= 0, forced, idx)
    taken = jnp.sum(jax.nn.one_hot(idx, pick.shape[-1]), -2) > 0
    deficit = jnp.maximum(
        jnp.max(jnp.where(taken, -jnp.inf, pick), -1)
        - jnp.min(jnp.take_along_axis(pick, idx, -1), -1), 0.0)
    w = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"], deficit


def experts_op(a, p, i, cfg, mm, forced=None):
    """(shared + routed output, the experts used [.., k], the deficit of
    that choice [..]): routing over all experts, the parts of the
    experts held added."""
    w = lambda k: p[f"l{i}_{k}"]            # noqa: E731
    idx, wts, deficit = route(a, w("gate"), w("bias"), cfg, mm, forced)
    dense = jnp.sum(jax.nn.one_hot(idx, router_width(cfg),
                                   dtype=jnp.float32) * wts[..., None], -2)
    lo, n = held(cfg)

    def one(acc, xs):
        up, down, col = xs
        return acc + col[..., None] * relu2_mlp(a, up, down, mm), None

    cols = jnp.moveaxis(dense, -1, 0)[lo:lo + n]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(a),
                             (w("e_up"), w("e_down"), cols))
    return (relu2_mlp(a, w("s_up"), w("s_down"), mm) + routed, idx, deficit)


# ---------------------------------------------------------------- stages
def embed(p, ids):
    return p["embed"][ids]


def layer(p, i, x, cfg, mm=matmul_f32, forced=None):
    """Layer ``i`` on ``x [B, S, H]`` -> (x, experts used ``[B, S, k]``
    and the deficit of that choice ``[B, S]``, or None, None for a layer
    that routes nothing)."""
    u = rms(x, p[f"l{i}_norm"], cfg["layer_norm_epsilon"])
    what = kind(cfg, i)
    if what == "moe":
        out, idx, deficit = experts_op(u, p, i, cfg, mm, forced)
        return x + out, idx, deficit
    op = mixer_op if what == "ssm" else attn_op
    return x + op(u, p, i, cfg, mm), None, None


def head(p, x, cfg, mm=matmul_f32):
    return mm(rms(x, p["out_norm"], cfg["layer_norm_epsilon"]), p["head"])


def forward(params, ids, cfg, mm=matmul_f32, forced=None):
    """(logits ``[B, S, V]`` float32, the experts used ``[B, S, expert
    layers, k]``, the deficit of that choice ``[B, S, expert layers]``);
    ``forced`` ``[B, S, expert layers, k]`` hands in another
    implementation's experts (:func:`route`)."""
    x = embed(params, ids)
    used, deficits = [], []
    for i in range(cfg["num_hidden_layers"]):
        x, idx, deficit = layer(
            params, i, x, cfg, mm,
            None if forced is None or kind(cfg, i) != "moe"
            else forced[:, :, len(used)])
        if idx is not None:
            used.append(idx)
            deficits.append(deficit)
    return head(params, x, cfg, mm), jnp.stack(used, 2), \
        jnp.stack(deficits, 2)


def logits(params, ids, cfg, mm=matmul_f32):
    """[B, S, V] float32 logits."""
    return forward(params, ids, cfg, mm)[0]
