"""Plain reference of EXAONE-MoE (``LGAI-EXAONE/K-EXAONE-236B-A23B``,
model type ``exaone_moe``): sliding-window and global attention layers
three to one, a dense SwiGLU layer first and sigmoid-routed experts
beside a shared one after it. No cache, no ring, no kernel, no chunking:
float32 ``jax.numpy`` (the caller holds
``jax.default_matmul_precision("highest")``), the window as the DENSE
``[S, S]`` band mask. For ``x`` the residual stream ``[T, H]`` and every
norm an RMSNorm with ``rms_norm_eps``::

    stream   x_0 = Embed(ids)                    (no positional table)
             per layer:  h = x + Attn(RMS(x));   x = h + FF(RMS(h))
             logits = RMS_f(x) W_head                          (untied)
    Attn     q = a W_q (heads x head_dim);  k = a W_k;  v = a W_v   (no bias)
             q, k <- RMS over each head's head_dim lanes, learned weight
             sliding layer: q, k rotated (rotate-half over the whole head,
             rope_theta);  position i sees i - (sliding_window - 1) .. i
             global layer:  NOTHING rotated;  position i sees 0 .. i
             softmax(q k^T / sqrt(head_dim));  query head h on key/value
             head h // (heads / kv heads);  Attn = concat_h(a_h) W_o
    FF dense   (silu(b W_g) * (b W_u)) W_d               at intermediate_size
    FF sparse  s = sigmoid(b W_r) in float32;  S = top-k of (s + bias)  (the
             bias selects only);  p_e = routed_scaling_factor x s_e /
             (sum_S s + 1e-20)
             FF = sum_{e in S} p_e SwiGLU_e(b) + SwiGLU_shared(b)

A chip's SHARE (``held_experts`` = [first, count] with ``router_experts``
the router's published width and ``num_experts`` the experts held): the
router keeps all its outputs and its k a token; only the held experts'
parts are added (their weights are the leaves), the shared expert in
full, and that partial sum goes on — nothing stands in for the other
chips.

Every projection goes through ``mm`` (the lower-precision control swaps
it); the norms, the rotation, the router's sigmoid and the softmax are
plain float32 arithmetic. Attention runs in blocks of query rows (64
query heads of a 9,216-token sequence's scores never exist whole); the
experts run one after another over ALL tokens, each weighted by its
(mostly zero) routing weight. The model is computed STAGE BY STAGE
(:func:`stage_leaves`: the embedding, each layer, the head), each needing
only its own leaves, so that a caller can draw and free the float32
weights a stage at a time (``drivers/serve_routed_kinds.py``);
:func:`forward` runs them all with every leaf at hand.

A layer's attention is sliding or global BY THE LEAVES IT HOLDS: a
sliding layer's are ``wq wk wv wo wqn wkn`` (w for window), a global
layer's ``q k v o qn kn``. The staged driver compiles one program a
:func:`kind` (``dense`` / ``moe``: it asks the kind whether a layer
routes) and hands every layer of a kind its leaves under the kind's
first layer's names; the names that remain tell the two attentions
apart, and a jitted stage sees them as structure (one program each).

Departures from the publication are in the configuration file
(``assumed``). Pieces a control replaces are module attributes
(``head_norm``, ``sliding_positions``, ``global_positions``,
``selection_bias``): ``control_exaone.py`` patches them one at a time;
the window, the factor and the shared expert are bent through the
configuration and the leaves."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import matmul_f32
from .lfm2_moe import rms, rope_tables, rotate_half, swiglu

STACKED = ()
PLACED = {}                # every parameter is its leaf, cast

_ATTN = {True: ("wq", "wk", "wv", "wo", "wqn", "wkn"),
         False: ("q", "k", "v", "o", "qn", "kn")}
_FF = {"dense": ("d_gate", "d_up", "d_down"),
       "moe": ("gate", "bias", "e_gate", "e_up", "e_down", "s_gate", "s_up",
               "s_down")}
_GAINS = ("norm1", "norm2", "out_norm", "qn", "kn", "wqn", "wkn")


def kind(cfg: dict, i: int) -> str:
    """``dense`` / ``moe``: layer i's feed-forward (``moe`` routes)."""
    return "dense" if cfg["mlp_layer_types"][i] == "dense" else "moe"


def is_sliding(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "sliding_attention"


def router_width(cfg: dict) -> int:
    return cfg.get("router_experts") or cfg["num_experts"]


def held(cfg: dict):
    """(first, count) of the experts whose weights are the leaves."""
    return tuple(cfg.get("held_experts") or (0, cfg["num_experts"]))


def layer_leaves(cfg: dict, i: int) -> tuple:
    return ("norm1",) + _ATTN[is_sliding(cfg, i)] + ("norm2",) \
        + _FF[kind(cfg, i)]


def leaf_specs(cfg: dict) -> dict:
    """{leaf: (shape, init, scale)}; 'normal' = N(0, scale), 'gain' =
    1 + N(0, scale). Every leaf has its own scale (``init_scales``; a
    sliding layer's projections take the global ones' scales), and every
    layer's matrices are leaves of their own: a stage draws only what it
    needs."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    F, Fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    Fs = Fe * cfg["num_shared_experts"]
    E, R = held(cfg)[1], router_width(cfg)
    shapes = {"norm1": (H,), "norm2": (H,), "q": (H, nh * hd),
              "k": (H, nkv * hd), "v": (H, nkv * hd), "o": (nh * hd, H),
              "qn": (hd,), "kn": (hd,),
              "d_gate": (H, F), "d_up": (H, F), "d_down": (F, H),
              "gate": (H, R), "bias": (R,), "e_gate": (E, H, Fe),
              "e_up": (E, H, Fe), "e_down": (E, Fe, H), "s_gate": (H, Fs),
              "s_up": (H, Fs), "s_down": (Fs, H)}
    s = cfg["init_scales"]

    def spec(name, plain):
        return (shapes[plain],
                "gain" if name in _GAINS else "normal", s[plain])

    out = {"embed": ((V, H), "normal", s["embed"]),
           "out_norm": ((H,), "gain", s["out_norm"]),
           "head": ((H, V), "normal", s["head"])}
    for i in range(cfg["num_hidden_layers"]):
        for name in layer_leaves(cfg, i):
            plain = name[1:] if name in _ATTN[True] else name
            out[f"l{i}_{name}"] = spec(name, plain)
    return out


def stage_leaves(cfg: dict) -> list:
    """[(stage, the leaves it reads)]: ``"embed"``, each layer's index,
    ``"head"``."""
    return [("embed", ["embed"])] + [
        (i, [f"l{i}_{name}" for name in layer_leaves(cfg, i)])
        for i in range(cfg["num_hidden_layers"])] \
        + [("head", ["out_norm", "head"])]


# -------------------------------------------------------- the controls' seams
def head_norm(x, g, eps):
    """The per-head RMS norm of q and k. A control drops it."""
    return rms(x, g, eps)


def rotated(q, k, cfg):
    """q, k ``[B, S, heads, hd]`` rotated: rotate-half over all
    ``head_dim`` lanes, base ``rope_theta``."""
    S = q.shape[1]
    cos, sin = rope_tables(jnp.arange(S), q.shape[-1],
                           float(cfg["rope_parameters"]["rope_theta"]))
    cos, sin = cos[None, :, None], sin[None, :, None]
    return (q * cos + rotate_half(q) * sin, k * cos + rotate_half(k) * sin)


def sliding_positions(q, k, cfg):
    """What a sliding layer does to q and k for position's sake: it
    rotates them. A control rotates nothing."""
    return rotated(q, k, cfg)


def global_positions(q, k, cfg):
    """What a global layer does: nothing. A control rotates."""
    return q, k


def selection_bias(b):
    return b


# ---------------------------------------------------------------- the pieces
def attn_op(u, p, i, cfg, mm):
    """u ``[B, S, H]`` -> Attn ``[B, S, H]``, causal, a sliding layer's
    under the band ``0 <= i - j < sliding_window``; in blocks of query
    rows."""
    B, S, _ = u.shape
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    g = nh // nkv
    sliding = f"l{i}_wq" in p
    w = lambda k: p[f"l{i}_{'w' if sliding else ''}{k}"]     # noqa: E731
    eps = cfg["rms_norm_eps"]
    q = head_norm(mm(u, w("q")).reshape(B, S, nh, hd), w("qn"), eps)
    k = head_norm(mm(u, w("k")).reshape(B, S, nkv, hd), w("kn"), eps)
    v = mm(u, w("v")).reshape(B, S, nkv, hd)
    q, k = (sliding_positions if sliding else global_positions)(q, k, cfg)
    back = cfg["sliding_window"] if sliding else S
    qg = q.reshape(B, S, nkv, g, hd)
    rows = math.gcd(S, 256)

    def block(j):
        qb = jax.lax.dynamic_slice_in_dim(qg, j * rows, rows, 1)
        s = jnp.einsum("bqngd,bknd->bngqk", qb, k) / (hd ** 0.5)
        ago = (j * rows + jnp.arange(rows))[:, None] - jnp.arange(S)
        sees = (ago >= 0) & (ago < back)
        return jnp.einsum("bngqk,bknd->bqngd",
                          jax.nn.softmax(jnp.where(sees, s, -jnp.inf), -1), v)

    a = jax.lax.map(block, jnp.arange(S // rows))    # [blocks, B, rows, ..]
    a = jnp.moveaxis(a, 0, 1).reshape(B, S, nh * hd)
    return mm(a, w("o"))


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
        w1, w3, w2, col = xs
        return acc + col[..., None] * swiglu(a, w1, w3, w2, mm), None

    cols = jnp.moveaxis(dense, -1, 0)[lo:lo + n]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(a),
                             (w("e_gate"), w("e_up"), w("e_down"), cols))
    shared = swiglu(a, w("s_gate"), w("s_up"), w("s_down"), mm)
    return shared + routed, idx, deficit


# ---------------------------------------------------------------- stages
def embed(p, ids):
    return p["embed"][ids]


def layer(p, i, x, cfg, mm=matmul_f32, forced=None):
    """Layer ``i`` on ``x [B, S, H]`` -> (x, experts used ``[B, S, k]``
    and the deficit of that choice ``[B, S]``, or None, None for the
    dense layer). Sliding or global by the attention leaves ``p`` holds
    under ``l{i}_``; dense or sparse likewise."""
    eps = cfg["rms_norm_eps"]
    h = x + attn_op(rms(x, p[f"l{i}_norm1"], eps), p, i, cfg, mm)
    b = rms(h, p[f"l{i}_norm2"], eps)
    if f"l{i}_d_gate" in p:
        return h + swiglu(b, p[f"l{i}_d_gate"], p[f"l{i}_d_up"],
                          p[f"l{i}_d_down"], mm), None, None
    out, idx, deficit = experts_op(b, p, i, cfg, mm, forced)
    return h + out, idx, deficit


def head(p, x, cfg, mm=matmul_f32):
    return mm(rms(x, p["out_norm"], cfg["rms_norm_eps"]), p["head"])


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
