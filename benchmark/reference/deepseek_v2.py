"""Plain reference of DeepSeek-V2 (``deepseek-ai/DeepSeek-V2``, model
type ``deepseek_v2``): token embedding; every layer ``h = x +
Attn(RMS(x))``, ``y = h + FF(RMS(h))``; final RMSNorm; an untied head.
The EXPANDED form of latent attention throughout, no cache, no kernel:

    RMS(x; g)  = x / sqrt(mean(x^2) + eps) * g
    queries    c_q = RMS(x W_qa);  q = c_q W_qb -> per head [q_nope | q_rope]
    latent     [c_kv | k_rope] = x W_kva;  c = RMS(c_kv)   (k_rope: ONE
               vector for all heads)
    expanded   [k_nope | v] per head = c W_kvb
               q_h = [q_nope | RoPE(q_rope)],  k_h = [k_nope | RoPE(k_rope)]
               a_h = softmax(s q_h k_h^T + causal) v_h;  Attn = concat(a_h) W_o
    rotary     YaRN on the rope lanes: f_i = theta^(-2i/d), g_i = f_i /
               factor, ramp between the dimensions that turn beta_fast
               and beta_slow times over the original context; rotate-half
               over the lanes AS THE PROJECTION GIVES THEM (the published
               code de-interleaves first: a fixed permutation of W_qb's
               and W_kva's rope columns, the same model under seeded
               weights — ``assumed`` in the configuration); cos and sin
               carry mscale(f, mscale) / mscale(f, mscale_all_dim)
    scale      s = (d_nope + d_rope)^-0.5 * mscale(f, mscale_all_dim)^2,
               mscale(f, m) = 0.1 m ln f + 1
    layer 0..  first_k_dense_replace dense SwiGLU layers, then
    experts    p = softmax(a W_g) over ALL routed experts (float32);
               group score = the largest p of the group's experts (the
               groups are contiguous); the topk_group best groups kept;
               S = the k largest p among their experts; weights p_e
               themselves (norm_topk_prob false) x routed_scaling_factor;
               FF = Shared(a) + sum_{e in S} w_e Expert_e(a), Shared ONE
               SwiGLU of n_shared_experts x the expert width
    head       logits = RMS(x) W_head

A chip's SHARE (``held_group`` g with ``router_experts`` the router's
published width and ``n_routed_experts`` the experts held): the router
keeps all its outputs and its k a token; only group g's experts' parts
are added (their weights are the leaves), the shared experts in full,
and that partial sum goes on — nothing stands in for the other chips.

Every projection goes through ``mm`` (the lower-precision control swaps
it). Attention runs in blocks of query rows, so that 128 heads of
scores of a 6,144-token sequence never exist whole; the experts run one
after another over ALL tokens, each weighted by its (mostly zero)
routing weight. The model is computed STAGE BY STAGE (:func:`stages`:
the embedding, each layer, the head), each needing only its own leaves
(:func:`stage_leaves`), so that a caller can draw and free the float32
weights one layer at a time (``drivers/serve_routed_staged.py``);
:func:`forward` runs them all with every leaf at hand.

Not served: ``seq_aux`` (a training loss)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import matmul_f32
from .lfm2_moe import rms, rotate_half, swiglu

STACKED = ()

_ATTN = ("attn_norm", "ffn_norm", "qa", "qa_norm", "qb", "kva", "kva_norm",
         "kvb", "o")
_DENSE = ("w1", "w3", "w2")
_MOE = ("sw1", "sw3", "sw2", "gate", "w1", "w3", "w2")


def router_width(cfg: dict) -> int:
    return cfg.get("router_experts") or cfg["n_routed_experts"]


def held(cfg: dict):
    """(first, count) of the experts whose weights are the leaves."""
    n = cfg["n_routed_experts"]
    return (cfg.get("held_group") or 0) * n if n < router_width(cfg) else 0, n


def is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


def leaf_specs(cfg: dict) -> dict:
    """{leaf: (shape, init, scale)}; 'normal' = N(0, scale), 'gain' =
    1 + N(0, scale). Every layer's matrices are leaves of their own
    (``l0_qa`` ...): a stage draws only what it needs."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    F, Fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    nh, rq, rkv = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                   cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    E, Fs = cfg["n_routed_experts"], cfg["n_shared_experts"] * Fe
    std = cfg["initializer_range"]
    out = {"embed": ((V, H), "normal", std),
           "head": ((H, V), "normal", std),
           "out_norm": ((H,), "gain", std)}
    for i in range(cfg["num_hidden_layers"]):
        layer = {"attn_norm": ((H,), "gain"), "ffn_norm": ((H,), "gain"),
                 "qa": ((H, rq), "normal"), "qa_norm": ((rq,), "gain"),
                 "qb": ((rq, nh * (dn + dr)), "normal"),
                 "kva": ((H, rkv + dr), "normal"),
                 "kva_norm": ((rkv,), "gain"),
                 "kvb": ((rkv, nh * (dn + dv)), "normal"),
                 "o": ((nh * dv, H), "normal")}
        if is_dense(cfg, i):
            layer.update(w1=((H, F), "normal"), w3=((H, F), "normal"),
                         w2=((F, H), "normal"))
        else:
            layer.update(
                sw1=((H, Fs), "normal"), sw3=((H, Fs), "normal"),
                sw2=((Fs, H), "normal"),
                gate=((H, router_width(cfg)), "normal"),
                w1=((E, H, Fe), "normal"), w3=((E, H, Fe), "normal"),
                w2=((E, Fe, H), "normal"))
        out.update({f"l{i}_{k}": v + (std,) for k, v in layer.items()})
    return out


def stage_leaves(cfg: dict) -> list:
    """[(stage, its leaves)]: 'embed', each layer's index, 'head'."""
    out = [("embed", ["embed"])]
    for i in range(cfg["num_hidden_layers"]):
        names = _ATTN + (_DENSE if is_dense(cfg, i) else _MOE)
        out.append((i, [f"l{i}_{k}" for k in names]))
    return out + [("head", ["out_norm", "head"])]


# ---------------------------------------------------------------- rotary
def mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_tables(positions, cfg: dict):
    """cos, sin ``[S, d_rope]`` float32 (half tables repeated: the
    rotate-half layout) at integer ``positions``, YaRN-scaled where the
    configuration says so."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ys = cfg.get("rope_scaling")
    amp = 1.0
    if ys:
        def dim_of(rotations):
            return d * math.log(ys["original_max_position_embeddings"]
                                / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))
        low = max(math.floor(dim_of(ys["beta_fast"])), 0)
        high = min(math.ceil(dim_of(ys["beta_slow"])), d - 1)
        ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                       0, 1)
        f = f / ys["factor"] * ramp + f * (1 - ramp)
        amp = mscale(ys["factor"], ys["mscale"]) \
            / mscale(ys["factor"], ys["mscale_all_dim"])
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(f, jnp.float32)
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang) * amp, jnp.sin(ang) * amp


def softmax_scale(cfg: dict) -> float:
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    ys = cfg.get("rope_scaling")
    return s * mscale(ys["factor"], ys["mscale_all_dim"]) ** 2 if ys else s


def rope(x, cos, sin):
    return x * cos + rotate_half(x) * sin


# what a control replaces to show that the check would notice
rope_key = rope
latent_norm = rms


# ------------------------------------------------------------- attention
def attn_op(u, p, i, cfg, mm):
    """u ``[B, S, H]`` -> Attn ``[B, S, H]``, expanded, causal."""
    B, S, _ = u.shape
    nh, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps, w = cfg["rms_norm_eps"], lambda k: p[f"l{i}_{k}"]
    cos, sin = rope_tables(jnp.arange(S), cfg)
    q = mm(rms(mm(u, w("qa")), w("qa_norm"), eps), w("qb")).reshape(
        B, S, nh, dn + dr)
    kv = mm(u, w("kva"))
    c = latent_norm(kv[..., :rkv], w("kva_norm"), eps)
    k_rope = rope_key(kv[..., rkv:], cos, sin)               # [B, S, dr]
    kvb = mm(c, w("kvb")).reshape(B, S, nh, dn + dv)
    qh = jnp.concatenate(
        [q[..., :dn], rope(q[..., dn:], cos[:, None], sin[:, None])], -1)
    kh = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_rope[:, :, None],
                                         (B, S, nh, dr))], -1)
    v, scale = kvb[..., dn:], softmax_scale(cfg)
    rows = math.gcd(S, 256)         # a block of query rows, all heads

    def block(j):
        qb = jax.lax.dynamic_slice_in_dim(qh, j * rows, rows, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kh) * scale
        sees = (j * rows + jnp.arange(rows))[:, None] >= jnp.arange(S)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(jnp.where(sees, s, -jnp.inf), -1), v)

    a = jax.lax.map(block, jnp.arange(S // rows))    # [blocks, B, rows, ..]
    a = jnp.moveaxis(a, 0, 1).reshape(B, S, nh * dv)
    return mm(a, w("o"))


# --------------------------------------------------------------- experts
def route(a, gate, cfg, mm, forced=None):
    """(expert ids [.., k], weights [.., k], deficit [..]) in float32.
    ``forced`` [.., k] hands in the experts another implementation
    chose (a row of -1 leaves that row to its own choice); the weights
    are then the reference's probabilities AT those experts, and
    ``deficit`` says how far that choice is from one the reference's own
    probabilities allow, on the two levels of the choice: by how much
    the reference's ``topk_group``-th best group beats the worst group
    the choice reaches, and by how much the best expert left out, among
    the groups the choice implies (those it reaches, filled up with the
    reference's best), beats the worst one taken. 0 for the reference's
    own choice; top k is discontinuous, so a sound program in bf16 reads
    a rounding's worth (``reference/lfm2_moe.route``)."""
    E, G, kg = router_width(cfg), cfg["n_group"], cfg["topk_group"]
    pr = jax.nn.softmax(mm(a.astype(jnp.float32),
                           gate.astype(jnp.float32)), -1)
    lead = pr.shape[:-1]
    g = pr.reshape(lead + (G, E // G)).max(-1)               # group scores
    best, groups = jax.lax.top_k(g, kg)
    kept = jnp.sum(jax.nn.one_hot(groups, G), -2) > 0
    _, idx = jax.lax.top_k(
        jnp.where(jnp.repeat(kept, E // G, -1), pr, 0.0),
        cfg["num_experts_per_tok"])
    if forced is not None:
        idx = jnp.where(forced >= 0, forced, idx)
    taken = jnp.sum(jax.nn.one_hot(idx, E), -2) > 0
    w = jnp.take_along_axis(pr, idx, -1)
    reached = taken.reshape(lead + (G, E // G)).any(-1)
    d_group = best[..., -1] - jnp.min(jnp.where(reached, g, jnp.inf), -1)
    _, fill = jax.lax.top_k(jnp.where(reached, jnp.inf, g), kg)
    implied = reached | (jnp.sum(jax.nn.one_hot(fill, G), -2) > 0)
    left_out = jnp.repeat(implied, E // G, -1) & ~taken
    d_expert = jnp.max(jnp.where(left_out, pr, -jnp.inf), -1) \
        - jnp.min(w, -1)
    deficit = jnp.maximum(jnp.maximum(d_group, d_expert), 0.0)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return idx, w * cfg["routed_scaling_factor"], deficit


def experts_ff(a, p, i, cfg, mm, forced=None):
    """(shared + routed output, the experts used [.., k], the deficit
    of that choice [..]): routing over all experts, the parts of the
    experts held added."""
    w = lambda k: p[f"l{i}_{k}"]            # noqa: E731
    idx, wts, deficit = route(a, w("gate"), cfg, mm, forced)
    dense = jnp.sum(jax.nn.one_hot(idx, router_width(cfg),
                                   dtype=jnp.float32) * wts[..., None], -2)
    lo, n = held(cfg)

    def one(acc, xs):
        w1, w3, w2, col = xs
        return acc + col[..., None] * swiglu(a, w1, w3, w2, mm), None

    cols = jnp.moveaxis(dense, -1, 0)[lo:lo + n]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(a),
                             (w("w1"), w("w3"), w("w2"), cols))
    return (swiglu(a, w("sw1"), w("sw3"), w("sw2"), mm) + routed, idx,
            deficit)


# ---------------------------------------------------------------- stages
def embed(p, ids):
    return p["embed"][ids]


def layer(p, i, x, cfg, mm=matmul_f32, forced=None):
    """One layer on ``x [B, S, H]`` -> (x, experts used ``[B, S, k]``
    and the deficit of that choice ``[B, S]``, or None, None for a dense
    layer)."""
    eps = cfg["rms_norm_eps"]
    x = x + attn_op(rms(x, p[f"l{i}_attn_norm"], eps), p, i, cfg, mm)
    a = rms(x, p[f"l{i}_ffn_norm"], eps)
    if is_dense(cfg, i):
        return x + swiglu(a, p[f"l{i}_w1"], p[f"l{i}_w3"], p[f"l{i}_w2"],
                          mm), None, None
    ff, idx, deficit = experts_ff(a, p, i, cfg, mm, forced)
    return x + ff, idx, deficit


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
            None if forced is None or is_dense(cfg, i)
            else forced[:, :, len(used)])
        if idx is not None:
            used.append(idx)
            deficits.append(deficit)
    return head(params, x, cfg, mm), jnp.stack(used, 2), \
        jnp.stack(deficits, 2)


def logits(params, ids, cfg, mm=matmul_f32):
    """[B, S, V] float32 logits."""
    return forward(params, ids, cfg, mm)[0]
