"""Plain reference of SDAR-MoE (``JetLM/SDAR-30B-A3B-Chat``, model type
``sdar_moe``), a block-diffusion language model: token embedding; every
layer ``h = x + Attn(RMS(x))``, ``y = h + Experts(RMS(h))``; final
RMSNorm; an untied head.

    RMS(x; g)  = x / sqrt(mean(x^2) + eps) * g
    attention  q, k, v = u W_q, u W_k, u W_v; per-head RMS of q and k;
               rotary (rotate-half, base rope_theta) on q, k at the
               token's POSITION; softmax(q k^T / sqrt(head_dim) + M) v,
               query head h on key/value head h // (heads / kv heads);
               Attn = heads W_o
    experts    p = softmax(a W_g) over all experts; S = top8(p);
               w_e = p_e / sum_S p (norm_topk_prob);
               FF = sum_{e in S} w_e (silu(a W1e) * (a W3e)) W2e
    head       logits_i = RMS(x_i) W_head: position i's logits give the
               token AT position i (a masked position is fed [MASK] and
               predicts itself): no shift

The mask M is HANDED IN (``mask[i, j]``: row i sees column j) together
with the rows' positions, because one network serves three uses:

* a sequence under the block-causal mask (:func:`block_causal_mask`:
  with block length B position i sees j iff ``j // B <= i // B`` — every
  earlier block and ALL of its own);
* generation (:func:`generate`, the family's published loop written
  plainly, no cache: every pass is a whole forward);
* the check of what a serving program did, pass by pass, in ONE forward
  per pass index over ``[clean sequence ; a noisy copy of its generated
  blocks]`` under the published training mask (:func:`clean_noisy`): a
  noisy block sees the clean blocks before it and itself, which is what
  a denoise pass over that block against the cache sees.

Every matmul goes through ``mm`` (the lower-precision control swaps
it); the experts run one after another over ALL tokens, each weighted
by its (mostly zero) routing weight; attention runs one query head at a
time, so nothing ``[heads, S, S]`` is live beside 12 GB of weights.

Departures from the publication are in the configuration file
(``assumed``): block length, ``[MASK]`` id, greedy
``low_confidence_static`` un-masking that never re-fixes a fixed
position, the initialisation scales."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import matmul_f32
from .lfm2_moe import rms, rope_tables, rotate_half, swiglu

STACKED = ("attn_norm", "ffn_norm")


def leaf_specs(cfg: dict) -> dict:
    """{leaf: (shape, init, scale)}; 'normal' = N(0, scale), 'gain' =
    1 + N(0, scale). A layer's matrices are leaves of their own
    (``l0_q`` ...): one stacked leaf of all layers' experts would be
    drawn in float32 whole."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    Fe, E = cfg["moe_intermediate_size"], cfg["num_experts"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    L, std = cfg["num_hidden_layers"], cfg["initializer_range"]
    out = {"embed": ((V, H), "normal", std),
           "head": ((H, V), "normal", std),
           "attn_norm": ((L, H), "gain", std),
           "ffn_norm": ((L, H), "gain", std),
           "out_norm": ((H,), "gain", std)}
    for i in range(L):
        out.update({
            f"l{i}_q": ((H, nh * hd), "normal", std),
            f"l{i}_k": ((H, nkv * hd), "normal", std),
            f"l{i}_v": ((H, nkv * hd), "normal", std),
            f"l{i}_o": ((nh * hd, H), "normal", std),
            f"l{i}_qnorm": ((hd,), "gain", std),
            f"l{i}_knorm": ((hd,), "gain", std),
            f"l{i}_gate": ((H, E), "normal", std),
            f"l{i}_w1": ((E, H, Fe), "normal", std),
            f"l{i}_w3": ((E, H, Fe), "normal", std),
            f"l{i}_w2": ((E, Fe, H), "normal", std)})
    return out


def block_causal_mask(n: int, block: int):
    """``[n, n]`` bool: row i sees column j iff ``j // block <= i //
    block`` (block 1: causal)."""
    b = np.arange(n) // block
    return b[None, :] <= b[:, None]


def clean_noisy(n_clean: int, first: int, block: int):
    """(positions ``[n_clean + m]``, mask ``[n_clean + m, n_clean +
    m]``) of ``[clean ; noisy]`` with ``m = n_clean - first``: the clean
    sequence's positions under the block-causal mask, then a noisy copy
    of positions ``first ..`` (``first`` a block's start), each noisy
    block seeing the clean blocks BEFORE it and itself. No clean row
    sees a noisy column."""
    m = n_clean - first
    pos = np.concatenate([np.arange(n_clean), np.arange(first, n_clean)])
    blk = pos // block
    noisy = np.arange(n_clean + m) >= n_clean
    sees = np.where(noisy[None, :],
                    noisy[:, None] & (blk[None, :] == blk[:, None]),
                    np.where(noisy[:, None], blk[None, :] < blk[:, None],
                             blk[None, :] <= blk[:, None]))
    return pos, sees


def attn_op(u, p, i, cfg, mm, mask, positions):
    B, S, _ = u.shape
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    g, eps = nh // nkv, cfg["rms_norm_eps"]
    q = mm(u, p[f"l{i}_q"]).reshape(B, S, nh, hd)
    k = mm(u, p[f"l{i}_k"]).reshape(B, S, nkv, hd)
    v = mm(u, p[f"l{i}_v"]).reshape(B, S, nkv, hd)
    q = rms(q, p[f"l{i}_qnorm"], eps)
    k = rms(k, p[f"l{i}_knorm"], eps)
    cos, sin = rope_tables(positions, hd, cfg["rope_theta"])
    cos, sin = cos[None, :, None], sin[None, :, None]
    q = q * cos + rotate_half(q) * sin
    k = k * cos + rotate_half(k) * sin

    def one_head(h):
        s = jnp.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h // g]) \
            / (hd ** 0.5)
        s = jnp.where(mask, s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1),
                          v[:, :, h // g])

    a = jax.lax.map(one_head, jnp.arange(nh))            # [nh, B, S, hd]
    return mm(jnp.moveaxis(a, 0, 2).reshape(B, S, nh * hd), p[f"l{i}_o"])


def route(a, gate, cfg, mm, forced=None):
    """(expert ids [.., k], weights [.., k], deficit [..]) in float32.
    ``forced`` [.., k] hands in the experts another implementation
    chose (a row of -1 leaves that row to its own top k); the weights
    are then the reference's probabilities AT those experts, and
    ``deficit`` says by how much, in the reference's own probabilities,
    the best expert left out beats the worst one taken (0 for the
    reference's own top k). Top k is discontinuous: see
    ``reference/lfm2_moe.route``."""
    pr = jax.nn.softmax(mm(a.astype(jnp.float32),
                           gate.astype(jnp.float32)), -1)
    _, idx = jax.lax.top_k(pr, cfg["num_experts_per_tok"])
    if forced is not None:
        idx = jnp.where(forced >= 0, forced, idx)
    taken = jnp.sum(jax.nn.one_hot(idx, pr.shape[-1]), -2) > 0
    w = jnp.take_along_axis(pr, idx, -1)
    deficit = jnp.maximum(
        jnp.max(jnp.where(taken, -jnp.inf, pr), -1) - jnp.min(w, -1), 0.0)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return idx, w, deficit


def experts_ff(a, p, i, cfg, mm, held=None, forced=None):
    """(the expert layer's output, the experts used [.., k], the
    deficit of that choice [..]); ``held`` = (first, count) keeps only
    that contiguous share of the experts' parts (routing is over all)."""
    E = cfg["num_experts"]
    idx, w, deficit = route(a, p[f"l{i}_gate"], cfg, mm, forced)
    dense = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                    * w[..., None], -2)
    lo, n = held or (0, E)

    def one(acc, xs):
        w1, w3, w2, col = xs
        return acc + col[..., None] * swiglu(a, w1, w3, w2, mm), None

    cols = jnp.moveaxis(dense, -1, 0)[lo:lo + n]
    out, _ = jax.lax.scan(one, jnp.zeros_like(a), (
        p[f"l{i}_w1"][lo:lo + n], p[f"l{i}_w3"][lo:lo + n],
        p[f"l{i}_w2"][lo:lo + n], cols))
    return out, idx, deficit


def forward(params, ids, cfg, mm=matmul_f32, mask=None, forced=None,
            positions=None, head_from: int = 0):
    """One forward of ``ids [B, S]`` under ``mask [S, S]`` (default:
    block-causal by ``cfg["block_length"]``) at ``positions [S]``
    (default ``0 .. S-1``): (logits ``[B, S - head_from, V]`` float32
    of the rows from ``head_from`` on, the experts used ``[B, S, layers,
    k]``, the deficit of that choice ``[B, S, layers]``). ``forced``
    ``[B, S, layers, k]`` hands in another implementation's experts
    (:func:`route`)."""
    S = ids.shape[1]
    if mask is None:
        mask = block_causal_mask(S, cfg["block_length"])
    if positions is None:
        positions = jnp.arange(S)
    mask = jnp.asarray(mask)
    eps = cfg["rms_norm_eps"]
    x = params["embed"][ids]
    used, deficits = [], []
    for i in range(cfg["num_hidden_layers"]):
        x = x + attn_op(rms(x, params["attn_norm"][i], eps), params, i,
                        cfg, mm, mask, positions)
        ff, idx, deficit = experts_ff(
            rms(x, params["ffn_norm"][i], eps), params, i, cfg, mm,
            forced=None if forced is None else forced[:, :, i])
        x = x + ff
        used.append(idx)
        deficits.append(deficit)
    x = rms(x[:, head_from:], params["out_norm"], eps)
    return (mm(x, params["head"]), jnp.stack(used, 2),
            jnp.stack(deficits, 2))


def logits(params, ids, cfg, mm=matmul_f32):
    """[B, S, V] float32 logits under the block-causal mask."""
    return forward(params, ids, cfg, mm)[0]


def confidence(lg):
    """(argmax token, its log-probability) of logits ``[..., V]``."""
    return jnp.argmax(lg, -1), jnp.max(lg, -1) - jax.nn.logsumexp(lg, -1)


def choose(conf, masked, n_fix: int):
    """The ``n_fix`` masked positions of highest confidence (ties: the
    earlier position), as a bool vector; numpy."""
    score = np.where(masked, np.asarray(conf, np.float64), -np.inf)
    order = np.argsort(-score, kind="stable")[:n_fix]
    fix = np.zeros(len(score), bool)
    fix[order] = True
    return fix & masked


def generate(params, prompt, max_new: int, cfg, steps=None,
             mm=matmul_f32):
    """The family's published loop, plainly (greedy,
    ``low_confidence_static``; no cache: every pass is a whole forward
    of the committed tokens and the block under the block-causal mask,
    which is what a pass against the cache of committed blocks
    computes). The prompt's whole blocks are given; what is left of it
    opens the first block, already fixed; a block's ``steps`` denoise
    passes each fix ``B / steps`` masked positions (fewer when fewer are
    left) and the block is then committed — a block is computed whole
    even where ``max_new`` ends inside it. Returns (the ``max_new``
    tokens, the record [(block start, ids after the pass with -1 where
    still masked)] of every denoise pass in order)."""
    B = cfg["block_length"]
    steps = steps or B
    per = B // steps
    seq = [int(t) for t in prompt]
    limit = len(seq) + max_new
    record = []
    fwd = jax.jit(lambda ids: forward(params, ids, cfg, mm)[0])
    total = -(-limit // B) * B      # one shape: no row sees the zeros
    while len(seq) < limit:
        start = len(seq) // B * B
        ids = np.full(B, cfg["mask_token_id"], np.int64)
        ids[:len(seq) - start] = seq[start:]
        masked = np.arange(B) >= len(seq) - start
        while masked.any():
            feed = np.where(masked, cfg["mask_token_id"], ids)
            padded = np.zeros((1, total), np.int32)
            padded[0, :start] = seq[:start]
            padded[0, start:start + B] = feed
            lg = fwd(jnp.asarray(padded))[0, start:start + B]
            tok, conf = confidence(lg)
            fix = choose(conf, masked, min(per, int(masked.sum())))
            ids = np.where(fix, np.asarray(tok), ids)
            masked &= ~fix
            record.append((start, np.where(masked, -1, ids)))
        seq = seq[:start] + ids.tolist()
    return seq[len(prompt):limit], record
