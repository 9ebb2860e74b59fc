"""DeepSeek-V2 decoder family (``deepseek-ai/DeepSeek-V2``, model type
``deepseek_v2``): RMSNorm pre-norms, per layer

    h = x + Attn(RMS(x)),   y = h + FF(RMS(h))

with multi-head LATENT attention and, after ``first_k_dense_replace``
dense SwiGLU layers, shared experts beside softmax-routed experts under
group-limited routing; a final RMSNorm and an UNTIED head.

Latent attention (MLA). Queries come through a low-rank pair, ``c_q =
RMS(x W_qa)``, ``q = c_q W_qb``: per head ``[q_nope | q_rope]``. Keys and
values come from ONE latent vector a token, ``[c_kv | k_rope] = x
W_kva``, ``c = RMS(c_kv)``: the cache holds ``[c | RoPE(k_rope)]``
(``kv_lora_rank + qk_rope_head_dim`` numbers a token and layer) and
nothing per head. Two associations of the same product:

* EXPANDED (:meth:`LatentAttention.full`, a whole causal sequence: the
  model's forward and the serving prefill): ``[k_nope | v]`` per head
  ``= c W_kvb``; ``k_h = [k_nope | RoPE(k_rope)]`` (the rotary part is
  one vector shared by all heads); flash attention with query/key width
  ``qk_nope + qk_rope`` and value width ``v_head_dim``. HEAD-MAJOR: the
  projections write q, k and v as (batch, heads, seq, dim), the flash
  kernels' own layout, taken by ``kernels.attention.attention_bhsd`` ->
  ``pallas_flash.flash_attention_bhsd`` (every other family holds the
  reference's (batch, seq, heads, dim) and goes through
  ``scaled_dot_product_attention`` -> ``flash_attention_bshd``), and
  ``W_o`` contracts over (heads, dim) of the kernel's output.
* ABSORBED (:meth:`LatentAttention.absorb` / :meth:`unabsorb`, one new
  token against the cache: the serving decode): ``W_kvb`` is split per
  head into ``W_UK`` and ``W_UV``; ``q'_h = [q_nope W_UK^T |
  RoPE(q_rope)]`` is scored against the cached vectors themselves, the
  softmax weighs the latents ``c``, and ``a_h = o_h W_UV``.

Rotary positions are YaRN-scaled on the rope lanes only
(``_decoder.yarn_inv_freq``), rotate-half over the lanes as the
projection gives them; the softmax scale carries ``mscale^2``.

The expert layer: ``y = Shared(a) + routed_scaling_factor x sum_{e in
S} p_e Expert_e(a)``, ``Shared`` one SwiGLU of width ``n_shared_experts x
moe_intermediate_size``, ``p = softmax(a W_g)`` over all routed experts,
``S`` by ``group_limited_greedy``
(:func:`~paddle2_tpu.incubate.moe.softmax_group_limited_route`).
``held_group`` g makes this model ONE chip's share of a deployment that
spreads the routed experts over ``n_group`` chips by routing group: the
layer holds group g's experts, routes over all, and adds its own
experts' part (and the shared experts in full); nothing stands in for
the other chips. Inference only, as ``models/lfm2.py``."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..incubate.moe import DroplessExperts
from ..ops.linalg import _mxu_precision
from ._decoder import (SwiGLU, created_in, linear, mm, pre_norm, rms_head,
                       rope_tables, rotate_half_rope, rotate_half_rope_mxu,
                       yarn_inv_freq, yarn_mscale)

__all__ = ["DeepseekV2Config", "DeepseekV2ForCausalLM", "LatentAttention",
           "deepseek_v2_tiny"]


@dataclass
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    n_shared_experts: int = 2
    n_routed_experts: int = 160
    routed_scaling_factor: float = 16.0
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    topk_method: str = "group_limited_greedy"
    n_group: int = 8
    topk_group: int = 3
    num_experts_per_tok: int = 6
    moe_layer_freq: int = 1
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = False
    scoring_func: str = "softmax"
    seq_aux: bool = True                 # a training loss: not served
    hidden_act: str = "silu"
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = field(default_factory=lambda: {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"})
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    # the dtype parameters are CREATED in (None: the framework default)
    dtype: Optional[str] = None
    # the routing group whose experts are held here (None: all of them)
    held_group: Optional[int] = None

    def __post_init__(self):
        if self.topk_method != "group_limited_greedy" \
                or self.scoring_func != "softmax" or self.moe_layer_freq != 1:
            raise ValueError("only group_limited_greedy routing over a "
                             "softmax, an expert layer every layer, is "
                             "implemented for this family")
        if self.attention_bias or self.tie_word_embeddings \
                or self.hidden_act != "silu" or not self.q_lora_rank:
            raise ValueError("attention_bias, a tied head, activations "
                             "other than silu and full-rank queries are "
                             "not implemented for this family")
        if self.rope_scaling and self.rope_scaling.get("type") != "yarn":
            raise ValueError("only YaRN rotary scaling is implemented")
        if self.n_routed_experts % self.n_group:
            raise ValueError(f"{self.n_routed_experts} experts do not "
                             f"divide into {self.n_group} groups")
        if self.held_group is not None \
                and not 0 <= self.held_group < self.n_group:
            raise ValueError(f"held_group {self.held_group} is not one of "
                             f"the {self.n_group} routing groups")

    @property
    def held_experts(self):
        """(first, count) of the experts held here, or None for all."""
        if self.held_group is None:
            return None
        n = self.n_routed_experts // self.n_group
        return self.held_group * n, n


class LatentAttention(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        H, std, dt = cfg.hidden_size, cfg.initializer_range, cfg.dtype
        self.nh = cfg.num_attention_heads
        self.dn, self.dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self.dv, self.rank = cfg.v_head_dim, cfg.kv_lora_rank
        self.eps, self.theta = cfg.rms_norm_eps, float(cfg.rope_theta)
        self.q_a_proj = linear(H, cfg.q_lora_rank, std, dt)
        self.q_a_layernorm = nn.RMSNorm(cfg.q_lora_rank, epsilon=self.eps)
        self.q_b_proj = linear(cfg.q_lora_rank,
                               self.nh * (self.dn + self.dr), std, dt)
        self.kv_a_proj_with_mqa = linear(H, self.rank + self.dr, std, dt)
        self.kv_a_layernorm = nn.RMSNorm(self.rank, epsilon=self.eps)
        self.kv_b_proj = linear(self.rank, self.nh * (self.dn + self.dv),
                                std, dt)
        self.o_proj = linear(self.nh * self.dv, H, std, dt)
        self.scale = (self.dn + self.dr) ** -0.5
        self.inv_freq = None
        ys = cfg.rope_scaling
        if ys:
            self.inv_freq = yarn_inv_freq(
                self.dr, self.theta, ys["factor"],
                ys["original_max_position_embeddings"], ys["beta_fast"],
                ys["beta_slow"])
            # cos and sin carry mscale(f, mscale) / mscale(f,
            # mscale_all_dim); the published pair is equal: 1
            if ys["mscale"] != ys["mscale_all_dim"]:
                raise ValueError("mscale != mscale_all_dim is not "
                                 "implemented")
            self.scale *= yarn_mscale(ys["factor"], ys["mscale_all_dim"]) ** 2

    def _tables(self, positions):
        """cos, sin ``[T, dr]`` f32 at ``positions [T]``."""
        return rope_tables(positions, self.dr, self.theta, self.inv_freq)

    def _rope(self, x, positions):
        """x ``[T, ..., dr]`` rotated at ``positions [T]``."""
        cos, sin = self._tables(positions)
        lead = (slice(None),) + (None,) * (x.ndim - 2)
        return rotate_half_rope(x, cos[lead], sin[lead])

    def _c_q(self, u):
        """u ``[..., H]`` -> the queries' normed low-rank vector ``[...,
        q_lora_rank]``."""
        return rms_head(mm(u, self.q_a_proj),
                        self.q_a_layernorm.weight._data, self.eps)

    def queries(self, u, positions):
        """u ``[T, H]`` -> (q_nope ``[T, nh, dn]``, RoPE(q_rope) ``[T,
        nh, dr]``), token-major: the decode step's rows."""
        with jax.named_scope("q_lora"):
            q = mm(self._c_q(u), self.q_b_proj).reshape(
                -1, self.nh, self.dn + self.dr)
            return q[..., :self.dn], self._rope(q[..., self.dn:], positions)

    def latent(self, u, positions):
        """u ``[T, H]`` -> (c ``[T, rank]`` normed, RoPE(k_rope) ``[T,
        dr]``): what the cache keeps of a token."""
        with jax.named_scope("kv_latent"):
            kv = mm(u, self.kv_a_proj_with_mqa)
            c = rms_head(kv[:, :self.rank],
                         self.kv_a_layernorm.weight._data, self.eps)
            return c, self._rope(kv[:, self.rank:], positions)

    def _w_qb(self):
        return self.q_b_proj.weight._data.reshape(
            -1, self.nh, self.dn + self.dr)

    def _w_kvb(self):
        return self.kv_b_proj.weight._data.reshape(
            self.rank, self.nh, self.dn + self.dv)

    def full(self, u):
        """Causal attention over a whole sequence, EXPANDED: u ``[B, S,
        H]`` -> (Op, c ``[B, S, rank]``, RoPE(k_rope) ``[B, S, dr]``).
        HEAD-MAJOR from the projections to ``W_o``: q, k and v are
        written ``[B, nh, S, d]``, the layout the flash kernel reads, by
        the matmuls that compute them (q whole from ``W_qb``; k from
        ``W_UK`` with ``dr`` zero lanes behind it), the rope turns q's
        ``dr`` rope lanes and the one shared ``RoPE(k_rope)`` fills k's
        where they lie, and the kernel's output meets ``W_o`` viewed
        ``[nh, dv, H]`` as the kernel wrote it: no activation is
        transposed, and none of ``nh x (dn + dr)`` lanes a token is
        sliced or joined."""
        from ..kernels.attention import attention_bhsd
        B, S, H = u.shape
        dn, pos = self.dn, jnp.arange(S)
        c, k_rope = self.latent(u.reshape(B * S, H), jnp.tile(pos, B))
        c, k_rope = c.reshape(B, S, -1), k_rope.reshape(B, S, -1)

        def heads(x, w):
            return jnp.einsum("bsr,rhd->bhsd", x, w,
                              precision=_mxu_precision(x, w))

        def rope_lanes(x, lanes):
            return jax.lax.dynamic_update_slice_in_dim(x, lanes, dn, -1)

        with jax.named_scope("q_lora"):
            q = heads(self._c_q(u), self._w_qb())
        with jax.named_scope("rope"):
            q = rope_lanes(q, rotate_half_rope_mxu(q[..., dn:],
                                                   *self._tables(pos)))
        with jax.named_scope("expand"):
            w = self._w_kvb()
            k = heads(c, jnp.pad(w[..., :dn], ((0, 0), (0, 0), (0, self.dr))))
            k = rope_lanes(k, jnp.broadcast_to(
                k_rope[:, None], (B, self.nh, S, self.dr)))
            v = heads(c, w[..., dn:])
        a = attention_bhsd(q, k, v, causal=True, scale=self.scale)
        with jax.named_scope("out"):
            w = self.o_proj.weight._data
            a = a.astype(w.dtype)
            op = jnp.einsum("bhsv,hvo->bso", a,
                            w.reshape(self.nh, self.dv, -1),
                            precision=_mxu_precision(a, w))
        return op, c, k_rope

    def absorb(self, q_nope):
        """``q_nope W_UK^T``: ``[T, nh, dn] -> [T, nh, rank]``, the query
        that scores against the cached latents themselves."""
        with jax.named_scope("absorb"):
            w = self._w_kvb()[..., :self.dn]
            return jnp.einsum("thd,rhd->thr", q_nope, w,
                              precision=_mxu_precision(q_nope, w))

    def unabsorb(self, o):
        """``o W_UV``: the softmax-weighted latents ``[T, nh, rank] ->
        [T, nh * dv]``."""
        with jax.named_scope("absorb"):
            w = self._w_kvb()[..., self.dn:]
            a = jnp.einsum("thr,rhv->thv", o, w,
                           precision=_mxu_precision(o, w))
            return a.reshape(o.shape[0], -1)

    def project(self, a):
        """The heads' outputs ``[..., nh * dv]``, token-major, through
        ``W_o``."""
        with jax.named_scope("out"):
            return mm(a.astype(self.o_proj.weight._data.dtype), self.o_proj)


class DeepseekV2MoE(nn.Layer):
    """Shared experts (one SwiGLU) beside the routed ones."""

    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        std, dt = cfg.initializer_range, cfg.dtype
        self.shared_experts = SwiGLU(
            cfg.hidden_size, cfg.n_shared_experts * cfg.moe_intermediate_size,
            std, dt)
        self.experts = DroplessExperts(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts,
            cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor, held=cfg.held_experts, std=std,
            dtype=dt, router="softmax_group_limited", n_group=cfg.n_group,
            topk_group=cfg.topk_group)

    def run(self, a, valid=None, interpret=None):
        """a ``[T, H]`` -> (the layer's output, its routing record)."""
        with jax.named_scope("shared"):
            shared = self.shared_experts.run(a)
        routed, record = self.experts.route_and_run(a, valid, interpret)
        return shared + routed, record


class DeepseekV2DecoderLayer(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config, index: int):
        super().__init__()
        self.cfg = cfg
        self.is_dense = index < cfg.first_k_dense_replace
        eps = cfg.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, epsilon=eps)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=eps)
        self.self_attn = LatentAttention(cfg)
        self.mlp = SwiGLU(cfg.hidden_size, cfg.intermediate_size,
                          cfg.initializer_range, cfg.dtype) \
            if self.is_dense else DeepseekV2MoE(cfg)

    def attn_norm(self, x):
        return pre_norm(self.input_layernorm, x, self.cfg.rms_norm_eps)

    def feed(self, h, valid=None, interpret=None):
        """``h + FF(RMS(h))`` on ``[..., H]`` -> (y, the layer's routing
        record or None)."""
        with jax.named_scope("mlp" if self.is_dense else "moe"):
            a = pre_norm(self.post_attention_layernorm, h,
                         self.cfg.rms_norm_eps)
            if self.is_dense:
                return h + self.mlp.run(a), None
            out, record = self.mlp.run(
                a.reshape(-1, a.shape[-1]),
                None if valid is None else valid.reshape(-1), interpret)
            return h + out.reshape(h.shape), record


class DeepseekV2Model(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=nn.initializer.Normal(
                0.0, cfg.initializer_range)))
        created_in(self.embed_tokens.weight, cfg.dtype)
        self.layers = nn.LayerList([DeepseekV2DecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def embed(self, ids):
        with jax.named_scope("embed"):
            return self.embed_tokens.weight._data[ids]

    def full(self, ids, valid=None, interpret=None):
        """A whole causal pass over ``ids [B, S]`` -> (hidden ``[B, S,
        H]`` before the final norm, per layer the latents (c, RoPE(k_rope)),
        per expert layer the routing record)."""
        x = self.embed(ids)
        latents, records = [], []
        for layer in self.layers:
            with jax.named_scope("attn"):
                op, c, k_rope = layer.self_attn.full(layer.attn_norm(x))
                latents.append((c, k_rope))
                x = x + op
            x, record = layer.feed(x, valid, interpret)
            if record is not None:
                records.append(record)
        return x, latents, records


class DeepseekV2ForCausalLM(nn.Layer):
    """Trunk + the untied head."""

    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.cfg = cfg
        self.model = DeepseekV2Model(cfg)
        self.lm_head = linear(cfg.hidden_size, cfg.vocab_size,
                              cfg.initializer_range, cfg.dtype)

    def head(self, x):
        """Final norm and the head on ``[..., H]`` -> f32 logits."""
        x = pre_norm(self.model.norm, x, self.cfg.rms_norm_eps)
        with jax.named_scope("head_ce"):
            w = self.lm_head.weight._data
            return jnp.dot(x, w, precision=_mxu_precision(x, w),
                           preferred_element_type=jnp.float32)

    def forward(self, input_ids):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        hidden, _, _ = self.model.full(ids.astype(jnp.int32))
        return Tensor(self.head(hidden))

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


def deepseek_v2_tiny(**overrides) -> DeepseekV2Config:
    """Test size, every ratio kept: a dense layer and two expert layers,
    hidden 64, 4 heads of 16 + 8 query/key lanes and 16 value lanes over
    a latent of 32 (+ 8 rope lanes) and a query rank of 48, 8 experts in
    4 groups, 2 groups and 2 experts a token, 2 shared."""
    kw = dict(vocab_size=503, hidden_size=64, intermediate_size=160,
              moe_intermediate_size=48, num_hidden_layers=3,
              num_attention_heads=4, num_key_value_heads=4,
              n_shared_experts=2, n_routed_experts=8, kv_lora_rank=32,
              q_lora_rank=48, qk_rope_head_dim=8, v_head_dim=16,
              qk_nope_head_dim=16, n_group=4, topk_group=2,
              num_experts_per_tok=2, max_position_embeddings=256,
              rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 4,
                            "mscale": 0.707, "mscale_all_dim": 0.707,
                            "original_max_position_embeddings": 64,
                            "type": "yarn"})
    kw.update(overrides)
    return DeepseekV2Config(**kw)
