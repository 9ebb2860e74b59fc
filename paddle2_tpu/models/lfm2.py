"""LFM2-MoE decoder family (``LiquidAI/LFM2-24B-A2B``, model type
``lfm2_moe``): RMSNorm pre-norms, no learned positions, and per layer

    h = x + Op(RMS(x)),   y = h + FF(RMS(h))

where ``Op`` is a gated short convolution (``layer_types[i] == "conv"``:
``[B, C, X] = split3(u W_in)``, ``z = B * X``, a depthwise causal
convolution of ``conv_L_cache`` taps over ``z``, ``Op = (C * conv) W_out``)
or grouped-query attention (per-head RMS norm of q and k, rotary
positions in the rotate-half form, ``num_key_value_heads`` key/value
heads), and ``FF`` is a dense SwiGLU in the first ``num_dense_layers``
layers and sigmoid-routed dropless experts
(:class:`~paddle2_tpu.incubate.moe.DroplessExperts`) after them. The
head is tied to the token table behind a final RMSNorm.

The config class takes the published ``config.json`` keys by their own
names. The layers' mathematics is written once, on arrays, in the
``full`` (a whole causal sequence) and ``step`` (one new token against
kept state) methods: ``forward`` runs ``full``, and the serving family
(``serving/lfm2_family.py``) runs ``full`` for prefill and ``step`` for
decode over the paged cache and the state slots. Inference only: the
array-level methods record no autograd tape (the training path of this
family is ROADMAP work).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..incubate.moe import DroplessExperts
from ..kernels.pallas_fused import fused_rms_norm
from ..ops.linalg import _mxu_precision
from ._decoder import (GroupedQueryAttention, SwiGLU,
                       created_in as _created_in, linear, mm as _mm,
                       pre_norm)

__all__ = ["Lfm2MoeConfig", "Lfm2MoeForCausalLM", "lfm2_moe_tiny"]


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    layer_types: Optional[List[str]] = None
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None
    num_experts: int = 64
    num_experts_per_tok: int = 4
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_parameters: dict = field(
        default_factory=lambda: {"rope_theta": 1000000,
                                 "rope_type": "default"})
    max_position_embeddings: int = 128000
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    # the dtype parameters are CREATED in (None: the framework default);
    # 2.7 B parameters drawn in float32 and cast afterwards would not
    # fit beside the weights they are about to be replaced with
    dtype: Optional[str] = None
    # (first, count): the contiguous share of the experts held here
    held_experts: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = [
                "full_attention" if i % 4 == 2 else "conv"
                for i in range(self.num_hidden_layers)]
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        bad = set(self.layer_types) - {"conv", "full_attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.conv_bias or not self.tie_word_embeddings:
            raise ValueError("conv_bias and an untied head are not "
                             "implemented for this family")
        if self.rope_parameters.get("rope_type", "default") != "default":
            raise ValueError("only the default rotary form is implemented")
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads

    @property
    def rope_theta(self) -> float:
        return float(self.rope_parameters["rope_theta"])


# --------------------------------------------------------------- the maths
class Lfm2ShortConv(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        H, std = cfg.hidden_size, cfg.initializer_range
        self.taps = cfg.conv_L_cache
        self.in_proj = _linear(H, 3 * H, cfg)
        self.conv_weight = self.create_parameter(
            [self.taps, H], dtype=cfg.dtype,
            default_initializer=nn.initializer.Normal(0.0, std))
        self.out_proj = _linear(H, H, cfg)

    def full(self, u):
        """u ``[B, S, H]`` -> (Op, z ``[B, S, H]``)."""
        b, c, x = jnp.split(_mm(u, self.in_proj), 3, axis=-1)
        z = b * x
        S = z.shape[1]
        zp = jnp.pad(z, ((0, 0), (self.taps - 1, 0), (0, 0)))
        w = self.conv_weight._data
        conv = sum(w[j] * zp[:, j:j + S] for j in range(self.taps))
        return _mm(c * conv, self.out_proj), z

    def step(self, u, state):
        """u ``[B, H]``, state ``[B, taps - 1, H]`` (the last z's,
        oldest first) -> (Op ``[B, H]``, the state shifted by this z)."""
        b, c, x = jnp.split(_mm(u, self.in_proj), 3, axis=-1)
        z = b * x
        window = jnp.concatenate([state, z[:, None]], axis=1)
        conv = jnp.sum(window * self.conv_weight._data[None], axis=1)
        return _mm(c * conv, self.out_proj), window[:, 1:]


def _linear(d_in, d_out, cfg):
    return linear(d_in, d_out, cfg.initializer_range, cfg.dtype)


class Lfm2DecoderLayer(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig, index: int):
        super().__init__()
        self.cfg = cfg
        self.is_conv = cfg.layer_types[index] == "conv"
        self.is_dense = index < cfg.num_dense_layers
        self.operator_norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)
        self.ffn_norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)
        if self.is_conv:
            self.conv = Lfm2ShortConv(cfg)
        else:
            self.self_attn = GroupedQueryAttention(
                cfg.hidden_size, cfg.num_attention_heads,
                cfg.num_key_value_heads, cfg.head_dim, cfg.norm_eps,
                cfg.rope_theta, cfg.initializer_range, cfg.dtype)
        if self.is_dense:
            self.feed_forward = SwiGLU(cfg.hidden_size, cfg.intermediate_size,
                                       cfg.initializer_range, cfg.dtype)
        else:
            self.feed_forward = DroplessExperts(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, cfg.use_expert_bias,
                cfg.norm_topk_prob, cfg.routed_scaling_factor,
                held=cfg.held_experts, std=cfg.initializer_range,
                dtype=cfg.dtype)

    @property
    def op_scope(self) -> str:
        return "conv" if self.is_conv else "attn"

    def pre_norm(self, norm, x):
        return pre_norm(norm, x, self.cfg.norm_eps)

    def feed(self, h, valid=None, interpret=None):
        """``h + FF(RMS(h))`` on ``[..., H]`` -> (y, routing counts or
        None)."""
        with jax.named_scope("mlp" if self.is_dense else "moe"):
            a = self.pre_norm(self.ffn_norm, h)
            if self.is_dense:
                return h + self.feed_forward.run(a), None
            flat = a.reshape(-1, a.shape[-1])
            out, counts = self.feed_forward.route_and_run(
                flat, None if valid is None else valid.reshape(-1),
                interpret)
            return h + out.reshape(h.shape), counts


class Lfm2MoeModel(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=nn.initializer.Normal(
                0.0, cfg.initializer_range)))
        _created_in(self.embed_tokens.weight, cfg.dtype)
        self.layers = nn.LayerList([Lfm2DecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)

    def embed(self, ids):
        with jax.named_scope("embed"):
            return self.embed_tokens.weight._data[ids]

    def head(self, x):
        """Final norm and the tied head on ``[..., H]`` -> f32 logits."""
        with jax.named_scope("norm"):
            x = fused_rms_norm(x, self.norm.weight._data,
                               self.cfg.norm_eps)
        with jax.named_scope("head_ce"):
            w = self.embed_tokens.weight._data
            return jnp.dot(x, w.T, precision=_mxu_precision(x, w),
                           preferred_element_type=jnp.float32)

    def full(self, ids, valid=None, interpret=None):
        """A whole causal pass over ``ids [B, S]`` -> (hidden ``[B, S,
        H]`` before the final norm, per attention layer (k, v), per
        conv layer z ``[B, S, H]``, per expert layer counts)."""
        x = self.embed(ids)
        kvs, zs, counts = [], [], []
        for layer in self.layers:
            with jax.named_scope(layer.op_scope):
                u = layer.pre_norm(layer.operator_norm, x)
                if layer.is_conv:
                    op, z = layer.conv.full(u)
                    zs.append(z)
                else:
                    op, k, v = layer.self_attn.full(u)
                    kvs.append((k, v))
                x = x + op
            x, c = layer.feed(x, valid, interpret)
            if c is not None:
                counts.append(c)
        return x, kvs, zs, counts


class Lfm2MoeForCausalLM(nn.Layer):
    """Trunk + the tied head."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = Lfm2MoeModel(cfg)

    def forward(self, input_ids):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        hidden, _, _, _ = self.model.full(ids.astype(jnp.int32))
        return Tensor(self.model.head(hidden))

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


def lfm2_moe_tiny(**overrides) -> Lfm2MoeConfig:
    """Test size: the five layer kinds of the benchmark's cut (dense
    conv, attention, three conv; experts after the first), hidden 64."""
    kw = dict(vocab_size=503, hidden_size=64, intermediate_size=160,
              moe_intermediate_size=48, num_hidden_layers=5,
              num_dense_layers=1,
              layer_types=["conv", "full_attention", "conv", "conv", "conv"],
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              num_experts=8, num_experts_per_tok=2,
              max_position_embeddings=256)
    kw.update(overrides)
    return Lfm2MoeConfig(**kw)
