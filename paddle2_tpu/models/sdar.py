"""SDAR-MoE decoder family (``JetLM/SDAR-30B-A3B-Chat``, model type
``sdar_moe``): a block-diffusion language model. Every layer is

    h = x + Attn(RMS(x)),   y = h + Experts(RMS(h))

with grouped-query attention (per-head RMS norm of q and k, rotary
positions in the rotate-half form) and softmax-routed dropless experts
(:class:`~paddle2_tpu.incubate.moe.DroplessExperts`, ``router="softmax"``)
in every layer; a final RMSNorm and an UNTIED head. Two things set it
apart from a causal decoder:

* the attention mask is block-causal: with block length ``B`` position
  ``i`` sees position ``j`` iff ``j // B <= i // B`` — every earlier
  block and ALL of its own, later positions included;
* position ``i``'s logits give the token AT position ``i`` (a masked
  position is fed the ``[MASK]`` id and predicts itself): no shift.

Generation un-masks a block of ``B`` positions over several passes and
is the serving engine's business (``serving/sdar_family.py``); this
file is the network. The config class takes the published
``config.json`` keys by their own names plus ``block_length`` and
``mask_token_id``. The mathematics is written once, on arrays
(``full``: a whole sequence under the block-causal mask); inference
only, as ``models/lfm2.py``, with which it shares ``models/_decoder``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..incubate.moe import DroplessExperts
from ..ops.linalg import _mxu_precision
from ._decoder import GroupedQueryAttention, created_in, linear, pre_norm

__all__ = ["SdarMoeConfig", "SdarMoeForCausalLM", "sdar_moe_tiny"]


@dataclass
class SdarMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144        # no dense layer: unused
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: List[int] = field(default_factory=list)
    hidden_act: str = "silu"
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 32768
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    # generation by diffusion over blocks: the mask's block length and
    # the id a not-yet-fixed position is fed
    block_length: int = 4
    mask_token_id: int = 151669
    # the dtype parameters are CREATED in (None: the framework default)
    dtype: Optional[str] = None
    # (first, count): the contiguous share of the experts held here
    held_experts: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.decoder_sparse_step != 1 or self.mlp_only_layers:
            raise ValueError("dense layers among the expert layers are not "
                             "implemented for this family")
        if self.attention_bias or self.tie_word_embeddings \
                or self.rope_scaling or self.hidden_act != "silu":
            raise ValueError("attention_bias, a tied head, rope_scaling and "
                             "activations other than silu are not "
                             "implemented for this family")
        if self.block_length < 1:
            raise ValueError("block_length must be at least 1")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} is not in "
                             f"the vocabulary of {self.vocab_size}")


class SdarDecoderLayer(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        eps = cfg.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, epsilon=eps)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=eps)
        self.self_attn = GroupedQueryAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, eps, cfg.rope_theta,
            cfg.initializer_range, cfg.dtype)
        self.mlp = DroplessExperts(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
            held=cfg.held_experts, std=cfg.initializer_range,
            dtype=cfg.dtype, router="softmax")

    def attn_norm(self, x):
        return pre_norm(self.input_layernorm, x, self.cfg.rms_norm_eps)

    def feed(self, h, valid=None, interpret=None):
        """``h + Experts(RMS(h))`` on ``[..., H]`` -> (y, the layer's
        routing record)."""
        with jax.named_scope("moe"):
            a = pre_norm(self.post_attention_layernorm, h,
                         self.cfg.rms_norm_eps)
            out, record = self.mlp.route_and_run(
                a.reshape(-1, a.shape[-1]),
                None if valid is None else valid.reshape(-1), interpret)
            return h + out.reshape(h.shape), record


class SdarMoeModel(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=nn.initializer.Normal(
                0.0, cfg.initializer_range)))
        created_in(self.embed_tokens.weight, cfg.dtype)
        self.layers = nn.LayerList([SdarDecoderLayer(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def embed(self, ids):
        with jax.named_scope("embed"):
            return self.embed_tokens.weight._data[ids]

    def full(self, ids, valid=None, interpret=None):
        """A whole pass over ``ids [B, S]`` under the block-causal mask
        -> (hidden ``[B, S, H]`` before the final norm, per layer (k,
        v), per layer the routing record)."""
        x = self.embed(ids)
        kvs, records = [], []
        for layer in self.layers:
            with jax.named_scope("attn"):
                op, k, v = layer.self_attn.full(layer.attn_norm(x),
                                                self.cfg.block_length)
                kvs.append((k, v))
                x = x + op
            x, record = layer.feed(x, valid, interpret)
            records.append(record)
        return x, kvs, records


class SdarMoeForCausalLM(nn.Layer):
    """Trunk + the untied head."""

    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = SdarMoeModel(cfg)
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


def sdar_moe_tiny(**overrides) -> SdarMoeConfig:
    """Test size: three layers, hidden 64, 4 query heads over 2, eight
    experts, two a token, blocks of 4."""
    kw = dict(vocab_size=503, hidden_size=64, intermediate_size=160,
              moe_intermediate_size=48, num_hidden_layers=3,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              num_experts=8, num_experts_per_tok=2,
              max_position_embeddings=256, block_length=4,
              mask_token_id=502)
    kw.update(overrides)
    return SdarMoeConfig(**kw)
