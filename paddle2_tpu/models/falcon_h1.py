"""Falcon-H1 decoder family (``tiiuae/Falcon-H1-34B-Instruct``, model
type ``falcon_h1``): a PARALLEL hybrid block. Every layer runs a
Mamba-2 state-space mixer and grouped-query attention on the SAME
normed input and adds both, each under its µP multiplier, in one
residual add; a SwiGLU feed-forward follows::

    x0 = Embed(ids) * embedding_multiplier
    u  = RMS_in(x)
    x  = x + ssm_out_multiplier * SSM(u)
           + attention_out_multiplier * Attn(u * attention_in_multiplier)
    x  = x + FF(RMS_ff(x))
    logits = Head(RMS_final(x)) * lm_head_multiplier        (untied head)

``Attn``: ``models/_decoder.GroupedQueryAttention`` without the q/k norm,
keys scaled by ``key_multiplier``, rotate-half rotary. ``FF``:
``models/_decoder.SwiGLU`` with the two ``mlp_multipliers``. ``SSM``:
``models/_decoder.Mamba2Mixer`` (the Mamba-2 mixer every state-space
family here uses) with ``ssm_in_multiplier`` on its input and the five
``ssm_multipliers`` on the lanes of z, x, B, C, dt of its input
projection (:class:`FalconH1Mixer`).

Every multiplier acts on activations at run time; none is folded into a
weight. The config class takes the published ``config.json`` keys by
their own names. Inference only; the serving family is
``serving/falcon_h1_family.py``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..kernels.pallas_fused import fused_rms_norm
from ..ops.linalg import _mxu_precision
from ._decoder import (GroupedQueryAttention, Mamba2Mixer, NormalDraw, SwiGLU,
                       created_in, linear, pre_norm)

__all__ = ["FalconH1Config", "FalconH1ForCausalLM", "falcon_h1_tiny"]


@dataclass
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    projectors_bias: bool = False
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    embedding_multiplier: float = 5.656854249492381
    key_multiplier: float = 0.011048543456039804
    lm_head_multiplier: float = 0.0078125
    mlp_multipliers: List[float] = field(
        default_factory=lambda: [0.1767766952966369, 0.011160714285714284])
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: List[float] = field(
        default_factory=lambda: [0.3535533905932738, 0.25,
                                 0.1767766952966369, 0.5,
                                 0.3535533905932738])
    ssm_out_multiplier: float = 0.08838834764831845
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_d_conv: int = 4
    mamba_d_head: int = 128
    mamba_d_ssm: Optional[int] = 4096
    mamba_d_state: int = 256
    mamba_expand: int = 2
    mamba_n_groups: int = 2
    mamba_n_heads: int = 32
    mamba_norm_before_gate: bool = False
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    initializer_range: float = 0.02
    # the dtype parameters are CREATED in (None: the framework default)
    dtype: Optional[str] = None

    def __post_init__(self):
        if self.mamba_d_ssm is None:
            self.mamba_d_ssm = self.mamba_expand * self.hidden_size
        refused = [k for k, bad in (
            ("attention_bias", self.attention_bias),
            ("mlp_bias", self.mlp_bias),
            ("projectors_bias", self.projectors_bias),
            ("mamba_proj_bias", self.mamba_proj_bias),
            ("mamba_norm_before_gate", self.mamba_norm_before_gate),
            ("mamba_rms_norm=False", not self.mamba_rms_norm),
            ("mamba_conv_bias=False", not self.mamba_conv_bias),
            ("tie_word_embeddings", self.tie_word_embeddings),
            ("rope_scaling", self.rope_scaling is not None),
            ("hidden_act", self.hidden_act != "silu")) if bad]
        if refused:
            raise ValueError(f"not implemented for this family: {refused}")
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"mamba_d_ssm {self.mamba_d_ssm} is not mamba_n_heads x "
                f"mamba_d_head ({self.mamba_n_heads} x {self.mamba_d_head})")
        if self.mamba_n_heads % self.mamba_n_groups \
                or self.mamba_d_ssm % self.mamba_n_groups:
            raise ValueError("mamba_n_groups must divide the mixer's heads")
        if len(self.mlp_multipliers) != 2 or len(self.ssm_multipliers) != 5:
            raise ValueError("mlp_multipliers has 2 entries (gate, down), "
                             "ssm_multipliers 5 (z, x, B, C, dt)")

    @property
    def conv_dim(self) -> int:
        """Lanes of ``[x | B | C]``, what the convolution runs over."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state


class FalconH1Mixer(Mamba2Mixer):
    """``models/_decoder.Mamba2Mixer`` at this config's sizes, under its
    µP multipliers."""

    def __init__(self, cfg: FalconH1Config):
        super().__init__(
            cfg.hidden_size, cfg.mamba_n_heads, cfg.mamba_d_head,
            cfg.mamba_n_groups, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_chunk_size, cfg.rms_norm_eps, cfg.initializer_range,
            cfg.dtype, cfg.ssm_in_multiplier, cfg.ssm_multipliers)


class FalconH1DecoderLayer(nn.Layer):
    def __init__(self, cfg: FalconH1Config):
        super().__init__()
        self.cfg = cfg
        H, std, eps = cfg.hidden_size, cfg.initializer_range, cfg.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(H, epsilon=eps)
        self.pre_ff_layernorm = nn.RMSNorm(H, epsilon=eps)
        self.mamba = FalconH1Mixer(cfg)
        self.self_attn = GroupedQueryAttention(
            H, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, eps, cfg.rope_theta, std, cfg.dtype,
            qk_norm=False, key_scale=cfg.key_multiplier)
        self.feed_forward = SwiGLU(H, cfg.intermediate_size, std, cfg.dtype,
                                   *cfg.mlp_multipliers)

    def mixer_input(self, x):
        """``RMS_in(x)``: what BOTH mixers read."""
        return pre_norm(self.input_layernorm, x, self.cfg.rms_norm_eps)

    def attn_input(self, u):
        m = self.cfg.attention_in_multiplier
        return u if m == 1 else u * jnp.asarray(m, u.dtype)

    def mix(self, x, ssm, attn):
        """``x + ssm_out_multiplier ssm + attention_out_multiplier
        attn``: ONE residual add for both mixers, summed in float32."""
        cfg = self.cfg
        f32 = jnp.float32
        return (x.astype(f32) + cfg.ssm_out_multiplier * ssm.astype(f32)
                + cfg.attention_out_multiplier * attn.astype(f32)
                ).astype(x.dtype)

    def feed(self, x):
        with jax.named_scope("mlp"):
            a = pre_norm(self.pre_ff_layernorm, x, self.cfg.rms_norm_eps)
            return x + self.feed_forward.run(a)


class FalconH1Model(nn.Layer):
    def __init__(self, cfg: FalconH1Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=NormalDraw(
                0.0, cfg.initializer_range)))
        created_in(self.embed_tokens.weight, cfg.dtype)
        # before the layers: a stock layer draws in float32 and is cast
        # afterwards, and the head's float32 draft (5.3 GB at the
        # published sizes) must not stand beside every layer's weights
        self.lm_head = linear(cfg.hidden_size, cfg.vocab_size,
                              cfg.initializer_range, cfg.dtype, NormalDraw)
        self.layers = nn.LayerList([FalconH1DecoderLayer(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.final_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_norm_eps)

    def embed(self, ids):
        with jax.named_scope("embed"):
            x = self.embed_tokens.weight._data[ids]
            return x * jnp.asarray(self.cfg.embedding_multiplier, x.dtype)

    def head(self, x):
        """Final norm and the untied head on ``[..., H]`` -> f32 logits,
        times ``lm_head_multiplier``."""
        with jax.named_scope("norm"):
            x = fused_rms_norm(x, self.final_layernorm.weight._data,
                               self.cfg.rms_norm_eps)
        with jax.named_scope("head_ce"):
            w = self.lm_head.weight._data
            return jnp.dot(x, w, precision=_mxu_precision(x, w),
                           preferred_element_type=jnp.float32) \
                * self.cfg.lm_head_multiplier

    def full(self, ids, valid=None):
        """A whole causal pass over ``ids [B, S]`` -> (hidden ``[B, S,
        H]`` before the final norm, per layer (k, v), per layer (xBC
        ``[B, S, conv_dim]``, recurrent state ``[B, heads, d_head,
        d_state]`` after the last valid position))."""
        x = self.embed(ids)
        kvs, states = [], []
        for layer in self.layers:
            u = layer.mixer_input(x)
            with jax.named_scope("ssm"):
                ssm, xbc, H = layer.mamba.full(u, valid)
            with jax.named_scope("attn"):
                attn, k, v = layer.self_attn.full(layer.attn_input(u))
            x = layer.feed(layer.mix(x, ssm, attn))
            kvs.append((k, v))
            states.append((xbc, H))
        return x, kvs, states


class FalconH1ForCausalLM(nn.Layer):
    """Trunk + the untied head."""

    def __init__(self, cfg: FalconH1Config):
        super().__init__()
        self.cfg = cfg
        self.model = FalconH1Model(cfg)

    def forward(self, input_ids):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        hidden, _, _ = self.model.full(ids.astype(jnp.int32))
        return Tensor(self.model.head(hidden))

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


def falcon_h1_tiny(**overrides) -> FalconH1Config:
    """Test size: hidden 64; 6 query over 2 key/value heads of 16 (a
    group of 3: not a power of two, as the model's 5 is not); 4 mixer
    heads of 16, state 16, 2 groups, chunk 8, 4 taps with bias; EVERY
    multiplier different from 1."""
    kw = dict(vocab_size=503, hidden_size=64, intermediate_size=160,
              num_hidden_layers=2, num_attention_heads=6,
              num_key_value_heads=2, head_dim=16, rope_theta=1e6,
              max_position_embeddings=256, attention_in_multiplier=0.8,
              attention_out_multiplier=0.6, embedding_multiplier=3.0,
              key_multiplier=0.7, lm_head_multiplier=0.5,
              mlp_multipliers=[0.9, 0.7], ssm_in_multiplier=1.25,
              ssm_multipliers=[0.9, 0.8, 0.7, 1.2, 1.1],
              ssm_out_multiplier=0.75, mamba_chunk_size=8, mamba_d_conv=4,
              mamba_d_head=16, mamba_d_ssm=64, mamba_d_state=16,
              mamba_n_groups=2, mamba_n_heads=4)
    kw.update(overrides)
    return FalconH1Config(**kw)
