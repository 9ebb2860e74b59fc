"""Nemotron-H decoder family (``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-
BF16``, model type ``nemotron_h``): ONE mixer a layer. The published
schedule is a string (``hybrid_override_pattern``), a character a layer,
and every layer is ``x + Mixer(RMS(x))`` and nothing else — there is no
attention + feed-forward pair anywhere::

    x_0 = Embed(ids)                              (no multiplier, no positions)
    for c in hybrid_override_pattern:  x = x + Mixer_c(RMS(x))
    logits = Head(RMS_f(x))                       (untied head, float32)

    M  the Mamba-2 state-space mixer (``models/_decoder.Mamba2Mixer``, no µP
       multipliers): ``mamba_num_heads`` heads of ``mamba_head_dim``,
       ``n_groups`` groups of ``ssm_state_size`` state lanes, ``conv_kernel``
       taps with a bias, gated RMS norm per group
    E  s = sigmoid(u W_g) in float32;  S = top-k of (s + e_score_correction_
       bias)  (the bias selects only);  p_e = s_e / (sum_S s + 1e-20) x
       routed_scaling_factor;
       Mixer = sum_{e in S} p_e W_down,e relu(W_up,e u)^2
               + W_down,sh relu(W_up,sh u)^2
       (``incubate.moe.DroplessExperts(gated=False, activation="relu2")``:
       two matrices an expert; the shared expert ``_decoder.Relu2MLP``)
    *  grouped-query attention WITHOUT a positional embedding (positions
       come from the state-space layers; ``rope_theta`` and
       ``partial_rotary_factor`` are keys the published code does not
       read), no q/k norm, no bias
       (``_decoder.GroupedQueryAttention(rotary=False, qk_norm=False)``)

So a layer keeps recurrent + convolution state (``M``), or keys and
values (``*``), or nothing per sequence (``E``).

``held_experts = (first, count)`` makes this model ONE chip's share of a
deployment that spreads the routed experts over chips by contiguous
ranges (``n_group`` is 1: there are no routing groups to go by): the
layer holds those experts, routes over all ``n_routed_experts``, and
adds its own experts' part and the shared expert in full; nothing
stands in for the other chips.

The config class takes the published ``config.json`` keys by their own
names and REFUSES what is not implemented. Inference only; the serving
family is ``serving/nemotron_h_family.py``."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..incubate.moe import DroplessExperts
from ..ops.linalg import _mxu_precision
from ._decoder import (GroupedQueryAttention, Mamba2Mixer, NormalDraw,
                       Relu2MLP, created_in, linear, pre_norm)

__all__ = ["NemotronHConfig", "NemotronHForCausalLM", "nemotron_h_tiny"]

# a layer's kind by its character of the pattern
KINDS = {"M": "ssm", "E": "moe", "*": "attn"}


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = \
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    sliding_window: Optional[int] = None
    rope_theta: float = 10000.0          # not read: no rotary embedding
    partial_rotary_factor: float = 1.0   # not read
    max_position_embeddings: int = 262144
    # the state-space mixer
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2                      # not read: d_inner = heads x dim
    mamba_hidden_act: str = "silu"
    mamba_proj_bias: bool = False
    use_conv_bias: bool = True
    use_bias: bool = False
    time_step_min: float = 0.001         # initialisation only
    time_step_max: float = 0.1           # initialisation only
    time_step_floor: float = 0.0001      # initialisation only
    use_mamba_kernels: bool = True       # an execution choice of theirs
    # experts
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    intermediate_size: int = 1856        # a dense MLP's: no such layer
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    moe_latent_size: Optional[int] = None
    num_nextn_predict_layers: int = 0
    # norms, head
    layer_norm_epsilon: float = 1e-5
    norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    residual_in_fp32: bool = False
    rescale_prenorm_residual: bool = True   # initialisation only
    num_logits_to_keep: int = 1
    initializer_range: float = 0.02
    # the dtype parameters are CREATED in (None: the framework default)
    dtype: Optional[str] = None
    # (first, count) of the routed experts held here (None: all of them)
    held_experts: Optional[Tuple[int, int]] = None
    # the experts' width is stored in whole multiples of this many lanes
    expert_width_align: int = 128

    def __post_init__(self):
        refused = [k for k, bad in (
            ("attention_bias", self.attention_bias),
            ("mlp_bias", self.mlp_bias), ("use_bias", self.use_bias),
            ("mamba_proj_bias", self.mamba_proj_bias),
            ("use_conv_bias=False", not self.use_conv_bias),
            ("tie_word_embeddings", self.tie_word_embeddings),
            ("residual_in_fp32", self.residual_in_fp32),
            ("sliding_window", self.sliding_window is not None),
            ("mamba_hidden_act", self.mamba_hidden_act != "silu"),
            ("mlp_hidden_act", self.mlp_hidden_act != "relu2"),
            ("moe_latent_size", self.moe_latent_size is not None),
            ("num_nextn_predict_layers", self.num_nextn_predict_layers != 0),
            ("n_group", self.n_group != 1),
            ("topk_group", self.topk_group != 1),
            ("n_shared_experts", self.n_shared_experts != 1),
            ("layer_norm_epsilon != norm_eps",
             self.layer_norm_epsilon != self.norm_eps)) if bad]
        if refused:
            raise ValueError(f"not implemented for this family: {refused}")
        pattern = self.hybrid_override_pattern
        unknown = sorted(set(pattern) - set(KINDS))
        if unknown:
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: {unknown} are not "
                f"layer kinds this family has ('-', a dense MLP layer, is "
                f"not implemented)")
        if len(pattern) != self.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern has {len(pattern)} characters, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("n_groups must divide mamba_num_heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if self.held_experts is not None:
            first, count = self.held_experts = tuple(self.held_experts)
            if not 0 <= first < first + count <= self.n_routed_experts:
                raise ValueError(
                    f"held_experts {self.held_experts} is not a range of "
                    f"the {self.n_routed_experts} routed experts")

    @property
    def layer_kinds(self) -> tuple:
        """``ssm`` / ``moe`` / ``attn`` per layer, as the pattern says."""
        return tuple(KINDS[c] for c in self.hybrid_override_pattern)

    @property
    def conv_dim(self) -> int:
        """Lanes of ``[x | B | C]``, what the convolution runs over."""
        return self.mamba_num_heads * self.mamba_head_dim \
            + 2 * self.n_groups * self.ssm_state_size


class NemotronHMoE(nn.Layer):
    """The shared expert beside the routed ones."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        std, dt = cfg.initializer_range, cfg.dtype
        self.shared_experts = Relu2MLP(
            cfg.hidden_size, cfg.moe_shared_expert_intermediate_size, std, dt)
        self.experts = DroplessExperts(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts,
            cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor, held=cfg.held_experts, std=std,
            dtype=dt, router="sigmoid", gated=False, activation="relu2",
            norm_eps=1e-20, width_align=cfg.expert_width_align)

    def run(self, a, valid=None, interpret=None):
        """a ``[T, H]`` -> (the layer's output, its routing record)."""
        with jax.named_scope("shared"):
            shared = self.shared_experts.run(a)
        routed, record = self.experts.route_and_run(a, valid, interpret)
        return shared + routed, record


class NemotronHBlock(nn.Layer):
    """``x + Mixer(RMS(x))`` with the ONE mixer of ``kind``."""

    def __init__(self, cfg: NemotronHConfig, kind: str):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        H, std, eps = cfg.hidden_size, cfg.initializer_range, cfg.norm_eps
        self.norm = nn.RMSNorm(H, epsilon=eps)
        if kind == "ssm":
            self.mixer = Mamba2Mixer(
                H, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                cfg.ssm_state_size, cfg.conv_kernel, cfg.chunk_size, eps,
                std, cfg.dtype)
        elif kind == "attn":
            self.mixer = GroupedQueryAttention(
                H, cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim, eps, cfg.rope_theta, std, cfg.dtype,
                qk_norm=False, rotary=False)
        else:
            self.mixer = NemotronHMoE(cfg)

    def mixer_input(self, x):
        return pre_norm(self.norm, x, self.cfg.norm_eps)

    def feed(self, x, valid=None, interpret=None):
        """An expert layer on ``[..., H]`` -> (y, its routing record)."""
        with jax.named_scope("moe"):
            u = self.mixer_input(x)
            out, record = self.mixer.run(
                u.reshape(-1, u.shape[-1]),
                None if valid is None else valid.reshape(-1), interpret)
            return x + out.reshape(x.shape), record


class NemotronHModel(nn.Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=NormalDraw(
                0.0, cfg.initializer_range)))
        created_in(self.embed_tokens.weight, cfg.dtype)
        # before the layers, as in models/falcon_h1.py: the head's
        # float32 draft must not stand beside every layer's weights
        self.lm_head = linear(cfg.hidden_size, cfg.vocab_size,
                              cfg.initializer_range, cfg.dtype, NormalDraw)
        self.layers = nn.LayerList([NemotronHBlock(cfg, kind)
                                    for kind in cfg.layer_kinds])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)

    def embed(self, ids):
        with jax.named_scope("embed"):
            return self.embed_tokens.weight._data[ids]

    def head(self, x):
        """Final norm and the untied head on ``[..., H]`` -> f32 logits."""
        x = pre_norm(self.norm_f, x, self.cfg.norm_eps)
        with jax.named_scope("head_ce"):
            w = self.lm_head.weight._data
            return jnp.dot(x, w, precision=_mxu_precision(x, w),
                           preferred_element_type=jnp.float32)

    def full(self, ids, valid=None, interpret=None):
        """A whole causal pass over ``ids [B, S]`` -> (hidden ``[B, S,
        H]`` before the final norm; per ``*`` layer (k, v); per ``M``
        layer (xBC ``[B, S, conv_dim]``, the recurrent state ``[B, heads,
        d_head, d_state]`` after the last valid position); per ``E``
        layer its routing record)."""
        x = self.embed(ids)
        kvs, states, records = [], [], []
        for layer in self.layers:
            if layer.kind == "moe":
                x, record = layer.feed(x, valid, interpret)
                records.append(record)
                continue
            with jax.named_scope(layer.kind):
                u = layer.mixer_input(x)
                if layer.kind == "ssm":
                    out, xbc, H = layer.mixer.full(u, valid)
                    states.append((xbc, H))
                else:
                    out, k, v = layer.mixer.full(u)
                    kvs.append((k, v))
                x = x + out
        return x, kvs, states, records


class NemotronHForCausalLM(nn.Layer):
    """Trunk + the untied head."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        self.model = NemotronHModel(cfg)

    def forward(self, input_ids):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        hidden = self.model.full(ids.astype(jnp.int32))[0]
        return Tensor(self.model.head(hidden))

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


def nemotron_h_tiny(**overrides) -> NemotronHConfig:
    """Test size: hidden 64; the pattern ``MEM*EME`` (3 state-space, 3
    expert, 1 attention layer); 4 mixer heads of 16 in 2 groups, state
    16, chunk 8, 4 taps; 8 experts top-2 of width 40, stored as 48 (a
    width its alignment of 16 does not divide), a shared expert of 80; 6
    query over 2 key/value heads of 16."""
    kw = dict(vocab_size=503, hidden_size=64, num_hidden_layers=7,
              hybrid_override_pattern="MEM*EME", num_attention_heads=6,
              num_key_value_heads=2, head_dim=16,
              max_position_embeddings=256, mamba_num_heads=4,
              mamba_head_dim=16, n_groups=2, ssm_state_size=16,
              conv_kernel=4, chunk_size=8, n_routed_experts=8,
              num_experts_per_tok=2, moe_intermediate_size=40,
              moe_shared_expert_intermediate_size=80, intermediate_size=40,
              expert_width_align=16)
    kw.update(overrides)
    return NemotronHConfig(**kw)
