"""EXAONE-MoE decoder family (``LGAI-EXAONE/K-EXAONE-236B-A23B``, model
type ``exaone_moe``): RMSNorm pre-norms, no learned positions, and per
layer

    h = x + Attn(RMS(x)),   y = h + FF(RMS(h))

``Attn`` is grouped-query attention with a per-head RMS norm of q and k,
of TWO kinds by ``layer_types[i]``::

    sliding_attention  position i sees i - (sliding_window - 1) .. i; q
                       and k are rotated (rotate-half, the whole head,
                       ``rope_theta``)
    full_attention     position i sees 0 .. i; NOTHING is rotated (the
                       global layers take their positions from the
                       sliding ones)

``FF`` is a dense SwiGLU of ``intermediate_size`` where
``mlp_layer_types[i]`` says ``dense`` and, where it says ``sparse``,
``num_experts`` SwiGLU experts of ``moe_intermediate_size``
(``incubate.moe.DroplessExperts``: ``s = sigmoid(a W_r)``, the
``num_experts_per_tok`` largest of ``s + bias``, weights
``routed_scaling_factor x s_e / sum s_chosen``) beside ONE shared SwiGLU
expert every row takes. Final RMSNorm, untied head.

So a sliding layer keeps a BOUNDED history, ``sliding_window`` keys and
values whatever the context, and its attention over a whole sequence
costs O(S x window), in one of two forms (``ExaoneMoeAttention.full``
chooses from the device and the head width, no argument does):
:func:`sliding_attention_kernel` on a TPU — ONE Pallas kernel
(``kernels/pallas_band.py``, ``window_fwd``) that reads q where its
projection wrote it, norms and rotates it in VMEM and keeps the band's
scores there — and :func:`sliding_attention` everywhere else, as the
definition the kernel is tested against and as its gradient: q normed
and rotated as plain XLA, then :func:`local_window_attention`, chunks of
a window each of which sees itself under the band and the chunk before
it. A global layer keeps every key and value.

``held_experts = (first, count)`` makes this model ONE chip's share of a
deployment that spreads the routed experts over chips by contiguous
ranges (``n_group`` is 1): the layer holds those experts, routes over
all ``num_experts``, and adds its own experts' part and the shared
expert in full; nothing stands in for the other chips.

The config class takes the published ``config.json`` keys by their own
names and REFUSES what is not implemented (the next-token-prediction
layer, ``num_nextn_predict_layers``, is a drafter beside the model:
greedy tokens are the same without it). Inference only; the serving
family is ``serving/exaone_moe_family.py``."""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..incubate.moe import DroplessExperts
from ..kernels import _platform, pallas_band
from ..ops.linalg import _mxu_precision
from ._decoder import (GroupedQueryAttention, NormalDraw, SwiGLU, created_in,
                       linear, mm, pre_norm, rms_head, rope_tables,
                       rotate_half_rope)

__all__ = ["ExaoneMoeConfig", "ExaoneMoeForCausalLM", "exaone_moe_tiny",
           "local_window_attention"]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass
class ExaoneMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    layer_types: Optional[List[str]] = None
    mlp_layer_types: Optional[List[str]] = None
    sliding_window: int = 128
    sliding_window_pattern: str = "LLLG"
    sliding_windows: Optional[List[int]] = None    # layer_types says it
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_parameters: dict = field(
        default_factory=lambda: {"rope_theta": 1000000,
                                 "rope_type": "default"})
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    # the drafter beside the model: not served (ROADMAP M12)
    num_nextn_predict_layers: int = 0
    mtp_layer_types: Optional[List[str]] = None    # the drafter's
    mtp_sliding_windows: Optional[List[int]] = None
    initializer_range: float = 0.02
    # the dtype parameters are CREATED in (None: the framework default)
    dtype: Optional[str] = None
    # (first, count) of the routed experts held here (None: all of them)
    held_experts: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            period = [SLIDING if c == "L" else FULL
                      for c in self.sliding_window_pattern]
            self.layer_types = [period[i % len(period)] for i in range(n)]
        if self.mlp_layer_types is None:
            self.mlp_layer_types = [
                "dense" if i < self.first_k_dense_replace else "sparse"
                for i in range(n)]
        for name, kinds in (("layer_types", (SLIDING, FULL)),
                            ("mlp_layer_types", ("dense", "sparse"))):
            value = getattr(self, name)
            if len(value) != n:
                raise ValueError(f"{name} names {len(value)} layers, "
                                 f"num_hidden_layers is {n}")
            if set(value) - set(kinds):
                raise ValueError(f"{name}: unknown kinds "
                                 f"{sorted(set(value) - set(kinds))}")
        refused = [k for k, bad in (
            ("num_nextn_predict_layers", self.num_nextn_predict_layers != 0),
            ("tie_word_embeddings", self.tie_word_embeddings),
            ("scoring_func", self.scoring_func != "sigmoid"),
            ("hidden_act", self.hidden_act != "silu"),
            ("n_group", self.n_group != 1),
            ("topk_group", self.topk_group != 1),
            ("num_shared_experts", self.num_shared_experts != 1),
            ("rope_type", self.rope_parameters.get(
                "rope_type", "default") != "default"),
            ("sliding_window", SLIDING in self.layer_types
             and self.sliding_window < 1)) if bad]
        if refused:
            raise ValueError(f"not implemented for this family: {refused}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if self.held_experts is not None:
            first, count = self.held_experts = tuple(self.held_experts)
            if not 0 <= first < first + count <= self.num_experts:
                raise ValueError(
                    f"held_experts {self.held_experts} is not a range of "
                    f"the {self.num_experts} routed experts")

    @property
    def rope_theta(self) -> float:
        return float(self.rope_parameters["rope_theta"])

    def window_of(self, i: int) -> Optional[int]:
        """Layer i's window; None for a global layer."""
        return self.sliding_window if self.layer_types[i] == SLIDING \
            else None


def local_window_attention(q, k, v, window: int):
    """Causal attention in which position i sees ``i - (window - 1) ..
    i``, in O(S x window): the sequence in chunks of ``window``, a chunk's
    queries against its own keys and the chunk's before it (``[S / w, w]``
    x ``[S / w, 2 w]`` scores, plain XLA), the band cut out of that
    rectangle. q ``[B, S, nh, hd]``, k, v ``[B, S, nkv, hd]`` -> ``[B, S,
    nh, hd]``; scores and softmax in float32, scaled by ``1 /
    sqrt(hd)``. The form of the CPU and of every gradient; on a TPU a
    prefill takes ``window_fwd`` instead (at 8,192 positions x 64 heads
    these scores are 537 MB of float32 a layer in HBM)."""
    B, S, nh, hd = q.shape
    nkv = k.shape[2]
    w = int(window)
    pad = -S % w
    if pad:
        # padded queries see real keys only and are cut off again; padded
        # keys lie after every real query
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    C = (S + pad) // w
    qc = q.reshape(B, C, w, nkv, nh // nkv, hd)

    def with_chunk_before(x):
        x = x.reshape(B, C, w, nkv, hd)
        before = jnp.pad(x[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
        return jnp.concatenate([before, x], axis=2)          # [B, C, 2w, ..]

    kc, vc = with_chunk_before(k), with_chunk_before(v)
    s = jnp.einsum("bcqngd,bcknd->bcngqk", qc, kc,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    # query i of a chunk is position c w + i, key j is c w + j - w
    back = (jnp.arange(w)[:, None] + w) - jnp.arange(2 * w)[None, :]
    sees = (back >= 0) & (back < w)
    # the first chunk has no chunk before it
    real = (jnp.arange(C)[:, None] > 0) | (jnp.arange(2 * w)[None, :] >= w)
    mask = sees[None] & real[:, None, :]                     # [C, w, 2w]
    s = jnp.where(mask[None, :, None, None], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    a = jnp.einsum("bcngqk,bcknd->bcqngd", p, vc)
    return a.reshape(B, C * w, nh, hd)[:, :S]


def sliding_attention(q, gain, k, v, cos, sin, window: int, eps: float):
    """A sliding layer's attention from q as its projection wrote it: q
    ``[B, S, nh x hd]`` takes its per-head RMS norm (``gain [hd]``,
    ``eps``) and its rotation (``cos``, ``sin`` ``[S, hd]``), then the
    band against k, v ``[B, S, nkv, hd]`` as they are kept -> ``[B, S, nh
    x hd]``. Plain XLA: the definition, the form off the chip, and the
    gradient of :func:`sliding_attention_kernel`."""
    B, S, _ = q.shape
    q = rms_head(q.reshape(B, S, -1, k.shape[-1]), gain, eps)
    q = rotate_half_rope(q, cos[:, None], sin[:, None])
    return local_window_attention(q, k, v, window).reshape(B, S, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def sliding_attention_kernel(q, gain, k, v, cos, sin, window, eps):
    """:func:`sliding_attention` as ONE Pallas kernel (``window_fwd``,
    ``kernels/pallas_band.py``): q is read once where its projection
    wrote it, normed and rotated in VMEM, and no score reaches HBM."""
    B, S = k.shape[:2]
    return pallas_band.band_attention(
        q, k.reshape(B, S, -1), v.reshape(B, S, -1), window, k.shape[-1],
        q_gain=gain, eps=eps, rope=(cos, pallas_band.signed_sin(sin)))


def _sliding_fwd(q, gain, k, v, cos, sin, window, eps):
    return (sliding_attention_kernel(q, gain, k, v, cos, sin, window, eps),
            (q, gain, k, v, cos, sin))


def _sliding_bwd(window, eps, kept, g):
    return jax.vjp(functools.partial(sliding_attention, window=window,
                                     eps=eps), *kept)[1](g)


sliding_attention_kernel.defvjp(_sliding_fwd, _sliding_bwd)


class ExaoneMoeAttention(GroupedQueryAttention):
    """``_decoder.GroupedQueryAttention`` (q and k normed per head) with
    a ``window``: a sliding layer rotates q and k and sees ``window``
    positions back, a global layer (``window`` None) rotates nothing and
    sees everything."""

    def __init__(self, cfg: ExaoneMoeConfig, window: Optional[int]):
        super().__init__(
            cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.rms_norm_eps,
            cfg.rope_theta, cfg.initializer_range, cfg.dtype, qk_norm=True,
            rotary=False)
        self.window = window

    def qkv(self, u, positions):
        """The base's projections and norms; a sliding layer's q and k
        then rotated as plain XLA, which the compiler fuses into the norm
        before it (the ``rope`` kernel's block of 64 heads x 128 lanes
        overruns the scoped VMEM at 8,192 rows: compiled for a described
        v5e, PR 45). What a decode step and a ring's re-walk call;
        :meth:`full` makes its own q, k, v."""
        q, k, v = super().qkv(u, positions)
        if self.window is None:
            return q, k, v
        cos, sin = rope_tables(positions, self.head_dim, self.theta)
        cos, sin = cos[:, :, None], sin[:, :, None]
        return rotate_half_rope(q, cos, sin), rotate_half_rope(k, cos, sin), v

    def full(self, u):
        """Attention over a whole sequence -> (Op, k, v); the keys as
        they are kept (a sliding layer's rotated). A sliding layer's band
        is the kernel on a TPU, for heads of whole lanes (the kernel
        slices a head out of a block by lanes), and the einsums
        elsewhere: chosen from what the layer sees, by no argument."""
        if self.window is None:
            return super().full(u)
        B, S, _ = u.shape
        hd = self.head_dim
        cos, sin = rope_tables(jnp.arange(S), hd, self.theta)
        k = rms_head(mm(u, self.k_proj).reshape(B, S, -1, hd),
                     self.k_norm.weight._data, self.eps)
        k = rotate_half_rope(k, cos[:, None], sin[:, None])
        v = mm(u, self.v_proj).reshape(B, S, -1, hd)
        attend = sliding_attention_kernel \
            if _platform.on_tpu() and hd % pallas_band.LANES == 0 \
            else sliding_attention
        a = attend(mm(u, self.q_proj), self.q_norm.weight._data, k, v, cos,
                   sin, self.window, self.eps)
        return self.project(a), k, v


class ExaoneMoeSparseBlock(nn.Layer):
    """The shared expert beside the routed ones."""

    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        std, dt = cfg.initializer_range, cfg.dtype
        self.shared_experts = SwiGLU(
            cfg.hidden_size,
            cfg.moe_intermediate_size * cfg.num_shared_experts, std, dt)
        self.experts = DroplessExperts(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor, held=cfg.held_experts, std=std,
            dtype=dt, router="sigmoid", gated=True, norm_eps=1e-20)

    def run(self, a, valid=None, interpret=None):
        """a ``[T, H]`` -> (the layer's output, its routing record)."""
        with jax.named_scope("shared"):
            shared = self.shared_experts.run(a)
        routed, record = self.experts.route_and_run(a, valid, interpret)
        return shared + routed, record


class ExaoneMoeDecoderLayer(nn.Layer):
    def __init__(self, cfg: ExaoneMoeConfig, index: int):
        super().__init__()
        self.cfg = cfg
        self.window = cfg.window_of(index)
        self.is_dense = cfg.mlp_layer_types[index] == "dense"
        H, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(H, epsilon=eps)
        self.post_attention_layernorm = nn.RMSNorm(H, epsilon=eps)
        self.self_attn = ExaoneMoeAttention(cfg, self.window)
        self.mlp = SwiGLU(H, cfg.intermediate_size, cfg.initializer_range,
                          cfg.dtype) if self.is_dense \
            else ExaoneMoeSparseBlock(cfg)

    def attn_scope(self):
        """``attn``, and inside it ``window`` for a sliding layer."""
        stack = contextlib.ExitStack()
        stack.enter_context(jax.named_scope("attn"))
        if self.window is not None:
            stack.enter_context(jax.named_scope("window"))
        return stack

    def attn_input(self, x):
        return pre_norm(self.input_layernorm, x, self.cfg.rms_norm_eps)

    def feed(self, h, valid=None, interpret=None):
        """``h + FF(RMS(h))`` on ``[..., H]`` -> (y, the routing record
        or None)."""
        with jax.named_scope("mlp" if self.is_dense else "moe"):
            a = pre_norm(self.post_attention_layernorm, h,
                         self.cfg.rms_norm_eps)
            if self.is_dense:
                return h + self.mlp.run(a), None
            out, record = self.mlp.run(
                a.reshape(-1, a.shape[-1]),
                None if valid is None else valid.reshape(-1), interpret)
            return h + out.reshape(h.shape), record


class ExaoneMoeModel(nn.Layer):
    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=NormalDraw(
                0.0, cfg.initializer_range)))
        created_in(self.embed_tokens.weight, cfg.dtype)
        # before the layers, as in models/nemotron_h.py: the head's
        # float32 draft must not stand beside every layer's weights
        self.lm_head = linear(cfg.hidden_size, cfg.vocab_size,
                              cfg.initializer_range, cfg.dtype, NormalDraw)
        self.layers = nn.LayerList([ExaoneMoeDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def embed(self, ids):
        with jax.named_scope("embed"):
            return self.embed_tokens.weight._data[ids]

    def head(self, x):
        """Final norm and the untied head on ``[..., H]`` -> f32 logits."""
        x = pre_norm(self.norm, x, self.cfg.rms_norm_eps)
        with jax.named_scope("head_ce"):
            w = self.lm_head.weight._data
            return jnp.dot(x, w, precision=_mxu_precision(x, w),
                           preferred_element_type=jnp.float32)

    def full(self, ids, valid=None, interpret=None):
        """A whole causal pass over ``ids [B, S]`` -> (hidden ``[B, S,
        H]`` before the final norm; per layer (k, v) ``[B, S, nkv, hd]``
        as kept; per expert layer its routing record)."""
        x = self.embed(ids)
        kvs, records = [], []
        for layer in self.layers:
            with layer.attn_scope():
                op, k, v = layer.self_attn.full(layer.attn_input(x))
                x = x + op
            kvs.append((k, v))
            x, record = layer.feed(x, valid, interpret)
            if record is not None:
                records.append(record)
        return x, kvs, records


class ExaoneMoeForCausalLM(nn.Layer):
    """Trunk + the untied head."""

    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = ExaoneMoeModel(cfg)

    def forward(self, input_ids):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        hidden = self.model.full(ids.astype(jnp.int32))[0]
        return Tensor(self.model.head(hidden))

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


def exaone_moe_tiny(**overrides) -> ExaoneMoeConfig:
    """Test size: the benchmark's cut (a dense sliding layer, then
    sliding, sliding, full, sliding with experts), hidden 64, a window
    of 8; 8 experts top-2 of width 48 beside a shared one; 4 query over
    2 key/value heads of 16."""
    kw = dict(vocab_size=503, hidden_size=64, intermediate_size=160,
              moe_intermediate_size=48, num_hidden_layers=5,
              sliding_window=8, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, num_experts=8,
              num_experts_per_tok=2, max_position_embeddings=256)
    kw.update(overrides)
    return ExaoneMoeConfig(**kw)
