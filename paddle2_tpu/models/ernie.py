"""ERNIE/BERT-style encoder family (BASELINE config 2: "ERNIE-3.0-base
SST-2 fine-tune"; the reference zoo lives in PaddleNLP — structure follows
ernie/modeling.py: word+position+token-type embeddings, post-LN encoder,
pooler, task heads).

TPU-first like models/gpt.py: the homogeneous encoder stack compiles as
ONE lax.scan body (depth-independent compile), attention routes through
the kernel selector (pallas flash on TPU), and the whole fine-tune step
runs under jit.train_step.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor
from .. import nn
from ..nn import functional as F
from ..kernels.attention import scaled_dot_product_attention


@dataclass
class ErnieConfig:
    vocab_size: int = 40000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: Optional[int] = None      # default 4*hidden
    max_position_embeddings: int = 2048
    type_vocab_size: int = 4
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-12
    num_classes: int = 2
    use_scan: bool = True
    # [L, ...] stacked parameter storage for the encoder stack (see
    # GPTConfig.stacked_blocks / models/_scan.py StackedLayerStack):
    # removes the per-step restack of the scan operands. Per-layer
    # sublayers stop being addressable; eager training requires jit.
    stacked_blocks: bool = False

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def _attr(std):
    return nn.ParamAttr(initializer=nn.initializer.Normal(mean=0.0, std=std))


class ErnieSelfAttention(nn.Layer):
    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        h = cfg.hidden_size
        self.cfg = cfg
        self.qkv = nn.Linear(h, 3 * h, weight_attr=_attr(cfg.initializer_range))
        self.out = nn.Linear(h, h, weight_attr=_attr(cfg.initializer_range))

    def forward(self, x, attn_bias=None):
        cfg = self.cfg
        b, s, h = x.shape
        qkv = self.qkv(x).reshape([b, s, 3, cfg.num_heads, cfg.head_dim])
        q, k, v = qkv.unbind(axis=2)
        o = scaled_dot_product_attention(
            q, k, v, attn_mask=attn_bias, is_causal=False,
            dropout_p=cfg.attention_dropout_prob, training=self.training)
        return self.out(o.reshape([b, s, h]))


class ErnieLayer(nn.Layer):
    """Post-LN encoder block (BERT/ERNIE convention)."""

    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        eps = cfg.layer_norm_epsilon
        self.attn = ErnieSelfAttention(cfg)
        self.ln_1 = nn.LayerNorm(cfg.hidden_size, epsilon=eps)
        self.up = nn.Linear(cfg.hidden_size, cfg.ffn_size,
                            weight_attr=_attr(cfg.initializer_range))
        self.down = nn.Linear(cfg.ffn_size, cfg.hidden_size,
                              weight_attr=_attr(cfg.initializer_range))
        self.ln_2 = nn.LayerNorm(cfg.hidden_size, epsilon=eps)
        self.drop = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, x, attn_bias=None):
        # the same scope names as models/gpt.py (metadata only): each
        # sublayer with its residual add and its post-norm
        with jax.named_scope("attn"):
            h = x + self.drop(self.attn(x, attn_bias))
            with jax.named_scope("norm"):
                x = self.ln_1(h)
        with jax.named_scope("mlp"):
            h = x + self.drop(self.down(F.gelu(self.up(x))))
            with jax.named_scope("norm"):
                return self.ln_2(h)


class ErnieModel(nn.Layer):
    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        self.cfg = cfg
        std = cfg.initializer_range
        h = cfg.hidden_size
        self.word_emb = nn.Embedding(cfg.vocab_size, h, weight_attr=_attr(std))
        self.pos_emb = nn.Embedding(cfg.max_position_embeddings, h,
                                    weight_attr=_attr(std))
        self.type_emb = nn.Embedding(cfg.type_vocab_size, h,
                                     weight_attr=_attr(std))
        self.emb_ln = nn.LayerNorm(h, epsilon=cfg.layer_norm_epsilon)
        self.drop = nn.Dropout(cfg.hidden_dropout_prob)
        blocks = [ErnieLayer(cfg) for _ in range(cfg.num_layers)]
        if cfg.stacked_blocks:
            from ._scan import StackedLayerStack
            self.layers = StackedLayerStack(blocks)
        else:
            self.layers = nn.LayerList(blocks)
        self.pooler = nn.Linear(h, h, weight_attr=_attr(std))

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        b, s = input_ids.shape
        with jax.named_scope("embed"):
            pos = Tensor(jnp.arange(s, dtype=jnp.int32)[None, :])
            x = self.word_emb(input_ids) + self.pos_emb(pos)
            if token_type_ids is not None:
                x = x + self.type_emb(token_type_ids)
            x = self.drop(self.emb_ln(x))
        attn_bias = None
        if attention_mask is not None:
            m = attention_mask._data if isinstance(attention_mask, Tensor) \
                else jnp.asarray(attention_mask)
            # finite min in the ACTIVATION dtype: f32-min cast to bf16
            # overflows to -inf, which NaNs fully-masked softmax rows
            neg = jnp.finfo(jnp.result_type(x._data.dtype,
                                            jnp.float32)
                            if not jnp.issubdtype(x._data.dtype,
                                                  jnp.inexact)
                            else x._data.dtype).min
            attn_bias = Tensor(
                jnp.where(m[:, None, None, :].astype(bool), 0.0,
                          neg).astype(x._data.dtype))
        if self._can_scan(x, attn_bias):
            x = self._scan_layers(x)
        elif self.cfg.stacked_blocks:
            if attn_bias is None:
                x = self.layers(x, allow_scan=False)
            else:
                for i in range(self.cfg.num_layers):
                    x = self.layers.layer_slice_call(i, x,
                                                     attn_bias=attn_bias)
        else:
            for layer in self.layers:
                x = layer(x, attn_bias)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled

    def _can_scan(self, x, attn_bias) -> bool:
        cfg = self.cfg
        return (cfg.use_scan and cfg.num_layers > 1 and attn_bias is None
                and isinstance(x._data, jax.core.Tracer)
                and (not self.training
                     or (cfg.hidden_dropout_prob == 0.0
                         and cfg.attention_dropout_prob == 0.0)))

    def _scan_layers(self, x: Tensor) -> Tensor:
        """Depth-independent compile: one scanned block body (shared
        machinery in models/_scan.py)."""
        if self.cfg.stacked_blocks:
            return self.layers(x)
        from ._scan import scan_layer_stack
        out = scan_layer_stack(list(self.layers), x)
        if out is not None:
            return out
        for layer in self.layers:
            x = layer(x)
        return x


class ErnieForSequenceClassification(nn.Layer):
    """SST-2-style fine-tune head (BASELINE config 2 task)."""

    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        self.cfg = cfg
        self.ernie = ErnieModel(cfg)
        self.drop = nn.Dropout(cfg.hidden_dropout_prob)
        self.classifier = nn.Linear(cfg.hidden_size, cfg.num_classes,
                                    weight_attr=_attr(cfg.initializer_range))

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                labels=None):
        _, pooled = self.ernie(input_ids, token_type_ids, attention_mask)
        with jax.named_scope("head_ce"):
            logits = self.classifier(self.drop(pooled))
            if labels is None:
                return logits
            loss = F.cross_entropy(logits.astype("float32"),
                                   labels.reshape([-1]))
            return logits, loss

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


def ernie3_base(**overrides) -> ErnieConfig:
    """ERNIE-3.0-base geometry (BASELINE config 2)."""
    cfg = dict(vocab_size=40000, hidden_size=768, num_layers=12,
               num_heads=12, max_position_embeddings=2048)
    cfg.update(overrides)
    return ErnieConfig(**cfg)


def ernie_tiny(**overrides) -> ErnieConfig:
    cfg = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
               max_position_embeddings=64, type_vocab_size=2)
    cfg.update(overrides)
    return ErnieConfig(**cfg)
