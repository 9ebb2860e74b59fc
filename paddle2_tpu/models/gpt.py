"""GPT-style decoder LM — the flagship model family (BASELINE config 4:
"GPT-3 1.3B Fleet hybrid-parallel"; reference model zoo lives in PaddleNLP,
structure mirrored from fleet mp examples: fused qkv, pre-LN blocks,
Column/Row-parallel MLP like fleet/layers/mpu/mp_layers.py usage).

TPU-first design: one logical module works at every parallelism degree —
  * tensor_parallel=True swaps Linear for GSPMD-sharded Column/Row layers
    (mp mesh axis), including the vocab-parallel embedding + tied head.
  * sequence_parallel=True keeps inter-block activations sharded over the
    'sep' axis on the sequence dim (Megatron-SP; attention re-gathers).
  * the flash-attention kernel (kernels/) serves the sdpa hot path.
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
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position_embeddings: int = 1024
    intermediate_size: Optional[int] = None  # default 4*hidden
    hidden_dropout_prob: float = 0.0
    attention_dropout_prob: float = 0.0
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = True
    tensor_parallel: bool = False
    sequence_parallel: bool = False
    # long-context attention over the 'sep' mesh ring: "none" | "ring"
    # (KV rotation via collective-permute) | "ulysses" (all-to-all head
    # resharding). See distributed/sep.py.
    context_parallel: str = "none"
    use_recompute: bool = False
    # remat selectivity: "full" recomputes everything (min memory);
    # "dots" saves matmul outputs and recomputes elementwise only
    # (jax checkpoint_policies.dots_with_no_batch_dims_saveable) — the
    # usual best speed/memory point on TPU; "dots_plus"/"dots_plus_ln"
    # additionally pin the gelu / LN outputs; "offload" parks the
    # heavies in pinned host memory; "search" runs the deterministic
    # cost-model policy search (incubate.autotune.search_remat_policy)
    # once per (batch, seq) and wires the minimal-recompute policy
    # that fits remat_budget_gb
    recompute_granularity: str = "full"
    # HBM budget the "search" granularity must fit (params + grads +
    # optimizer state + saved activations, cost-model accounting).
    # None: $PADDLE_REMAT_BUDGET_GB, else the v5e 16 GB default.
    remat_budget_gb: Optional[float] = None
    # compile the block stack as ONE lax.scan body under to_static —
    # compile time (and HLO size) become depth-independent, the standard
    # TPU recipe for deep transformers. Falls back to the Python loop in
    # eager mode or when dropout makes per-layer RNG streams necessary.
    use_scan: bool = True
    # store the block stack's parameters PRE-STACKED as [L, ...] leaves
    # (models/_scan.py StackedLayerStack): the scan consumes them with
    # zero per-step restacking. Measured on v5e (r5): the per-step
    # dynamic-update-slice stack of 24 layers' weights (+ the matching
    # grad unstack) is ~GBs of pure HBM traffic — the bulk of the
    # "framework tax" vs a bare-JAX probe. Trade-off: per-block
    # sub-layers (model.gpt.h[i]) are not addressable and eager
    # *training* must run under jit (to_static / train_step); eager
    # inference works.
    stacked_blocks: bool = False
    # compute the LM loss through the chunked fused head+CE kernel
    # (incubate.nn.functional.fused_linear_cross_entropy): the [tokens,
    # vocab] f32 logits are never materialized. forward(labels=...) then
    # returns (None, loss). Single-device / non-TP path only.
    fused_head_loss: bool = False
    # opt-in TRAINING-TIME int8 weight-only path for the lm_head /
    # logits matmul: the head weight is per-vocab-channel absmax
    # fake-quantized (straight-through gradients back to the fp
    # weight), so the forward logits equal the int8 weight-only
    # serving matmul within its analytic error bound while training
    # stays differentiable. Shared-embedding aware: with tied
    # embeddings only the HEAD read of wte is quantized, never the
    # embedding lookup. Mutually exclusive with fused_head_loss
    # (whose chunked kernel owns the head matmul).
    quantized_lm_head: bool = False

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def _init_attr(std):
    return nn.ParamAttr(initializer=nn.initializer.Normal(mean=0.0, std=std))


def convert_pre_r5_qkv_weight(w, num_heads: int, head_dim: int):
    """Permute a fused qkv weight/bias from the pre-r5 column layout
    ``[.., (q|k|v), heads, d]`` to the current HEAD-MAJOR layout
    ``[.., heads, (q|k|v), d]`` (see GPTAttention.forward — the change
    makes mp shards split at head boundaries). Apply to ``qkv.weight``
    ([in, 3h]) and ``qkv.bias`` ([3h]) when loading a checkpoint saved
    before the layout change; shapes are unchanged, so the load itself
    cannot detect the mismatch."""
    arr = w._data if isinstance(w, Tensor) else jnp.asarray(w)
    lead = arr.shape[:-1]
    out = arr.reshape(lead + (3, num_heads, head_dim))
    out = jnp.swapaxes(out, -3, -2).reshape(arr.shape)
    return Tensor(out) if isinstance(w, Tensor) else out


def _linear_pair(cfg: GPTConfig, d_in, d_mid, std):
    """(up, down) projections: parallel Column/Row when tensor_parallel."""
    if cfg.tensor_parallel:
        from ..distributed.fleet import (ColumnParallelLinear,
                                         RowParallelLinear)
        up = ColumnParallelLinear(d_in, d_mid, weight_attr=_init_attr(std),
                                  gather_output=False)
        down = RowParallelLinear(d_mid, d_in, weight_attr=_init_attr(std),
                                 input_is_parallel=True)
    else:
        up = nn.Linear(d_in, d_mid, weight_attr=_init_attr(std))
        down = nn.Linear(d_mid, d_in, weight_attr=_init_attr(std))
    return up, down


def _seq_constrain(x: Tensor, cfg: GPTConfig) -> Tensor:
    """Keep activations sharded [dp(batch), sep(seq), -] between blocks."""
    if not cfg.sequence_parallel:
        return x
    from ..distributed import get_mesh
    from ..distributed.fleet.mp_layers import _constrain_tensor
    from jax.sharding import PartitionSpec as P
    mesh = get_mesh()
    if mesh is None or "sep" not in mesh.axis_names:
        return x
    batch_axis = "dp" if "dp" in mesh.axis_names else None
    return _constrain_tensor(x, P(batch_axis, "sep",
                                  *([None] * (x.ndim - 2))))


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        std = cfg.initializer_range
        proj_std = std / math.sqrt(2 * cfg.num_layers)
        if cfg.tensor_parallel:
            from ..distributed.fleet import (ColumnParallelLinear,
                                             RowParallelLinear)
            self.qkv = ColumnParallelLinear(h, 3 * h,
                                            weight_attr=_init_attr(std),
                                            gather_output=False)
            self.out_proj = RowParallelLinear(h, h,
                                              weight_attr=_init_attr(std),
                                              input_is_parallel=True)
        else:
            self.qkv = nn.Linear(h, 3 * h, weight_attr=_init_attr(std))
            self.out_proj = nn.Linear(h, h, weight_attr=_init_attr(std))
        # GPT-2 init: residual-out projections scaled by 1/sqrt(2*layers)
        w = self.out_proj.weight
        data = nn.initializer.Normal(mean=0.0, std=proj_std)(w.shape, w.dtype)
        data = data._data if isinstance(data, Tensor) else jnp.asarray(data)
        w._replace_data(jax.device_put(data, w._data.sharding))

    def forward(self, x, cache=None):
        """cache: optional (k, v) of past tokens [b, s_past, H, D] —
        autoregressive decode appends this step's k/v and attends over the
        full prefix (causal stays correct: our sdpa is bottom-right
        aligned for s_q < s_k). Returns out, or (out, new_cache) when a
        cache (possibly empty tuple) is passed."""
        cfg = self.cfg
        b, s, h = x.shape
        qkv = self.qkv(x)  # [b, s, 3h] (mp-sharded when TP)
        # HEAD-MAJOR fused layout [heads, (q|k|v), head_dim]: an mp shard
        # of the output dim then splits at head boundaries, so the
        # manual-mp local block reshapes to whole heads (num_heads/mp of
        # them — hence -1) and GSPMD avoids a reshard on this reshape.
        # A (3, heads, d) layout would hand rank 0 "all of q + half of
        # k" under TP.
        qkv = qkv.reshape([b, s, -1, 3, cfg.head_dim])
        q, k, v = qkv.unbind(axis=3)
        new_cache = None
        if cache is not None:
            if len(cache) == 2:
                from ..ops.manipulation import concat
                k = concat([cache[0], k], axis=1)
                v = concat([cache[1], v], axis=1)
            new_cache = (k, v)
        if cfg.context_parallel != "none":
            if cfg.attention_dropout_prob > 0.0 and self.training:
                raise ValueError(
                    "attention_dropout_prob > 0 is not supported with "
                    "context_parallel (the ring/ulysses paths have no "
                    "dropout); set it to 0 or use hidden dropout")
            from ..distributed.sep import ring_attention, ulysses_attention
            attn = (ring_attention if cfg.context_parallel == "ring"
                    else ulysses_attention)
            out = attn(q, k, v, causal=True)
        else:
            out = scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=cfg.attention_dropout_prob, training=self.training)
        out = out.reshape([b, s, -1])   # h, or h/mp under manual-mp
        out = self.out_proj(out)
        return (out, new_cache) if cache is not None else out


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.up, self.down = _linear_pair(cfg, cfg.hidden_size, cfg.ffn_size,
                                          cfg.initializer_range)
        # the gelu residual tag only matters when the dots_plus remat
        # policy will consume it; other configs skip the extra dispatch.
        # "search"/"offload" tag unconditionally: the resolved policy
        # may pin the name, and an unconsumed checkpoint_name is a
        # bitwise-neutral identity
        self._tag_gelu = (cfg.use_recompute
                          and cfg.recompute_granularity in
                          ("dots_plus", "dots_plus_ln", "search",
                           "offload"))

    def forward(self, x):
        h = F.gelu(self.up(x))
        if self._tag_gelu and self.training:
            # named residual for the "dots_plus" policy (saves the gelu
            # output so backward skips its recompute). Routed through
            # apply_op: the tag must not sever the eager tape (it is a
            # recorded identity with identity VJP).
            from jax.ad_checkpoint import checkpoint_name
            from ..ops.dispatch import apply_op
            h = apply_op("mlp_gelu_tag",
                         lambda a: checkpoint_name(a, "mlp_gelu"),
                         (h,), {})
        return self.down(h)


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        eps = cfg.layer_norm_epsilon
        self.ln_1 = nn.LayerNorm(cfg.hidden_size, epsilon=eps)
        self.attn = GPTAttention(cfg)
        self.ln_2 = nn.LayerNorm(cfg.hidden_size, epsilon=eps)
        self.mlp = GPTMLP(cfg)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)
        self._tag_ln = (cfg.use_recompute
                        and cfg.recompute_granularity in
                        ("dots_plus_ln", "search", "offload"))

    def _ln(self, norm, x):
        out = norm(x)
        if self._tag_ln and self.training:
            # named residual for the "dots_plus_ln" policy (saves the LN
            # output so backward skips its re-reduction)
            from jax.ad_checkpoint import checkpoint_name
            from ..ops.dispatch import apply_op
            out = apply_op("ln_out_tag",
                           lambda a: checkpoint_name(a, "ln_out"),
                           (out,), {})
        return out

    def forward(self, x, cache=None):
        # jax.named_scope = the layer boundaries a device trace shows:
        # attn and mlp, each with its pre-norm (attn/norm, mlp/norm)
        # and its residual add; metadata only, the arithmetic is what
        # it was
        new_cache = None
        with jax.named_scope("attn"):
            with jax.named_scope("norm"):
                h = self._ln(self.ln_1, x)
            if cache is not None:
                a, new_cache = self.attn(h, cache=cache)
            else:
                a = self.attn(h)
            x = x + self.dropout(a)
        with jax.named_scope("mlp"):
            with jax.named_scope("norm"):
                h = self._ln(self.ln_2, x)
            x = x + self.dropout(self.mlp(h))
        x = _seq_constrain(x, self.cfg)
        return (x, new_cache) if cache is not None else x


class GPTModel(nn.Layer):
    """Transformer trunk: embeddings -> blocks -> final LN."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        std = cfg.initializer_range
        if cfg.tensor_parallel:
            from ..distributed.fleet import VocabParallelEmbedding
            self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size,
                                              weight_attr=_init_attr(std))
        else:
            self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                    weight_attr=_init_attr(std))
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                                weight_attr=_init_attr(std))
        self.drop = nn.Dropout(cfg.hidden_dropout_prob)
        blocks = [GPTBlock(cfg) for _ in range(cfg.num_layers)]
        if cfg.stacked_blocks:
            from ._scan import StackedLayerStack
            self.h = StackedLayerStack(blocks)
        else:
            self.h = nn.LayerList(blocks)
        self.ln_f = nn.LayerNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)
        # "search" granularity: plans resolved per (batch, seq) by the
        # deterministic cost-model searcher; the per-shape cache token
        # keys the jit.train_step program cache so two models differing
        # only in resolved policy never share a compiled entry
        self._remat_plans: dict = {}
        # untied-head models register their extra head params here
        # (GPTForCausalLM ctor): the budget's fixed-bytes accounting
        # must see EVERY trained parameter, not just the trunk's
        self._remat_fixed_params_extra = 0

    # -- remat policy resolution ----------------------------------------
    def _resolved_remat(self, batch: int, seq: int):
        """(use_recompute, granularity) for this forward. Non-"search"
        configs pass through; "search" resolves (and caches) a
        :class:`~paddle2_tpu.incubate.autotune.RematPlan` for the
        (batch, seq) shape — a pure function of config + rate model,
        so every host resolves the same policy."""
        cfg = self.cfg
        if not cfg.use_recompute:
            return False, cfg.recompute_granularity
        if cfg.recompute_granularity != "search":
            return True, cfg.recompute_granularity
        key = (int(batch), int(seq))
        plan = self._remat_plans.get(key)
        if plan is None:
            import os as _os
            from ..incubate import autotune
            budget_gb = cfg.remat_budget_gb
            if budget_gb is None:
                budget_gb = float(_os.environ.get(
                    "PADDLE_REMAT_BUDGET_GB", 16.0))
            # fixed footprint: bf16 params + bf16 grads + f32 master +
            # two f32 Adam moments (the multi-precision AdamW worst
            # case the BENCH config trains with); the extra term covers
            # params owned OUTSIDE the trunk (an untied lm_head)
            n_params = (sum(int(p.size) for p in self.parameters())
                        + int(self._remat_fixed_params_extra))
            fixed = float(n_params) * (2.0 + 2.0 + 3 * 4.0)
            plan = autotune.search_remat_policy(
                hidden=cfg.hidden_size, num_layers=cfg.num_layers,
                num_heads=cfg.num_heads, seq=seq, batch=batch,
                ffn=cfg.ffn_size, budget_bytes=budget_gb * 1e9,
                fixed_bytes=fixed)
            self._remat_plans[key] = plan
        return plan.use_recompute, plan.granularity

    def _remat_token_for(self, batch: int, seq: int):
        """The program-cache token of THIS shape's resolved plan —
        per shape, never the last-resolved one (a stale global token
        would force a duplicate compile every time shapes alternate)."""
        plan = self._remat_plans.get((int(batch), int(seq)))
        if plan is None:
            return None
        return plan.cache_token() + (int(batch), int(seq))

    def _prepare_remat(self, arg_arrays):
        """jit.train_step protocol: resolve the searched policy from
        the call's batch shape BEFORE the program-cache key is
        computed, and return THIS SHAPE's cache token (None when
        nothing is searched). Keeps the first compiled entry and every
        later same-shape call under the SAME key — no wasted duplicate
        compile, even when batch shapes alternate."""
        cfg = self.cfg
        if not (cfg.use_recompute
                and cfg.recompute_granularity == "search"
                and self.training):
            return None
        for a in arg_arrays:
            shp = getattr(a, "shape", None)
            if shp is not None and len(shp) == 2:
                self._resolved_remat(int(shp[0]), int(shp[1]))
                return self._remat_token_for(int(shp[0]), int(shp[1]))
        return None

    def remat_plan(self, batch: int, seq: int):
        """The resolved searched plan for a shape (resolving it if
        needed) — None unless granularity is "search"."""
        self._resolved_remat(batch, seq)
        return self._remat_plans.get((int(batch), int(seq)))

    def forward(self, input_ids):
        b, s = input_ids.shape
        with jax.named_scope("embed"):
            pos = Tensor(jnp.arange(s, dtype=jnp.int32)[None, :])
            x = self.wte(input_ids) + self.wpe(pos)
            x = _seq_constrain(self.drop(x), self.cfg)
        use_rc, gran = (self._resolved_remat(b, s) if self.training
                        else (False, None))
        if self._can_scan(x):
            x = self._scan_blocks(x, use_rc, gran)
        else:
            x = self._fallback_loop(x, use_rc, gran)
        with jax.named_scope("norm"):
            return self.ln_f(x)

    def _can_scan(self, x) -> bool:
        cfg = self.cfg
        return (cfg.use_scan and cfg.num_layers > 1
                and isinstance(x._data, jax.core.Tracer)
                and (cfg.hidden_dropout_prob == 0.0
                     and cfg.attention_dropout_prob == 0.0
                     or not self.training))

    def _scan_blocks(self, x: Tensor, use_rc: bool, gran) -> Tensor:
        """Run the homogeneous block stack as one lax.scan (shared
        machinery in models/_scan.py). With use_recompute the body is
        jax.checkpoint-ed with kernels.attention.remat_policy: 'dots' +
        pinned flash residuals means backward reuses the saved flash
        (o, lse) instead of re-running the kernel."""
        from ._scan import scan_layer_stack

        wrap = None
        if use_rc and self.training:
            from ..kernels.attention import remat_policy
            policy = remat_policy(
                gran if gran in ("dots", "dots_plus", "dots_plus_ln",
                                 "offload")
                else "nothing")
            wrap = lambda body: jax.checkpoint(body, policy=policy)
        if self.cfg.stacked_blocks:
            return self.h(x, wrap_body=wrap)
        out = scan_layer_stack(list(self.h), x, wrap_body=wrap)
        return out if out is not None else \
            self._fallback_loop(x, use_rc, gran)

    def _fallback_loop(self, x: Tensor, use_rc: bool = None,
                       gran=None) -> Tensor:
        if use_rc is None:
            use_rc, gran = (self._resolved_remat(*x.shape[:2])
                            if self.training else (False, None))
        if self.cfg.stacked_blocks:
            # allow_scan=False: this path is taken exactly when _can_scan
            # said no (eager, or dropout needs per-layer rng streams)
            return self.h(x, allow_scan=False)
        for block in self.h:
            if use_rc and self.training:
                from ..distributed.recompute import recompute
                x = recompute(block, x, policy=gran)
            else:
                x = block(x)
        return x

    def decode_step(self, input_ids, caches, position_offset: int):
        """KV-cached decode: run only the NEW tokens through the trunk,
        appending to per-layer (k, v) caches. caches: list of per-block
        tuples (() on the first/prefill call)."""
        b, s = input_ids.shape
        with jax.named_scope("embed"):
            pos = Tensor(jnp.arange(position_offset, position_offset + s,
                                    dtype=jnp.int32)[None, :])
            x = self.wte(input_ids) + self.wpe(pos)
            x = _seq_constrain(self.drop(x), self.cfg)
        new_caches = []
        if self.cfg.stacked_blocks:
            for i, cache in enumerate(caches):
                x, c = self.h.layer_slice_call(i, x, cache=cache)
                new_caches.append(c)
        else:
            for block, cache in zip(self.h, caches):
                x, c = block(x, cache=cache)
                new_caches.append(c)
        with jax.named_scope("norm"):
            return self.ln_f(x), new_caches


class GPTForCausalLM(nn.Layer):
    """Trunk + LM head (tied to wte by default, like the reference zoo)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        if cfg.quantized_lm_head and cfg.fused_head_loss:
            raise ValueError(
                "quantized_lm_head and fused_head_loss are mutually "
                "exclusive: the chunked fused-CE kernel owns the head "
                "matmul, so there is no logits matmul to quantize")
        self.gpt = GPTModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     weight_attr=_init_attr(
                                         cfg.initializer_range),
                                     bias_attr=False)
            # the remat searcher's fixed-bytes budget must count the
            # head params the trunk cannot see
            self.gpt._remat_fixed_params_extra = int(
                self.lm_head.weight.size)

    def _prepare_remat(self, arg_arrays):
        """jit.train_step cache-key protocol — delegate to the trunk."""
        return self.gpt._prepare_remat(arg_arrays)

    def _head(self, hidden):
        # serving-time int8 payload installed by
        # quantization.quantize_lm_head (shared-embedding aware: the
        # embedding LOOKUP stays fp — only this head read is int8)
        wo = getattr(self, "_wo_head", None)
        if wo is not None:
            return wo(hidden)
        if self.cfg.quantized_lm_head:
            # training-time int8 weight-only path: per-vocab-channel
            # absmax fake quantization (STE) — forward logits equal
            # the int8 serving matmul's dequantized product within its
            # analytic bound, gradients flow straight through to the
            # fp weight (and the tied embedding)
            from ..quantization import channel_absmax, fake_quant
            w = (self.gpt.wte.weight.T if self.cfg.tie_word_embeddings
                 else self.lm_head.weight)
            scale = channel_absmax(w, axis=1)
            w = fake_quant(w, scale, bits=8, quant_axis=1)
            return F.linear(hidden, w)
        if self.cfg.tie_word_embeddings:
            return F.linear(hidden, self.gpt.wte.weight.T)
        return self.lm_head(hidden)

    def forward(self, input_ids, labels=None):
        hidden = self.gpt(input_ids)
        with jax.named_scope("head_ce"):
            if (labels is not None and self.cfg.fused_head_loss
                    and not self.cfg.tensor_parallel):
                from ..incubate.nn.functional import \
                    fused_linear_cross_entropy
                w = (self.gpt.wte.weight.T if self.cfg.tie_word_embeddings
                     else self.lm_head.weight)
                loss = fused_linear_cross_entropy(hidden, w, labels)
                return None, loss
            logits = self._head(hidden)
            if labels is None:
                return logits
            loss = F.cross_entropy(
                logits.reshape([-1, self.cfg.vocab_size]).astype("float32"),
                labels.reshape([-1]))
            return logits, loss

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 eos_token_id: Optional[int] = None):
        """Autoregressive decoding (PaddleNLP generate() capability).

        Greedy when temperature == 0, otherwise temperature/top-k/top-p
        sampling through the framework RNG (seeded by paddle.seed).
        Decoding runs through per-layer KV caches (prefill once, then one
        new token per step); past max_position_embeddings — or when
        context_parallel attention is active, whose ring/ulysses paths
        need full equal-length sequences — it falls back to windowed full
        forwards.
        """
        from ..framework import core
        from ..framework import random as fr
        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(jnp.asarray(input_ids, jnp.int32))
        arr = ids._data.astype(jnp.int32)
        if arr.ndim == 1:
            arr = arr[None]
        max_pos = self.cfg.max_position_embeddings
        finished = jnp.zeros((arr.shape[0],), bool)
        caches = ([() for _ in range(self.cfg.num_layers)]
                  if self.cfg.context_parallel == "none" else None)
        pos = 0
        with core.no_grad():
            for _ in range(max_new_tokens):
                if arr.shape[1] > max_pos:
                    # context overflow: fall back to windowed full forward
                    caches = None
                if caches is not None:
                    new_tok = arr[:, pos:]        # prefill, then 1/step
                    hidden, caches = self.gpt.decode_step(
                        Tensor(new_tok), caches, pos)
                    pos = arr.shape[1]
                    # only the LAST position feeds sampling: skip the
                    # [s, vocab] prefill logits entirely
                    logits = self._head(hidden[:, -1:])
                else:
                    logits = self._head(self.gpt(Tensor(arr[:, -max_pos:])))
                step = logits._data[:, -1].astype(jnp.float32)  # [B, V]
                if temperature == 0.0:
                    nxt = jnp.argmax(step, axis=-1)
                else:
                    step = step / max(temperature, 1e-6)
                    if top_k is not None:
                        kth = jnp.sort(step, axis=-1)[:, -int(top_k)]
                        step = jnp.where(step < kth[:, None], -jnp.inf,
                                         step)
                    if top_p is not None:
                        from ..ops.extra import nucleus_filter_logits
                        step = nucleus_filter_logits(
                            step, jnp.full((step.shape[0],), top_p,
                                           jnp.float32))
                    nxt = jax.random.categorical(fr.next_key(), step)
                if eos_token_id is not None:
                    # finished rows pad with eos (PaddleNLP semantics)
                    nxt = jnp.where(finished, eos_token_id, nxt)
                    finished = finished | (nxt == eos_token_id)
                arr = jnp.concatenate(
                    [arr, nxt[:, None].astype(jnp.int32)], axis=1)
                if eos_token_id is not None and bool(jnp.all(finished)):
                    break
        return Tensor(arr, stop_gradient=True)


def gpt3_1p3b(**overrides) -> GPTConfig:
    """BASELINE config 4 geometry (GPT-3 1.3B)."""
    cfg = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
               num_heads=16, max_position_embeddings=2048)
    cfg.update(overrides)
    return GPTConfig(**cfg)


def gpt_small(**overrides) -> GPTConfig:
    cfg = dict(vocab_size=50304, hidden_size=768, num_layers=12,
               num_heads=12, max_position_embeddings=1024)
    cfg.update(overrides)
    return GPTConfig(**cfg)


def gpt_tiny(**overrides) -> GPTConfig:
    """Test/dryrun geometry."""
    cfg = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
               max_position_embeddings=64)
    cfg.update(overrides)
    return GPTConfig(**cfg)
