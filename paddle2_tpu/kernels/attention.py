"""Attention kernels (phi flash_attn_kernel.cu / third_party/flashattn parity).

Two paths:
- `scaled_dot_product_attention`: reference XLA implementation (fused well by
  XLA on small/medium sequence lengths).
- the Pallas TPU flash-attention kernel in pallas_flash.py, used automatically
  on TPU for long sequences (tile-wise online softmax, O(S) memory).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor
from ..ops.dispatch import apply_op, ensure_tensor
from ._platform import on_tpu


def _sdpa_xla(q, k, v, bias=None, causal=False, scale=None, dropout_p=0.0,
              dropout_key=None, causal_block=1):
    """q,k,v: (B, S, H, D) paddle layout. ``causal_block`` B > 1 widens
    the causal mask to blocks of B: a row sees its whole block."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # (B, H, S, D)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    from ..ops.linalg import _mxu_precision
    prec = _mxu_precision(qh, kh)
    logits = jnp.einsum("bhsd,bhtd->bhst", qh, kh, precision=prec) * scale
    if bias is not None:
        logits = logits + bias
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        rows = jnp.arange(s)[:, None] + (t - s)
        if causal_block > 1:
            rows = rows // causal_block * causal_block + causal_block - 1
        mask = rows >= jnp.arange(t)[None, :]
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vh, precision=prec)
    return jnp.swapaxes(out, 1, 2)


import threading

_flash_tls = threading.local()  # sdp_kernel toggles per-thread


def remat_policy(base: str = "dots"):
    """Rematerialization policy for transformer blocks using this module's
    attention: the base policy ('dots' = dots_with_no_batch_dims_saveable,
    'nothing' = full recompute) EXTENDED to always save the flash kernel's
    named residuals (o, lse), so backward never re-runs the forward pallas
    kernel. The TPU analog of the reference's recompute_granularity
    selective lists (fleet recompute 'core_attn' exclusion)."""
    cp = jax.checkpoint_policies
    names = cp.save_only_these_names("flash_out", "flash_lse")
    if base == "dots":
        return cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable, names)
    if base == "dots_plus":
        # dots + flash residuals + the tagged gelu output: backward
        # recomputes only cheap elementwise (ln/adds), at ~+64MB/layer
        more = cp.save_only_these_names("flash_out", "flash_lse",
                                        "mlp_gelu")
        return cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable, more)
    if base == "dots_plus_ln":
        # also pin the layernorm outputs (tagged "ln_out"): backward skips
        # the LN re-reduction (2 reduce passes over [tokens, H] each), at
        # +2 activation tensors (~32MB/layer at the GPT bench shape)
        more = cp.save_only_these_names("flash_out", "flash_lse",
                                        "mlp_gelu", "ln_out")
        return cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable, more)
    if base == "offload":
        # park the matmul outputs + named residuals in pinned host
        # memory instead of recomputing OR holding them in HBM (the
        # remat searcher's "offload_dots" candidate). Approximation of
        # the modeled candidate: only dot outputs and tagged names
        # offload — cheap elementwise still recomputes, exactly the
        # backward work the search charged it.
        offload_names = cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=["flash_out", "flash_lse",
                                          "mlp_gelu", "ln_out"],
            offload_src="device", offload_dst="pinned_host")
        return cp.save_from_both_policies(
            cp.offload_dot_with_no_batch_dims("device", "pinned_host"),
            offload_names)
    return names


def flash_enabled() -> bool:
    return getattr(_flash_tls, "enabled", True)


def set_flash_enabled(flag: bool) -> None:
    _flash_tls.enabled = bool(flag)


def use_pallas(q_shape) -> bool:
    if not flash_enabled():
        return False
    if not on_tpu():
        return False
    # Pallas wins once the S*S score matrix stops fitting in VMEM-friendly
    # tiles; below that XLA's fusion is already near-roofline.
    return q_shape[1] >= 1024


def _per_shard(fn, q_shape):
    """Run the flash kernel per shard under a multi-device mesh.

    The chip's partitioner cannot split a Mosaic kernel ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call
    in a shard_map" — what a GSPMD-partitioned jit over a sharded batch
    or tensor-parallel heads draws), so the kernel is wrapped in
    ``shard_map``: batch over the data axes, heads over the model axes,
    each device seeing its LOCAL batch and heads. Axes that divide
    neither stay replicated. Inside an enclosing ``shard_map`` (the
    compiled pipelines) the operands are already local."""
    from ..distributed import mesh as mesh_mod
    from ..distributed.spec_layout import SpecLayout, installed_layout
    mesh = mesh_mod.get_mesh(auto_init=False)
    if mesh is None or mesh.size == 1 \
            or jax.sharding.get_abstract_mesh().manual_axes:
        return fn
    # the distributed layer owns the axis names: the layout installed
    # with the mesh, else the default one (dp / sharding / mp)
    layout = installed_layout() or SpecLayout()

    def axes_for(names, dim):
        axes = tuple(a for a in names if mesh.shape.get(a, 1) > 1)
        return axes if axes and dim % math.prod(
            mesh.shape[a] for a in axes) == 0 else None

    spec = jax.sharding.PartitionSpec(
        axes_for((layout.data_axis, layout.fsdp_axis), q_shape[0]), None,
        axes_for((layout.tp_axis,), q_shape[2]), None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, causal=None,
                                 training=True, name=None, causal_block=1,
                                 scale=None):
    """paddle.nn.functional.scaled_dot_product_attention parity.

    Inputs are (batch, seq, num_heads, head_dim) like the reference flash-attn
    API (paddle/phi/kernels/gpu/flash_attn_kernel.cu qkv layout).
    ``causal_block`` B > 1 (with ``is_causal``) masks by blocks of B
    positions: a query sees its own block whole and every earlier one.
    ``scale`` replaces the ``head_dim ** -0.5`` on the scores; the value
    heads may be narrower or wider than the query/key heads.
    """
    causal = causal if causal is not None else is_causal
    query, key, value = (ensure_tensor(query), ensure_tensor(key),
                         ensure_tensor(value))
    tensors = [query, key, value]
    has_mask = attn_mask is not None
    if has_mask:
        tensors.append(ensure_tensor(attn_mask))
    drop_key = None
    if dropout_p > 0.0 and training:
        from ..framework import random as fr
        drop_key = fr.next_key()

    if use_pallas(tuple(query.shape)) and not has_mask and drop_key is None:
        from .pallas_flash import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q,
                                   flash_attention_bshd)
        from ..incubate import autotune
        bq, bk = DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
        if autotune.kernel_tuning_enabled():
            bq, bk = autotune.best_flash_blocks(
                tuple(query.shape), tuple(key.shape), causal, (bq, bk))

        def fn(q, k, v):
            return flash_attention_bshd(q, k, v, causal=causal, scale=scale,
                                        block_q=bq, block_k=bk,
                                        causal_block=causal_block)
        return apply_op("flash_attention",
                        _per_shard(fn, tuple(query.shape)),
                        tuple(tensors), {})

    def fn(q, k, v, *mask):
        bias = mask[0] if mask else None
        return _sdpa_xla(q, k, v, bias=bias, causal=causal, scale=scale,
                         dropout_p=dropout_p if drop_key is not None else 0.0,
                         dropout_key=drop_key, causal_block=causal_block)
    return apply_op("sdpa", fn, tuple(tensors), {})


def _sdpa_xla_bhsd(q, k, v, causal=False, scale=None, causal_block=1):
    """:func:`_sdpa_xla` on (batch, heads, seq, dim) arrays (the swaps
    cancel against its own in the compiled program)."""
    return jnp.swapaxes(_sdpa_xla(
        *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), causal=causal,
        scale=scale, causal_block=causal_block), 1, 2)


def attention_bhsd(q, k, v, causal=False, scale=None, causal_block=1):
    """Attention over raw arrays in the flash kernels' own layout,
    (batch, heads, seq, dim): for a model whose projections write
    head-major and whose output projection contracts over (heads, dim)
    (``models/deepseek.py``), so that nothing is transposed around the
    kernel. Takes the Pallas kernel where
    :func:`scaled_dot_product_attention` would (``use_pallas``) and the
    XLA path elsewhere. One device's arrays: no mask, dropout, tape or
    mesh. The value heads may be of another width than the query/key
    heads."""
    B, H, S, D = q.shape
    if use_pallas((B, S, H, D)):
        from .pallas_flash import flash_attention_bhsd as attend
    else:
        attend = _sdpa_xla_bhsd
    return attend(q, k, v, causal=causal, scale=scale,
                  causal_block=causal_block)
