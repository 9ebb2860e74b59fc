"""Normalization functionals (python/paddle/nn/functional/norm.py parity).

layer_norm/rms_norm are single fused XLA reductions; batch_norm returns
updated running stats functionally (the Layer wrapper owns the buffers).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...framework.tensor import Tensor
from ...ops.dispatch import apply_op, ensure_tensor

__all__ = ["batch_norm", "layer_norm", "instance_norm", "group_norm",
           "local_response_norm", "rms_norm"]


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05,
               data_format="NCHW", use_global_stats=None, name=None) -> Tensor:
    x = ensure_tensor(x)
    running_mean = ensure_tensor(running_mean)
    running_var = ensure_tensor(running_var)
    channel_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != channel_axis)
    use_batch_stats = training and not use_global_stats

    tensors = [x, running_mean, running_var]
    has_w = weight is not None
    has_b = bias is not None
    if has_w:
        tensors.append(ensure_tensor(weight))
    if has_b:
        tensors.append(ensure_tensor(bias))

    def fn(a, rm, rv, *wb):
        shape = [1] * a.ndim
        shape[channel_axis] = a.shape[channel_axis]
        if use_batch_stats:
            mean = jnp.mean(a, axis=reduce_axes)
            var = jnp.var(a, axis=reduce_axes)
        else:
            mean, var = rm, rv
        out = (a - mean.reshape(shape)) * jax.lax.rsqrt(
            var.reshape(shape) + epsilon)
        i = 0
        if has_w:
            out = out * wb[i].reshape(shape); i += 1
        if has_b:
            out = out + wb[i].reshape(shape)
        return out.astype(a.dtype)

    out = apply_op("batch_norm", fn, tuple(tensors), {})

    if use_batch_stats:
        # update running stats in place on the buffer tensors (eager semantics;
        # the jit bridge captures these as extra outputs)
        a = x._data
        mean = jnp.mean(a, axis=reduce_axes)
        var = jnp.var(a, axis=reduce_axes)
        running_mean._replace_data(
            momentum * running_mean._data + (1 - momentum) * mean)
        running_var._replace_data(
            momentum * running_var._data + (1 - momentum) * var)
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None) -> Tensor:
    x = ensure_tensor(x)
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    n_axes = len(normalized_shape)
    axes = tuple(range(x.ndim - n_axes, x.ndim))

    tensors = [x]
    has_w = weight is not None
    has_b = bias is not None
    if has_w:
        tensors.append(ensure_tensor(weight))
    if has_b:
        tensors.append(ensure_tensor(bias))

    def fn(a, *wb):
        mean = jnp.mean(a, axis=axes, keepdims=True)
        var = jnp.var(a, axis=axes, keepdims=True)
        out = (a - mean) * jax.lax.rsqrt(var + epsilon)
        i = 0
        if has_w:
            out = out * wb[i]; i += 1
        if has_b:
            out = out + wb[i]
        return out.astype(a.dtype)
    return apply_op("layer_norm", fn, tuple(tensors), {})


def rms_norm(x, weight=None, epsilon=1e-6, name=None) -> Tensor:
    x = ensure_tensor(x)
    tensors = [x] if weight is None else [x, ensure_tensor(weight)]
    def fn(a, *w):
        # rms in f32 for bf16 stability, like fused_rms_norm kernels
        h = a.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + epsilon)
        out = h * rms
        if w:
            out = out * w[0].astype(jnp.float32)
        return out.astype(a.dtype)
    return apply_op("rms_norm", fn, tuple(tensors), {})


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-05,
                  data_format="NCHW", name=None) -> Tensor:
    x = ensure_tensor(x)
    channel_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    reduce_axes = tuple(i for i in range(2, x.ndim)) if channel_axis == 1 else \
        tuple(i for i in range(1, x.ndim - 1))

    tensors = [x]
    has_w = weight is not None
    has_b = bias is not None
    if has_w:
        tensors.append(ensure_tensor(weight))
    if has_b:
        tensors.append(ensure_tensor(bias))

    def fn(a, *wb):
        mean = jnp.mean(a, axis=reduce_axes, keepdims=True)
        var = jnp.var(a, axis=reduce_axes, keepdims=True)
        out = (a - mean) * jax.lax.rsqrt(var + eps)
        shape = [1] * a.ndim
        shape[channel_axis] = a.shape[channel_axis]
        i = 0
        if has_w:
            out = out * wb[i].reshape(shape); i += 1
        if has_b:
            out = out + wb[i].reshape(shape)
        return out.astype(a.dtype)
    return apply_op("instance_norm", fn, tuple(tensors), {})


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None) -> Tensor:
    x = ensure_tensor(x)
    channel_last = not data_format.startswith("NC")

    tensors = [x]
    has_w = weight is not None
    has_b = bias is not None
    if has_w:
        tensors.append(ensure_tensor(weight))
    if has_b:
        tensors.append(ensure_tensor(bias))

    def fn(a, *wb):
        if channel_last:
            a_t = jnp.moveaxis(a, -1, 1)
        else:
            a_t = a
        n, c = a_t.shape[0], a_t.shape[1]
        rest = a_t.shape[2:]
        g = a_t.reshape(n, num_groups, c // num_groups, *rest)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(a_t.shape)
        shape = [1] * out.ndim
        shape[1] = c
        i = 0
        if has_w:
            out = out * wb[i].reshape(shape); i += 1
        if has_b:
            out = out + wb[i].reshape(shape)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out.astype(a.dtype)
    return apply_op("group_norm", fn, tuple(tensors), {})


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None) -> Tensor:
    x = ensure_tensor(x)
    channel_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    def fn(a):
        sq = jnp.square(a)
        half = size // 2
        pad_cfg = [(0, 0)] * a.ndim
        pad_cfg[channel_axis] = (half, size - half - 1)
        padded = jnp.pad(sq, pad_cfg)
        window = [1] * a.ndim
        window[channel_axis] = size
        s = jax.lax.reduce_window(padded, 0.0, jax.lax.add, tuple(window),
                                  (1,) * a.ndim, "VALID")
        return a / jnp.power(k + alpha * s / size, beta)
    return apply_op("local_response_norm", fn, (x,), {})
