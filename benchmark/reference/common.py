"""What the plain references share: layer norm, the matmul hook that
the lower-precision control swaps in, AdamW, and per-leaf norms."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def matmul_f32(x, w):
    """The reference's matmul: float32 operands, full precision (the
    caller holds ``jax.default_matmul_precision("highest")``)."""
    return jnp.matmul(x, w)


def _fake_int8(a, axis):
    """Symmetric absmax int8 along ``axis``, straight-through gradient."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(a / scale), -127, 127) * scale
    return a + jax.lax.stop_gradient(q - a)


def matmul_int8(x, w):
    """The control's matmul, the nearest precision below bf16: int8
    operands (activations per token, weights per output channel),
    exact accumulation."""
    return jnp.matmul(_fake_int8(x, -1), _fake_int8(w, -2))


def _fake_fp8(a, axis):
    """fp8 with 4 exponent and 3 mantissa bits (IEEE-style e4m3, largest
    finite value 240) after an absmax scale to that value;
    straight-through gradient."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 240.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jax.lax.reduce_precision(a / scale, 4, 3) * scale
    return a + jax.lax.stop_gradient(q - a)


def matmul_fp8(x, w):
    """The other precision below bf16: fp8 e4m3 operands, scaled per
    token and per output channel, exact accumulation."""
    return jnp.matmul(_fake_fp8(x, -1), _fake_fp8(w, -2))


def _round_bf16(a):
    return a + jax.lax.stop_gradient(jax.lax.reduce_precision(a, 8, 7) - a)


def matmul_bf16(x, w):
    """bf16 operands, exact accumulation: what the configuration states.
    Used only to show that the stated precision passes the check."""
    return jnp.matmul(_round_bf16(x), _round_bf16(w))


MATMULS = {"float32": matmul_f32, "bfloat16": matmul_bf16,
           "int8": matmul_int8, "fp8": matmul_fp8}


def adamw_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros, "v": jax.tree_util.tree_map(jnp.zeros_like, params)}


def adamw_update(params, grads, state, t, hp):
    """Decoupled AdamW as published (Loshchilov & Hutter), float32."""
    b1, b2, eps = hp["beta1"], hp["beta2"], hp["epsilon"]
    lr, wd = hp["learning_rate"], hp["weight_decay"]
    t = jnp.asarray(t, jnp.float32)

    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - step - lr * wd * p, m, v

    out = {k: one(params[k], grads[k], state["m"][k], state["v"][k])
           for k in params}
    return ({k: o[0] for k, o in out.items()},
            {"m": {k: o[1] for k, o in out.items()},
             "v": {k: o[2] for k, o in out.items()}})


def leaf_norms(tree, stacked):
    """L2 norm of every leaf; a leaf stacked over layers gives one norm
    per layer. Returns {name: f32 vector}."""
    out = {}
    for k, a in tree.items():
        a = a.astype(jnp.float32)
        if k in stacked:
            out[k] = jnp.sqrt(jnp.sum(a.reshape(a.shape[0], -1) ** 2, -1))
        else:
            out[k] = jnp.sqrt(jnp.sum(a ** 2)).reshape(1)
    return out
