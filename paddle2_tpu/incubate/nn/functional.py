"""paddle.incubate.nn.functional — genuinely fused TPU kernels.

Unlike the layer aliases in incubate.nn (where XLA's automatic fusion
covers the reference's fused_* kernels), the ops here are real fusions the
compiler cannot do on its own."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...framework.tensor import Tensor
from ...ops.dispatch import apply_op, ensure_tensor

__all__ = ["fused_linear_cross_entropy", "fused_rotary_position_embedding",
           "fused_rms_norm", "fused_adamw_kernel", "swiglu",
           "fused_matmul_bias", "fused_linear", "fused_linear_activation",
           "fused_bias_act", "fused_dropout_add", "fused_layer_norm",
           "fused_bias_dropout_residual_layer_norm", "fused_feedforward",
           "fused_multi_head_attention", "fused_moe",
           "masked_multihead_attention", "block_multihead_attention",
           "blha_get_max_len",
           "variable_length_memory_efficient_attention",
           "fused_multi_transformer"]

_ANGLE_CACHE: dict = {}


def _angle_table(S, D, base, neox, dtype):
    """Memoized rotary angle tables (decode loops call per step)."""
    import numpy as np
    import jax.numpy as jnp
    key = (S, D, base, neox, dtype)
    hit = _ANGLE_CACHE.get(key)
    if hit is not None:
        return hit
    import jax
    inv = 1.0 / (base ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    full = np.repeat(ang, 2, axis=1) if neox \
        else np.concatenate([ang, ang], axis=1)
    # concrete even under an active jit trace — otherwise the memo cache
    # would capture DynamicJaxprTracers and poison later eager calls
    with jax.ensure_compile_time_eval():
        out = (jnp.asarray(np.cos(full), dtype),
               jnp.asarray(np.sin(full), dtype))
    if len(_ANGLE_CACHE) > 64:
        _ANGLE_CACHE.clear()
    _ANGLE_CACHE[key] = out
    return out


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True,
                                    time_major=False,
                                    rotary_emb_base=10000.0):
    """Reference incubate fused_rotary_position_embedding.py:27 parity.

    q/k/v: [B, S, H, D]. On TPU the half-split convention
    (use_neox_rotary_style=False) runs the pallas fused_rope kernel —
    measured 2.23x over the XLA elementwise chain on v5e
    ([8,2048,16,128] bf16; the per-head angle broadcast stays in VMEM). The neox (adjacent-pair) convention and v (which rotary does
    not rotate in the reference either unless passed) use the XLA path.
    Returns (q_out, k_out, v_out) with None passthrough.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    first = next(t for t in (q, k, v) if t is not None)
    first = ensure_tensor(first)
    if time_major:
        raise NotImplementedError("time_major=True: transpose to "
                                  "[batch, seq, heads, dim] first")
    B, S, H, D = first.shape
    if sin is None or cos is None:
        rows = S
        if position_ids is not None \
                and not isinstance(ensure_tensor(position_ids)._data,
                                   jax.core.Tracer):
            # positions may exceed seq_len (decode loops index absolute
            # positions); JAX gathers clamp out-of-range indices, so an
            # S-row table would silently mis-rotate — size it to cover
            # the actual max position. Rows are bucketed to the next
            # multiple of 1024 so a decode loop reuses one memoized
            # table instead of rebuilding it every step. Traced
            # position_ids keep the S-row table (in-range by contract;
            # out-of-range needs explicit sin/cos sized to max position).
            pid = ensure_tensor(position_ids)._data
            max_pos = int(np.asarray(pid).max())
            if max_pos >= S:
                rows = -(-(max_pos + 1) // 1024) * 1024
        cos_a, sin_a = _angle_table(rows, D, float(rotary_emb_base),
                                    bool(use_neox_rotary_style),
                                    str(first._data.dtype))
    else:
        cos_a = ensure_tensor(cos)._data.reshape(-1, D)
        sin_a = ensure_tensor(sin)._data.reshape(-1, D)
        if cos_a.shape[0] != S and position_ids is None:
            if cos_a.shape[0] > S:
                # max-position table: positions are 0..S-1 here
                cos_a, sin_a = cos_a[:S], sin_a[:S]
            else:
                raise ValueError(
                    f"cos/sin table has {cos_a.shape[0]} positions but "
                    f"seq_len is {S}; pass position_ids or a table with "
                    "at least seq_len rows")
    if position_ids is not None:
        pos = ensure_tensor(position_ids)._data.astype(jnp.int32)
        cos_a = cos_a[pos].reshape(B * S, D)
        sin_a = sin_a[pos].reshape(B * S, D)

    from ...kernels._platform import on_tpu
    on_accel = on_tpu()

    def rot_one(arr):
        if not use_neox_rotary_style and on_accel:
            from ...kernels.pallas_fused import fused_rope
            return fused_rope(arr, cos_a, sin_a)
        c = cos_a.reshape(-1, S, 1, D) if cos_a.shape[0] != S \
            else cos_a[None, :, None, :]
        s = sin_a.reshape(-1, S, 1, D) if sin_a.shape[0] != S \
            else sin_a[None, :, None, :]
        if use_neox_rotary_style:
            x1 = arr[..., 0::2]
            x2 = arr[..., 1::2]
            rot = jnp.stack([-x2, x1], axis=-1).reshape(arr.shape)
        else:
            x1 = arr[..., : D // 2]
            x2 = arr[..., D // 2:]
            rot = jnp.concatenate([-x2, x1], axis=-1)
        return arr * c + rot * s

    outs = []
    for t in (q, k, v):
        if t is None:
            outs.append(None)
            continue
        t = ensure_tensor(t)
        outs.append(apply_op("fused_rope", rot_one, (t,), {}))
    return tuple(outs)


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None):
    """Pallas one-pass RMSNorm (fwd + custom bwd); with `bias`/`residual`
    the reference's fused add-then-norm: y = norm(x + bias + residual),
    returning (y, x + bias + residual) like fused_rms_norm's residual
    form. Quantized variants are not supported. NOTE: measured 0.83x of
    the XLA-fused chain on v5e ([8192,1024] bf16 fwd+bwd) — provided for
    reference parity and as a building block; prefer the plain
    expression under jit."""
    from ...kernels.pallas_fused import fused_rms_norm as kern
    x, w = ensure_tensor(x), ensure_tensor(norm_weight)
    nd = x.ndim
    if begin_norm_axis not in (-1, nd - 1):
        raise NotImplementedError(
            f"fused_rms_norm normalizes the LAST axis only "
            f"(begin_norm_axis={begin_norm_axis}, ndim={nd}); reshape "
            "so the normalized dims are flattened into the last axis")
    tensors = [x, w]
    if norm_bias is not None:
        tensors.append(ensure_tensor(norm_bias))
    if bias is not None:
        tensors.append(ensure_tensor(bias))
    if residual is not None:
        tensors.append(ensure_tensor(residual))

    def fn(xa, wa, *rest):
        it = iter(rest)
        nb = next(it) if norm_bias is not None else None
        ba = next(it) if bias is not None else None
        ra = next(it) if residual is not None else None
        pre = xa
        if ba is not None:
            pre = pre + ba
        if ra is not None:
            pre = pre + ra
        out = kern(pre, wa, epsilon=epsilon)
        if nb is not None:
            out = out + nb
        if residual is not None:
            return out, pre
        return out

    return apply_op("fused_rms_norm", fn, tuple(tensors), {})


def fused_adamw_kernel(param, grad, m, v, master, lr, beta1=0.9,
                       beta2=0.999, epsilon=1e-8, weight_decay=0.01,
                       step=1):
    """Single-pass pallas AdamW (fused_adam_kernel.cu parity). NOTE:
    measured 0.44x of XLA's fused update on v5e (84M f32 donated) — XLA
    already emits a one-pass loop for the update chain; kept for parity
    and for runtimes where the update is not under jit."""
    from ...kernels.pallas_fused import fused_adamw as kern
    outs = kern(ensure_tensor(param)._data, ensure_tensor(grad)._data,
                ensure_tensor(m)._data, ensure_tensor(v)._data,
                ensure_tensor(master)._data, lr, beta1, beta2, epsilon,
                weight_decay, step)
    return tuple(Tensor(a, stop_gradient=True) for a in outs)


def fused_linear_cross_entropy(x, weight, label, ignore_index=-100,
                               reduction="mean", name=None):
    """Cross-entropy of `softmax(x @ weight)` without materializing the
    [N, vocab] logits (chunked head+loss; kernels/fused_ce.py). The
    memory/bandwidth saver for large-vocab LM heads — the analog of the
    reference's c_softmax_with_cross_entropy fusion
    (python/paddle/distributed/fleet/layers/mpu/mp_ops.py) for the
    single-device case.

    x: [N, hidden] (or [B, S, hidden], flattened internally);
    weight: [hidden, vocab]; label: int [N] / [B, S].
    reduction: 'mean' over non-ignored tokens | 'sum' | 'none'.
    """
    from ...kernels.fused_ce import fused_linear_cross_entropy as kern
    import jax.numpy as jnp

    x, weight, label = (ensure_tensor(x), ensure_tensor(weight),
                        ensure_tensor(label))

    def fn(xa, wa, la):
        hidden = xa.shape[-1]
        losses, valid = kern(xa.reshape(-1, hidden), wa,
                             la.reshape(-1).astype(jnp.int32),
                             int(ignore_index))
        if reduction == "mean":
            denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
            return jnp.sum(losses) / denom
        if reduction == "sum":
            return jnp.sum(losses)
        return losses.reshape(la.shape)

    return apply_op("fused_linear_cross_entropy", fn, (x, weight, label), {})


def swiglu(x, y=None, name=None):
    """fused swiglu (incubate/nn/functional/swiglu.py): silu(x) * y;
    single-input form splits the last dim in half."""
    if y is None:
        def fn(a):
            u, v = jnp.split(a, 2, axis=-1)
            return jax.nn.silu(u) * v
        return apply_op("swiglu", fn, (ensure_tensor(x),), {})
    return apply_op("swiglu",
                    lambda a, b: jax.nn.silu(a) * b,
                    (ensure_tensor(x), ensure_tensor(y)), {})


def fused_matmul_bias(x, y, bias=None, transpose_x=False,
                      transpose_y=False, name=None):
    """fused_matmul_bias: one XLA fusion of matmul + bias."""
    ts = [ensure_tensor(x), ensure_tensor(y)]
    if bias is not None:
        ts.append(ensure_tensor(bias))

    def fn(a, b, *rest):
        if transpose_x:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_y:
            b = jnp.swapaxes(b, -1, -2)
        out = a @ b
        if rest:
            out = out + rest[0]
        return out
    return apply_op("fused_matmul_bias", fn, tuple(ts), {})


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    return fused_matmul_bias(x, weight, bias,
                             transpose_y=transpose_weight)


def fused_linear_activation(x, y, bias=None, trans_x=False, trans_y=False,
                            activation="gelu", name=None):
    out = fused_matmul_bias(x, y, bias, trans_x, trans_y)
    act = {"gelu": jax.nn.gelu, "relu": lambda a: jnp.maximum(a, 0),
           "none": lambda a: a, None: lambda a: a}[activation]
    return apply_op("fused_linear_act", act, (ensure_tensor(out),), {})


def fused_bias_act(x, bias=None, dequant_scales=None, shift=None,
                   smooth=None, act_method="gelu", compute_dtype="default",
                   quant_scale=-1, quant_round_type=0, quant_max_bound=0,
                   quant_min_bound=0, name=None):
    """fused_bias_act: bias + activation in one fusion (the quant knobs
    gate the int8 serving path; the float path is the TPU route)."""
    ts = [ensure_tensor(x)]
    if bias is not None:
        ts.append(ensure_tensor(bias))
    act = {"gelu": jax.nn.gelu, "relu": lambda a: jnp.maximum(a, 0),
           "swiglu": lambda a: (lambda u, v: jax.nn.silu(u) * v)(
               *jnp.split(a, 2, axis=-1)),
           "silu": jax.nn.silu}[act_method]

    def fn(a, *rest):
        if rest:
            a = a + rest[0]
        return act(a)
    return apply_op("fused_bias_act", fn, tuple(ts), {})


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    """fused_dropout_add: dropout(x) + y in one pass."""
    from ...framework import random as fr
    if not training or p == 0:
        return apply_op("fused_dropout_add", lambda a, b: a + b,
                        (ensure_tensor(x), ensure_tensor(y)), {})
    key = fr.next_key()

    def fn(a, b):
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        if mode == "upscale_in_train":
            a = jnp.where(keep, a / (1.0 - p), 0.0)
        else:
            a = jnp.where(keep, a, 0.0)
        return a + b
    return apply_op("fused_dropout_add", fn,
                    (ensure_tensor(x), ensure_tensor(y)), {})


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5,
                     residual_alpha=1.0, begin_norm_axis=1, bias=None,
                     residual=None, quant_scale=-1, quant_round_type=0,
                     quant_max_bound=0, quant_min_bound=0, name=None):
    """fused_layer_norm: (x + bias + alpha*residual) -> LayerNorm, one
    fusion. Returns (out, residual_out) when a residual is given, like
    the reference kernel."""
    ts = [ensure_tensor(x)]
    has_w = norm_weight is not None
    if has_w:
        ts.append(ensure_tensor(norm_weight))
    has_nb = norm_bias is not None
    if has_nb:
        ts.append(ensure_tensor(norm_bias))
    has_b = bias is not None
    if has_b:
        ts.append(ensure_tensor(bias))
    has_r = residual is not None
    if has_r:
        ts.append(ensure_tensor(residual))

    def fn(a, *rest):
        i = 0
        w = rest[i] if has_w else None
        i += has_w
        nb = rest[i] if has_nb else None
        i += has_nb
        b = rest[i] if has_b else None
        i += has_b
        r = rest[i] if has_r else None
        if b is not None:
            a = a + b
        if r is not None:
            a = a + residual_alpha * r
        red = tuple(range(begin_norm_axis, a.ndim))
        mu = jnp.mean(a, axis=red, keepdims=True)
        var = jnp.var(a, axis=red, keepdims=True)
        out = (a - mu) / jnp.sqrt(var + epsilon)
        if w is not None:
            out = out * w
        if nb is not None:
            out = out + nb
        return (out, a) if has_r else out
    return apply_op("fused_layer_norm", fn, tuple(ts), {})


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.5,
                                           ln_epsilon=1e-5,
                                           training=True,
                                           mode="upscale_in_train",
                                           name=None):
    """fused_bias_dropout_residual_layer_norm (incubate op): LayerNorm(
    residual + dropout(x + bias))."""
    y = fused_dropout_add(
        ensure_tensor(x) if bias is None else ensure_tensor(x)
        + ensure_tensor(bias),
        residual, p=dropout_rate, training=training, mode=mode)
    return fused_layer_norm(y, ln_scale, ln_bias, epsilon=ln_epsilon,
                            begin_norm_axis=y.ndim - 1)


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True, mode=
                      "upscale_in_train", name=None):
    """fused_feedforward (fused_transformer.py): the transformer FFN
    block — LN / linear1 / act / dropout / linear2 / dropout + residual
    — as one fused expression chain."""
    inp = ensure_tensor(x)
    h = inp
    if pre_layer_norm and ln1_scale is not None:
        h = fused_layer_norm(h, ln1_scale, ln1_bias, epsilon=ln1_epsilon,
                             begin_norm_axis=h.ndim - 1)
    h = fused_linear_activation(h, linear1_weight, linear1_bias,
                                activation=activation
                                if activation != "none" else "none")
    if training and dropout1_rate:
        from ...nn import functional as F
        h = F.dropout(h, p=dropout1_rate, training=True)
    h = fused_linear(h, linear2_weight, linear2_bias)
    h = fused_dropout_add(h, inp, p=dropout2_rate, training=training,
                          mode=mode)
    if not pre_layer_norm and ln2_scale is not None:
        h = fused_layer_norm(h, ln2_scale, ln2_bias, epsilon=ln2_epsilon,
                             begin_norm_axis=h.ndim - 1)
    return h


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None,
                               ln_bias=None, pre_ln_epsilon=1e-5,
                               qkv_bias=None, linear_bias=None,
                               cache_kv=None, attn_mask=None,
                               dropout_rate=0.5,
                               attn_dropout_rate=0.5, ln_epsilon=1e-5,
                               training=True, mode="upscale_in_train",
                               ring_id=-1, add_residual=True, name=None):
    """fused_multi_head_attention (fused_transformer.py:213): the full
    MHA block with fused qkv [3, H, D, hidden] weights."""
    from ...ops.dispatch import apply_op, ensure_tensor
    if cache_kv is not None:
        raise NotImplementedError(
            "fused_multi_head_attention cache_kv is the CUDA decode "
            "path; on TPU use nn.MultiHeadAttention with cache= or "
            "models.gpt.generate (scan KV cache)")
    inp = ensure_tensor(x)
    h = inp
    if pre_layer_norm and pre_ln_scale is not None:
        h = fused_layer_norm(h, pre_ln_scale, pre_ln_bias,
                             epsilon=pre_ln_epsilon,
                             begin_norm_axis=h.ndim - 1)
    qkvw = ensure_tensor(qkv_weight)
    ts = [ensure_tensor(h), qkvw]
    has_qb = qkv_bias is not None
    if has_qb:
        ts.append(ensure_tensor(qkv_bias))
    has_m = attn_mask is not None
    if has_m:
        ts.append(ensure_tensor(attn_mask))

    def attn(a, w, *rest):
        i = 0
        qb = rest[i] if has_qb else None
        i += has_qb
        m = rest[i] if has_m else None
        B, S, H = a.shape
        three, nh, hd, _ = w.shape
        qkv = jnp.einsum("bsh,tndh->tbsnd", a, w)
        if qb is not None:
            qkv = qkv + qb[:, None, None]
        q, k, v = qkv[0], qkv[1], qkv[2]
        scores = jnp.einsum("bsnd,btnd->bnst", q, k) / np.sqrt(hd)
        if m is not None:
            scores = scores + m
        p = jax.nn.softmax(scores, axis=-1)
        if training and attn_dropout_rate:
            keep = jax.random.bernoulli(_drop_key, 1.0 - attn_dropout_rate,
                                        p.shape)
            p = jnp.where(keep, p / (1.0 - attn_dropout_rate), 0.0)
        return jnp.einsum("bnst,btnd->bsnd", p, v).reshape(B, S, nh * hd)

    from ...framework import random as _fr
    _drop_key = _fr.next_key() if (training and attn_dropout_rate) \
        else None
    ctx = apply_op("fused_mha", attn, tuple(ts), {})
    out = fused_linear(ctx, linear_weight, linear_bias)
    if add_residual:
        out = fused_dropout_add(out, inp, p=dropout_rate,
                                training=training, mode=mode)
    if not pre_layer_norm and ln_scale is not None:
        out = fused_layer_norm(out, ln_scale, ln_bias, epsilon=ln_epsilon,
                               begin_norm_axis=out.ndim - 1)
    return out


def fused_moe(x, gate_weight, ffn1_weights, ffn2_weights, *args, **kwargs):
    """fused_moe: use incubate.MoELayer / distributed MoE dispatch — the
    TPU path is the GShard sort/scatter dispatch, not a monolithic
    kernel."""
    raise NotImplementedError(
        "fused_moe's monolithic kernel has no TPU analog; build the "
        "block with paddle.incubate.MoELayer (GShard dispatch, "
        "expert-parallel over the mesh)")


def masked_multihead_attention(x, cache_kv=None, bias=None,
                               src_mask=None, *args, **kwargs):
    raise NotImplementedError(
        "masked_multihead_attention is the CUDA serving decode kernel; "
        "on TPU use nn.MultiHeadAttention with cache= for decode, or "
        "models.gpt.generate (scan-based KV cache)")


def block_multihead_attention(*args, **kwargs):
    raise NotImplementedError(
        "block_multihead_attention (paged KV cache) is a CUDA serving "
        "kernel; the TPU serving path is paddle.inference over StableHLO "
        "with the flash-attention kernels")


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size):
    """Serving helper: max sequence lengths for the block attention —
    host-computable and kept functional."""
    import numpy as _np
    from ...framework.tensor import Tensor
    enc = _np.asarray(ensure_tensor(seq_lens_encoder).numpy())
    dec = _np.asarray(ensure_tensor(seq_lens_decoder).numpy())
    return (Tensor(jnp.asarray([int(enc.max()) if enc.size else 0])),
            Tensor(jnp.asarray([int(dec.max()) if dec.size else 0])))


def variable_length_memory_efficient_attention(query, key, value,
                                               seq_lens, kv_seq_lens,
                                               mask=None, scale=None,
                                               causal=False, pre_cache_length=0):
    """Varlen attention: routes to the packed varlen flash path (the
    TPU-native equivalent of the CUDA memory-efficient kernel)."""
    q = ensure_tensor(query)   # [B, H, S, D]
    k = ensure_tensor(key)
    v = ensure_tensor(value)
    sl = ensure_tensor(seq_lens)
    kl = ensure_tensor(kv_seq_lens)
    ts = [q, k, v, sl, kl]
    has_m = mask is not None
    if has_m:
        ts.append(ensure_tensor(mask))

    def fn(qa, ka, va, sla, kla, *rest):
        B, H, S, D = qa.shape
        sc = scale if scale is not None else 1.0 / np.sqrt(D)
        scores = jnp.einsum("bhsd,bhtd->bhst", qa, ka) * sc
        if rest:
            scores = scores + rest[0]   # additive mask (ALiBi/padding)
        q_pos = jnp.arange(S)[None, None, :, None]
        k_pos = jnp.arange(ka.shape[2])[None, None, None, :]
        valid = ((q_pos < sla.reshape(-1)[:, None, None, None])
                 & (k_pos < kla.reshape(-1)[:, None, None, None]))
        if causal:
            valid = valid & (k_pos <= q_pos)
        scores = jnp.where(valid, scores, -1e9)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhst,bhtd->bhsd", p, va)

    return apply_op("varlen_mem_eff_attn", fn, tuple(ts), {})


def fused_multi_transformer(x, ln_scales, ln_biases, qkv_weights,
                            qkv_biases, linear_weights, linear_biases,
                            ffn_ln_scales, ffn_ln_biases, ffn1_weights,
                            ffn1_biases, ffn2_weights, ffn2_biases,
                            pre_layer_norm=True, epsilon=1e-5,
                            cache_kvs=None, time_step=None,
                            attn_mask=None, dropout_rate=0.0,
                            activation="gelu", training=False,
                            mode="upscale_in_train", trans_qkvw=True,
                            ring_id=-1, name=None):
    """fused_multi_transformer (fused_transformer.py:750): a whole stack
    of pre-LN transformer layers in one call, composed from the fused
    blocks above (XLA fuses within each; the scan-based GPT stack is the
    training-speed path)."""
    h = x
    L = len(qkv_weights)
    if not trans_qkvw:
        # reference alternate layout [hidden, 3, H, D] -> [3, H, D, hidden]
        from ...ops.dispatch import ensure_tensor as _et
        from ...framework.tensor import Tensor as _T
        qkv_weights = [_T(jnp.transpose(_et(w)._data, (1, 2, 3, 0)))
                       for w in qkv_weights]
    for i in range(L):
        h = fused_multi_head_attention(
            h, qkv_weights[i], linear_weights[i], pre_layer_norm=True,
            pre_ln_scale=ln_scales[i],
            pre_ln_bias=ln_biases[i] if ln_biases else None,
            qkv_bias=qkv_biases[i] if qkv_biases else None,
            linear_bias=linear_biases[i] if linear_biases else None,
            attn_mask=attn_mask, dropout_rate=dropout_rate,
            attn_dropout_rate=dropout_rate, training=training, mode=mode,
            pre_ln_epsilon=epsilon)
        h = fused_feedforward(
            h, ffn1_weights[i], ffn2_weights[i],
            linear1_bias=ffn1_biases[i] if ffn1_biases else None,
            linear2_bias=ffn2_biases[i] if ffn2_biases else None,
            ln1_scale=ffn_ln_scales[i],
            ln1_bias=ffn_ln_biases[i] if ffn_ln_biases else None,
            dropout1_rate=dropout_rate, dropout2_rate=dropout_rate,
            activation=activation, pre_layer_norm=True,
            ln1_epsilon=epsilon, training=training, mode=mode)
    return h
