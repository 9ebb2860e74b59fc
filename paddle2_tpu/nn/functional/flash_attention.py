"""paddle.nn.functional.flash_attention (reference
python/paddle/nn/functional/flash_attention.py:195 flash_attention,
:593 flash_attn_unpadded, plus scaled_dot_product_attention re-export).

All paths route through the kernel selector in kernels/attention.py: the
Pallas flash kernel on TPU for long sequences, the XLA fused path
otherwise. Layout is the reference's (batch, seq, heads, head_dim).

``flash_attn_unpadded`` (varlen, cu_seqlens) is served by densifying into
a padded batch with a length mask — static shapes for jit; the packed
CUDA layout has no XLA analog, and padded+masked is the TPU-idiomatic
equivalent.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...framework.tensor import Tensor
from ...kernels.attention import (_sdpa_xla,
                                  scaled_dot_product_attention)
from ...ops.dispatch import apply_op, ensure_tensor

__all__ = ["flash_attention", "flash_attn_unpadded", "flash_attn_qkvpacked",
           "flash_attn_varlen_qkvpacked",
           "scaled_dot_product_attention", "sdp_kernel", "flashmask_attention", "sparse_attention"]


def flash_attention(query, key, value, dropout: float = 0.0,
                    causal: bool = False, return_softmax: bool = False,
                    *, fixed_seed_offset=None, rng_name: str = "",
                    training: bool = True, name=None):
    """flash_attention.py:195 parity: returns (out, softmax) — softmax is
    None unless return_softmax (which forces the XLA path: the flash
    kernel never materializes probabilities, that is its point)."""
    out = scaled_dot_product_attention(query, key, value,
                                       dropout_p=dropout, is_causal=causal,
                                       training=training)
    softmax = None
    if return_softmax:
        q, k = ensure_tensor(query), ensure_tensor(key)

        def probs(qa, ka):
            import math
            qh = jnp.swapaxes(qa, 1, 2).astype(jnp.float32)
            kh = jnp.swapaxes(ka, 1, 2).astype(jnp.float32)
            s = jnp.einsum("bhsd,bhtd->bhst", qh, kh) \
                / math.sqrt(qa.shape[-1])
            if causal:
                t_q, t_k = s.shape[-2], s.shape[-1]
                mask = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q)
                s = jnp.where(mask, s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1)
        softmax = apply_op("flash_softmax", probs, (q, k), {},
                           differentiable=False)
    return out, softmax


def flash_attn_qkvpacked(qkv, dropout: float = 0.0, causal: bool = False,
                         return_softmax: bool = False, **kwargs):
    """Packed [b, s, 3, h, d] variant (flash_attention.py qkvpacked)."""
    t = ensure_tensor(qkv)
    q, k, v = t[:, :, 0], t[:, :, 1], t[:, :, 2]
    return flash_attention(q, k, v, dropout=dropout, causal=causal,
                           return_softmax=return_softmax, **kwargs)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q: int, max_seqlen_k: int, scale: float,
                        dropout: float = 0.0, causal: bool = False,
                        return_softmax: bool = False, *,
                        fixed_seed_offset=None, rng_name: str = "",
                        training: bool = True, name=None):
    """Varlen attention over packed sequences (flash_attention.py:593).

    query/key/value: [total_tokens, heads, dim] packed rows;
    cu_seqlens_*: [batch+1] cumulative offsets. Densified to a padded
    [b, max_seqlen, h, d] batch; padding keys are masked out of the
    softmax and padded query rows are zeroed on output re-packing.
    """
    import numpy as np
    if return_softmax:
        raise NotImplementedError(
            "flash_attn_unpadded(return_softmax=True): the varlen path "
            "never materializes probabilities; use flash_attention")
    q = ensure_tensor(query)
    k = ensure_tensor(key)
    v = ensure_tensor(value)
    cu_q = np.asarray(ensure_tensor(cu_seqlens_q).numpy()).astype(np.int64)
    cu_k = np.asarray(ensure_tensor(cu_seqlens_k).numpy()).astype(np.int64)
    B = len(cu_q) - 1
    Sq, Sk = int(max_seqlen_q), int(max_seqlen_k)
    len_q = cu_q[1:] - cu_q[:-1]
    len_k = cu_k[1:] - cu_k[:-1]
    drop_key = None
    if dropout > 0.0 and training:
        from ...framework import random as fr
        drop_key = fr.next_key()

    # packed pallas path: the ragged batch stays ONE [T, H, D] packed
    # sequence with per-row segment ids — no O(B*Smax^2) densify
    from ...kernels._platform import on_tpu
    from ...kernels.attention import flash_enabled
    on_accel = on_tpu()
    head_dim = int(q.shape[-1])
    if (on_accel and flash_enabled() and drop_key is None
            and head_dim <= 256):   # pallas kernel range (supported())
        return _unpadded_packed(q, k, v, cu_q, cu_k, len_q, len_k,
                                scale, causal), None

    def _row_index(cu, lens, S):
        # [B, S] gather map into the packed rows; out-of-range positions
        # point at a sentinel zero row appended to the source
        idx = np.zeros((B, S), np.int64)
        for i in range(B):
            L = int(lens[i])
            idx[i, :L] = np.arange(int(cu[i]), int(cu[i]) + L)
            idx[i, L:] = -1  # sentinel (last row after the append below)
        return jnp.asarray(idx)

    iq_map = _row_index(cu_q, len_q, Sq)
    ik_map = _row_index(cu_k, len_k, Sk)

    def run(qa, ka, va):
        # one gather per tensor (sentinel row = zeros) instead of B
        # sequential full-buffer scatter copies
        def pad_one(arr, idx):
            with_sentinel = jnp.concatenate(
                [arr, jnp.zeros((1,) + arr.shape[1:], arr.dtype)], axis=0)
            return with_sentinel[idx]
        qp = pad_one(qa, iq_map)
        kp = pad_one(ka, ik_map)
        vp = pad_one(va, ik_map)
        # per-sequence mask: key must be real, and under causal each
        # query position may only see keys up to its own bottom-right
        # aligned diagonal len_k[i] - len_q[i] + qpos (PER ROW — the
        # padded maxes differ from each sequence's true lengths)
        lk = jnp.asarray(len_k)[:, None, None]            # [B,1,1]
        lq = jnp.asarray(len_q)[:, None, None]
        qpos = jnp.arange(Sq)[None, :, None]              # [1,Sq,1]
        kpos = jnp.arange(Sk)[None, None, :]              # [1,1,Sk]
        allowed = kpos < lk
        if causal:
            allowed = allowed & (kpos <= qpos + (lk - lq))
        bias = jnp.where(allowed, 0.0, -jnp.inf)[:, None]  # [B,1,Sq,Sk]
        out = _sdpa_xla(qp, kp, vp, bias=bias, causal=False,
                        scale=scale,
                        dropout_p=dropout if drop_key is not None else 0.0,
                        dropout_key=drop_key)
        # re-pack valid query rows with ONE gather (a per-sequence slice
        # loop would emit B dynamic-slices + concatenate)
        seq_of_row = np.repeat(np.arange(B), len_q.astype(np.int64))
        pos_of_row = (np.arange(int(cu_q[-1]))
                      - np.repeat(cu_q[:-1], len_q.astype(np.int64)))
        return out[jnp.asarray(seq_of_row), jnp.asarray(pos_of_row)]
    out = apply_op("flash_attn_unpadded", run, (q, k, v), {})
    return out, None


_SEG_CACHE: dict = {}


def _seg_off_device(cu_q, cu_k, len_q, len_k, causal):
    """Per-row (segment, causal-offset) metadata as DEVICE arrays, memoized
    on the cu_seqlens bytes — a bucketed training loop pays the host loop
    and the four uploads once per bucket, not once per step."""
    import numpy as np
    key = (cu_q.tobytes(), cu_k.tobytes(), bool(causal))
    hit = _SEG_CACHE.get(key)
    if hit is not None:
        return hit

    def seg_off(cu, lens, pad_id):
        T = int(cu[-1])
        seg = np.full(T, 0, np.int32)
        off = np.zeros(T, np.int32)
        for i in range(len(lens)):
            a, b = int(cu[i]), int(cu[i + 1])
            seg[a:b] = i
            off[a:b] = np.arange(b - a)
        Tp = -(-max(T, 8) // 8) * 8
        if Tp != T:
            seg = np.concatenate([seg, np.full(Tp - T, pad_id, np.int32)])
            off = np.concatenate([off, np.zeros(Tp - T, np.int32)])
        return seg, off, T, Tp

    seg_q, off_q, Tq, Tqp = seg_off(cu_q, len_q, -1)
    seg_k, off_k, Tk, Tkp = seg_off(cu_k, len_k, -2)
    if causal:
        # bottom-right alignment per sequence: q row allowance shifts by
        # (len_k - len_q) of its sequence
        for i in range(len(len_q)):
            a, b = int(cu_q[i]), int(cu_q[i + 1])
            off_q[a:b] = off_q[a:b] + int(len_k[i] - len_q[i])
    else:
        off_q = np.full_like(off_q, 2 ** 30)
    out = (jnp.asarray(seg_q), jnp.asarray(off_q), jnp.asarray(seg_k),
           jnp.asarray(off_k), Tq, Tqp, Tk, Tkp)
    if len(_SEG_CACHE) > 512:
        _SEG_CACHE.clear()
    _SEG_CACHE[key] = out
    return out


def _unpadded_packed(q, k, v, cu_q, cu_k, len_q, len_k, scale, causal):
    """Packed varlen kernel dispatch (no densify): per-row metadata from
    the host cu_seqlens (memoized), pallas kernel on the packed rows."""
    from ...kernels.pallas_flash import flash_attention_varlen_packed
    sq, oq, sk, ok, Tq, Tqp, Tk, Tkp = _seg_off_device(
        cu_q, cu_k, len_q, len_k, causal)

    def run(qa, ka, va):
        def pad_rows(a, Tp):
            T = a.shape[0]
            if Tp == T:
                return a
            return jnp.concatenate(
                [a, jnp.zeros((Tp - T,) + a.shape[1:], a.dtype)], axis=0)
        o = flash_attention_varlen_packed(
            pad_rows(qa, Tqp), pad_rows(ka, Tkp), pad_rows(va, Tkp),
            sq, oq, sk, ok, scale=scale)
        return o[:Tq]

    return apply_op("flash_attn_unpadded_packed", run, (q, k, v), {})


class sdp_kernel:
    """Kernel-selection context (reference sdp_kernel): toggles the
    Pallas flash path — enable_flash=False forces the XLA/math backend
    inside the block (thread-local, like the selection itself). The math
    backend cannot be disabled: it is the guaranteed-shape fallback, so
    enable_math=False raises instead of silently not applying."""

    def __init__(self, enable_math: bool = True, enable_flash: bool = True,
                 enable_mem_efficient: bool = True):
        if not enable_math:
            raise ValueError(
                "sdp_kernel(enable_math=False): the XLA math path is the "
                "guaranteed fallback on TPU and cannot be disabled")
        self.enable_flash = enable_flash
        self._prev = None

    def __enter__(self):
        from ...kernels import attention as _att
        self._prev = _att.flash_enabled()
        _att.set_flash_enabled(bool(self.enable_flash))
        return self

    def __exit__(self, *exc):
        from ...kernels import attention as _att
        _att.set_flash_enabled(self._prev)
        return False



def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k,
                                max_seqlen_q, max_seqlen_k, scale,
                                dropout: float = 0.0, causal: bool = False,
                                return_softmax: bool = False, **kwargs):
    """Varlen packed-QKV variant (flash_attention.py
    flash_attn_varlen_qkvpacked): qkv [total_tokens, 3, h, d]."""
    t = ensure_tensor(qkv)
    q, k, v = t[:, 0], t[:, 1], t[:, 2]
    return flash_attn_unpadded(q, k, v, cu_seqlens_q, cu_seqlens_k,
                               max_seqlen_q, max_seqlen_k, scale,
                               dropout=dropout, causal=causal,
                               return_softmax=return_softmax, **kwargs)


def flashmask_attention(query, key, value, startend_row_indices=None,
                        dropout: float = 0.0, causal: bool = False,
                        window_size=None, return_softmax_lse: bool = False,
                        return_seed_offset: bool = False,
                        fixed_seed_offset=None, rng_name: str = "",
                        training: bool = True, name=None):
    """FlashMask attention (reference flash_attention.py:1098): the mask
    is a column-wise sparse description — per KEY position, row ranges
    of the score matrix to mask:

      causal, last dim 1:  mask rows i >= s0[j]            (+ causal)
      causal, last dim 2:  mask s0[j] <= i < s1[j]         (+ causal)
      bidir,  last dim 2:  mask i >= s0[j]  and  i < s1[j]
      bidir,  last dim 4:  mask s0<=i<s1    and  s2<=i<s3

    The reference's CUDA kernel skips masked tiles; here the ranges
    materialize as a boolean mask inside one fused XLA attention — the
    tile-skipping Pallas variant follows the same contract.
    """
    tensors = [ensure_tensor(query), ensure_tensor(key),
               ensure_tensor(value)]
    has_idx = startend_row_indices is not None
    if has_idx:
        tensors.append(ensure_tensor(startend_row_indices))

    def fn(q, k, v, *rest):
        B, Sq, H, D = q.shape
        Sk = k.shape[1]
        scale = 1.0 / np.sqrt(D)
        # [B, H, Sq, Sk]
        scores = jnp.einsum("bqhd,bkhd->bhqk",
                            q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        rows = jnp.arange(Sq)[:, None]             # i
        cols = jnp.arange(Sk)[None, :]             # j
        masked = jnp.zeros((1, 1, Sq, Sk), bool)
        if causal:
            masked = masked | (rows < cols)[None, None]
        if window_size is not None:
            w = ((window_size, window_size)
                 if isinstance(window_size, int) else tuple(window_size))
            masked = masked | (rows - cols > w[0])[None, None]
            if not causal:
                masked = masked | (cols - rows > w[1])[None, None]
        if has_idx:
            idx = rest[0].astype(jnp.int32)        # [B, Hk, Sk, {1,2,4}]
            if idx.shape[1] == 1:
                idx = jnp.broadcast_to(idx, (B, H) + idx.shape[2:])
            n = idx.shape[-1]
            i = rows[None, None]                   # [1, 1, Sq, 1]
            s = jnp.swapaxes(idx, 2, 3)            # [B, H, n, Sk]
            if causal and n == 1:
                band = i >= s[:, :, 0][:, :, None, :]
            elif causal and n == 2:
                band = ((i >= s[:, :, 0][:, :, None, :])
                        & (i < s[:, :, 1][:, :, None, :]))
            elif not causal and n == 2:
                band = ((i >= s[:, :, 0][:, :, None, :])
                        | (i < s[:, :, 1][:, :, None, :]))
            elif not causal and n == 4:
                band = (((i >= s[:, :, 0][:, :, None, :])
                         & (i < s[:, :, 1][:, :, None, :]))
                        | ((i >= s[:, :, 2][:, :, None, :])
                           & (i < s[:, :, 3][:, :, None, :])))
            else:
                raise ValueError(
                    f"startend_row_indices last dim {n} invalid for "
                    f"causal={causal}")
            masked = masked | band
        scores = jnp.where(masked, -jnp.inf, scores)
        lse = jax.scipy.special.logsumexp(scores, axis=-1)
        probs = jnp.exp(scores - lse[..., None])
        # fully-masked rows: zero output, not NaN
        probs = jnp.where(jnp.isfinite(lse)[..., None], probs, 0.0)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                         v.astype(jnp.float32)).astype(q.dtype)
        if return_softmax_lse:
            return out, lse
        return out

    res = apply_op("flashmask_attention", fn, tuple(tensors), {})
    if return_seed_offset:
        extra = Tensor(jnp.zeros((2,), jnp.int32))
        return (res + (extra,)) if isinstance(res, tuple) else (res, extra)
    return res


def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None, name=None):
    """Block-sparse attention with a CSR pattern (reference
    sparse_attention op): q/k/v are [B, H, S, D]; per query row r, only
    the keys listed in columns[offset[r]:offset[r+1]] participate in the
    softmax. Dense-equivalent lowering: the CSR pattern scatters into a
    boolean mask consumed by one fused masked softmax."""
    tensors = [ensure_tensor(query), ensure_tensor(key),
               ensure_tensor(value), ensure_tensor(sparse_csr_offset),
               ensure_tensor(sparse_csr_columns)]
    extra = []
    if key_padding_mask is not None:
        extra.append(ensure_tensor(key_padding_mask))
    if attn_mask is not None:
        extra.append(ensure_tensor(attn_mask))
    tensors.extend(extra)
    has_kpm = key_padding_mask is not None
    has_am = attn_mask is not None

    def fn(q, k, v, offset, columns, *rest):
        B, H, S, D = q.shape
        nnz = columns.shape[-1]
        offset = offset.astype(jnp.int32)
        columns = columns.astype(jnp.int32)

        def one(off, cols):
            # nnz element e belongs to row searchsorted(off, e, 'right')-1
            rows = jnp.searchsorted(off, jnp.arange(nnz), side="right") - 1
            rows = jnp.clip(rows, 0, S - 1)
            valid = jnp.arange(nnz) < off[-1]
            m = jnp.zeros((S, S), bool)
            # max-scatter: padded tail elements (valid=False) collide at
            # clipped positions and must not clear real True entries
            return m.at[rows, jnp.clip(cols, 0, S - 1)].max(valid)

        allow = jax.vmap(jax.vmap(one))(offset, columns)   # [B, H, S, S]
        scale = 1.0 / np.sqrt(D)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        if has_kpm:
            kpm = rest[0]
            allow = allow & (kpm[:, None, None, :] > -1.0)
        if has_am:
            am = rest[-1]
            scores = scores + am.astype(jnp.float32)
        scores = jnp.where(allow, scores, -jnp.inf)
        lse = jax.scipy.special.logsumexp(scores, axis=-1)
        probs = jnp.where(jnp.isfinite(lse)[..., None],
                          jnp.exp(scores - lse[..., None]), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", probs,
                          v.astype(jnp.float32)).astype(q.dtype)

    return apply_op("sparse_attention", fn, tuple(tensors), {})
