"""Sequence / context parallelism: ring attention + Ulysses all-to-all.

Parity targets: the reference's sep parallelism (fleet/base/topology.py sep
axis, meta_parallel segment utilities) and its ring-p2p long-context path
(NCCL send/recv of KV blocks). TPU-native redesign:

- ``ring_attention``: shard_map over the 'sep' mesh axis. Each device owns
  a sequence chunk of Q/K/V; KV blocks rotate around the ICI ring via
  lax.ppermute while each step's partial attention is merged online with
  the numerically-stable log-sum-exp rule (the flash-attention merge).
  Peak memory is O(S/n) per chip and the N-1 rotations overlap compute.
- ``ulysses_attention``: the all-to-all formulation (DeepSpeed-Ulysses):
  resharding seq-sharded QKV to head-sharded via sharding constraints, so
  GSPMD emits the all-to-alls; full-sequence attention runs per head
  group, then the output reshards back to sequence-sharded.

Both consume paddle-layout (batch, seq, heads, dim) Tensors.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..framework.tensor import Tensor
from ..ops.dispatch import apply_op, ensure_tensor
from . import mesh as mesh_mod

__all__ = ["ring_attention", "ulysses_attention",
           "SequenceAxisError", "HeadShardingError"]

_NEG = float("-inf")


class SequenceAxisError(ValueError):
    """The requested (or inferred) sequence-parallel mesh axis does not
    exist on the current mesh. Subclasses ValueError so pre-existing
    callers that caught the untyped inference failure keep working —
    the fix (ISSUE 20) is that a *named* ``mesh_axis=`` absent from the
    mesh now raises this instead of a bare ``KeyError`` from the later
    ``mesh.shape[axis]`` lookup."""


class HeadShardingError(ValueError):
    """Ulysses head sharding is impossible: the head count does not
    divide by the sequence-parallel degree, so the seq->head all-to-all
    has no integral head group per rank. Subclasses ValueError for
    backward compatibility with callers catching the untyped raise."""


def _block_attn_lse(q, k, v, scale, mask):
    """Full (small-block) attention returning (out, lse).

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; mask: None | 'causal' | a
    traced/bool [Sq, Sk] matrix (True = attend)."""
    B, Sq, H, D = q.shape
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhsd,bhtd->bhst", qh, kh) * scale
    if mask is not None:
        if isinstance(mask, str):
            Sk = s.shape[-1]
            mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        s = jnp.where(mask, s, _NEG)
    m = jnp.max(s, axis=-1)                                  # [B,H,Sq]
    m_safe = jnp.where(m == _NEG, 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(s == _NEG, 0.0, p)
    l = jnp.sum(p, axis=-1)                                  # [B,H,Sq]
    o = jnp.einsum("bhst,bhtd->bhsd", p, vh)
    o = o / jnp.maximum(l, 1e-30)[..., None]
    lse = jnp.where(l == 0.0, _NEG, m_safe + jnp.log(jnp.maximum(l, 1e-30)))
    return jnp.swapaxes(o, 1, 2).astype(q.dtype), lse


def _merge(o1, lse1, o2, lse2):
    """Log-sum-exp merge of two partial attention results."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(m == _NEG, 0.0, m)
    w1 = jnp.where(lse1 == _NEG, 0.0, jnp.exp(lse1 - m_safe))
    w2 = jnp.where(lse2 == _NEG, 0.0, jnp.exp(lse2 - m_safe))
    tot = jnp.maximum(w1 + w2, 1e-30)
    o = (o1.astype(jnp.float32) * jnp.swapaxes(w1, 1, 2)[..., None]
         + o2.astype(jnp.float32) * jnp.swapaxes(w2, 1, 2)[..., None]) \
        / jnp.swapaxes(tot, 1, 2)[..., None]
    lse = jnp.where((w1 + w2) == 0.0, _NEG, m_safe + jnp.log(tot))
    return o.astype(o1.dtype), lse


def _ring_body(q, k, v, *, axis, n, scale, causal):
    """Local computation inside shard_map: q/k/v are the device's sequence
    chunk [B, S/n, H, D]."""
    i = jax.lax.axis_index(axis)
    o = jnp.zeros_like(q)
    lse = jnp.full(
        (q.shape[0], q.shape[2], q.shape[1]), _NEG, jnp.float32)
    perm = [(r, (r + 1) % n) for r in range(n)]
    cur_k, cur_v = k, v
    chunk = q.shape[1]
    for t in range(n):
        # Block-offset convention (load-bearing for causal masking, and
        # mirrored float64-for-float64 by the longseq_fleet oracle): KV
        # blocks rotate FORWARD around the ring (rank r sends to r+1),
        # so after t hops rank i holds the KV chunk that ORIGINATED on
        # rank j = (i - t) mod n. Global token indices are block-major:
        # query rows of rank i are [i*chunk, (i+1)*chunk) and the held
        # KV columns are [j*chunk, (j+1)*chunk), which makes causality
        # a pure block predicate on (i, j) — no per-token global-index
        # arithmetic is ever needed.
        j = (i - t) % n  # origin chunk of the kv currently held
        if causal:
            # bottom-right-aligned global causality across chunks, as ONE
            # mask select (no duplicated attention): j < i full block
            # (every KV column is strictly in the past), j == i
            # intra-chunk lower-triangular, j > i fully masked (the
            # whole block is in the future; _block_attn_lse returns
            # lse = -inf rows and _merge drops them with weight 0)
            tril = jnp.tril(jnp.ones((chunk, chunk), bool))
            full = jnp.ones((chunk, chunk), bool)
            none = jnp.zeros((chunk, chunk), bool)
            mask = jnp.where(j == i, tril, jnp.where(j < i, full, none))
            o_b, lse_b = _block_attn_lse(q, cur_k, cur_v, scale, mask)
        else:
            o_b, lse_b = _block_attn_lse(q, cur_k, cur_v, scale, None)
        o, lse = _merge(o, lse, o_b, lse_b)
        if t < n - 1:
            cur_k = jax.lax.ppermute(cur_k, axis, perm)
            cur_v = jax.lax.ppermute(cur_v, axis, perm)
    return o


def _seq_axis(mesh_axis: Optional[str]) -> str:
    mesh = mesh_mod.get_mesh()
    if mesh_axis is not None:
        if mesh_axis not in mesh.axis_names:
            raise SequenceAxisError(
                f"mesh axis {mesh_axis!r} not on the current mesh "
                f"(axes: {tuple(mesh.axis_names)}); init a mesh with "
                f"that axis or drop mesh_axis= to auto-detect")
        return mesh_axis
    for name in ("sep", "cp", "sp"):
        if name in mesh.axis_names and mesh.shape[name] > 1:
            return name
    raise SequenceAxisError(
        "no sequence-parallel mesh axis found; init a mesh "
        "with a 'sep' axis or pass mesh_axis=")


def ring_attention(query, key, value, causal: bool = False,
                   scale: Optional[float] = None,
                   mesh_axis: Optional[str] = None):
    """Exact attention over a sequence sharded on a mesh ring.

    Inputs are GLOBAL [B, S, H, D] Tensors (sharded or replicated); the
    sequence dim is (re)sharded over the ring axis, KV blocks rotate via
    collective-permute, and the result equals full softmax attention to
    numerical precision — memory per chip stays O(S/n * S/n) per step.
    """
    q, k, v = (ensure_tensor(t) for t in (query, key, value))
    mesh = mesh_mod.get_mesh()
    axis = _seq_axis(mesh_axis)
    n = int(mesh.shape[axis])
    if q.shape[1] % n != 0:
        raise ValueError(f"seq len {q.shape[1]} not divisible by ring "
                         f"degree {n}")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    batch_axis = "dp" if "dp" in mesh.axis_names else None
    spec = P(batch_axis, axis, None, None)
    from .fleet.mp_layers import _constrain_tensor
    q = _constrain_tensor(q, spec)  # commit chunks onto the ring
    k = _constrain_tensor(k, spec)
    v = _constrain_tensor(v, spec)
    key = (id(mesh), axis, n, float(scale), bool(causal), batch_axis)
    fn = _ring_cache.get(key)
    if fn is None:
        fn = shard_map(
            partial(_ring_body, axis=axis, n=n, scale=float(scale),
                    causal=bool(causal)),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        fn = jax.jit(fn)  # executable cache keyed on avals by jax itself
        _ring_cache[key] = fn
    return apply_op("ring_attention", fn, (q, k, v), {})


_ring_cache: dict = {}


def ulysses_attention(query, key, value, causal: bool = False,
                      scale: Optional[float] = None,
                      mesh_axis: Optional[str] = None):
    """DeepSpeed-Ulysses sequence parallelism: all-to-all from seq-sharded
    to head-sharded, full attention per head group, all-to-all back. The
    resharding is expressed as GSPMD constraints; XLA emits all-to-alls."""
    from ..kernels.attention import scaled_dot_product_attention as sdpa
    q, k, v = (ensure_tensor(t) for t in (query, key, value))
    mesh = mesh_mod.get_mesh()
    axis = _seq_axis(mesh_axis)
    if q.shape[2] % mesh.shape[axis] != 0:
        raise HeadShardingError(
            f"num_heads {q.shape[2]} not divisible by sep "
            f"degree {mesh.shape[axis]}")
    batch_axis = "dp" if "dp" in mesh.axis_names else None
    from .fleet.mp_layers import _constrain_tensor
    head_spec = P(batch_axis, None, axis, None)
    seq_spec = P(batch_axis, axis, None, None)
    if scale is not None:
        # sdpa hard-codes 1/sqrt(D) (paddle API); fold a custom scale in
        q = q * (float(scale) * math.sqrt(q.shape[-1]))
    q = _constrain_tensor(q, head_spec)   # a2a: seq-shard -> head-shard
    k = _constrain_tensor(k, head_spec)
    v = _constrain_tensor(v, head_spec)
    out = sdpa(q, k, v, is_causal=causal)
    return _constrain_tensor(out, seq_spec)  # a2a back to seq-shard
