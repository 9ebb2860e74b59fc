"""Low-precision Pallas matmul paths for the big GPT projections
(fused QKV, out_proj, MLP up/down, lm_head).

Two dtype families:

* **int8 weight-only** — weights ride as int8 with per-OUT-CHANNEL f32
  absmax scales (the ``quantization`` module's channel-wise observer
  convention; :func:`channel_absmax` here is the shared primitive the
  observers reduce with). The kernel streams the int8 weight tile into
  VMEM (HALF the HBM bytes of bf16 — decode and lm_head matmuls are
  weight-bandwidth-bound), dequantizes in-register, and runs the MXU in
  the activation dtype. Error is ANALYTICALLY bounded:
  per-element weight error <= s_j / (2*qmax) (round-to-nearest half
  step), so ``|y_ref - y_q|[i, j] <= ||x_i||_1 * s_j / (2*qmax)`` —
  :func:`weight_quant_error_bound` computes it and the bench gate
  asserts it holds AND is non-vacuous (a mis-scaled payload violates
  it).
* **int4 weight-only** (ISSUE 14 satellite, ROADMAP item 4) — the same
  machinery at ``quant_bits=4``: :func:`pack_int4` stores two weights
  per byte (QUARTER the bf16 HBM bytes — decode is weight-bandwidth
  bound, so this is the aggressive end of the same trade), and
  :func:`weight_quant_error_bound` generalizes unchanged — the bench
  gates the 4-bit bound both HOLDS (f64 reference) and is NON-VACUOUS
  (a 2-bit payload must violate it, and it must beat the trivial
  ``|y|`` bound).
* **int8 x int8** — both operands int8, int32 MXU accumulation (2x the
  bf16 rate on v5e), dequantized at the epilogue: the
  ``QuantedInferenceLinear`` full-int8 path as a Pallas kernel.

Dispatch (``interpret=None``): the Pallas kernel on a TPU for aligned
shapes, the numerically-equivalent XLA lowering elsewhere (CPU/CI,
ragged shapes) — both produce the same dequantized product, so the
analytic bound gates BOTH lowerings. An explicit ``interpret=False``
is the compiled kernel or an error, never the XLA dot.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._platform import on_tpu

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 512


def _pallas_interpret(aligned: bool, interpret: Optional[bool],
                      what: str) -> Optional[bool]:
    """Resolve a wrapper's ``interpret=`` into the flag its
    ``pallas_call`` takes, or ``None`` for the XLA lowering.
    ``interpret=False`` means the COMPILED kernel or an error — never
    the XLA dot; ``None`` picks the compiled kernel on a TPU for
    aligned shapes and the XLA lowering elsewhere; ``True`` interprets
    aligned shapes."""
    if interpret is False and not aligned:
        raise ValueError(
            f"{what}: interpret=False asks for the compiled Pallas "
            "kernel, which needs block-aligned operands")
    if not aligned:
        return None
    if interpret is None:
        return False if on_tpu() else None
    return bool(interpret)


# ------------------------------------------------------------ primitives
def channel_absmax(arr, axis: int):
    """Per-channel absmax of ``arr`` along ``axis`` (reduced over every
    OTHER axis) — the one reduction the quantization observers, the
    weight-only packers, and the training-time fake-quant head all
    share, so their scales agree bitwise."""
    axis = axis % arr.ndim
    red = tuple(i for i in range(arr.ndim) if i != axis)
    return jnp.max(jnp.abs(arr), axis=red).astype(jnp.float32)


def quantize_channelwise(w, quant_bits: int = 8, axis: int = 1
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(w_int8, scale): symmetric per-channel absmax quantization of a
    weight along ``axis`` (out-channel for ``[in, out]`` Linear
    weights)."""
    qmax = float(2 ** (quant_bits - 1) - 1)
    scale = jnp.maximum(channel_absmax(w, axis), 1e-8)
    shape = [1] * w.ndim
    shape[axis % w.ndim] = -1
    s = scale.reshape(shape)
    w_q = jnp.clip(jnp.round(w.astype(jnp.float32) / s * qmax),
                   -qmax, qmax).astype(jnp.int8)
    return w_q, scale


def weight_quant_error_bound(x, w_scale, quant_bits: int = 8):
    """Analytic per-(row, out-channel) bound on the weight-only
    quantization error of ``x @ W``: each dequantized weight element is
    within ``s_j / (2*qmax)`` of the original (round-to-nearest), so
    the product error is bounded by the l1 norm of the activation row
    times that half-step. Returns ``[..., out]`` f32."""
    qmax = float(2 ** (quant_bits - 1) - 1)
    l1 = jnp.sum(jnp.abs(x.astype(jnp.float32)), axis=-1,
                 keepdims=True)
    return l1 * (w_scale.astype(jnp.float32) / (2.0 * qmax))


# ---------------------------------------------------------- int4 storage
def pack_int4(w_q):
    """Pack a ``[K, N]`` int4-valued int8 array (values in [-7, 7])
    into ``[K, N/2]`` uint8 nibbles (even column in the low nibble) —
    QUARTER the bf16 weight bytes in HBM. N must be even. The compute
    paths consume the unpacked int8 form (the MXU has no int4 lanes on
    this generation; the win is bandwidth, which is what decode and
    lm_head matmuls are bound by)."""
    w_q = jnp.asarray(w_q, jnp.int8)
    if w_q.shape[-1] % 2:
        raise ValueError("pack_int4 needs an even out-channel count")
    lo = (w_q[..., 0::2] & 0xF).astype(jnp.uint8)
    hi = (w_q[..., 1::2] & 0xF).astype(jnp.uint8)
    return lo | (hi << 4)


def unpack_int4(packed, n: int):
    """Inverse of :func:`pack_int4`: ``[K, N/2]`` uint8 -> ``[K, N]``
    sign-extended int8 (values in [-8, 7])."""
    packed = jnp.asarray(packed, jnp.uint8)
    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)

    def sext(v):
        return jnp.where(v >= 8, v - 16, v).astype(jnp.int8)

    out = jnp.stack([sext(lo), sext(hi)], axis=-1)
    return out.reshape(packed.shape[:-1] + (2 * packed.shape[-1],))[..., :n]


def int4_weight_only_matmul(x, w_packed, w_scale, bias=None,
                            block_m: int = DEFAULT_BLOCK_M,
                            block_n: int = DEFAULT_BLOCK_N,
                            block_k: int = DEFAULT_BLOCK_K,
                            interpret: Optional[bool] = None):
    """int4 weight-only ``x @ dequant(W)``: unpack the nibble payload
    in-register and run the shared weight-only path at
    ``quant_bits=4`` (the PR 10 error-bound machinery generalizes —
    ``weight_quant_error_bound(x, s, quant_bits=4)`` bounds THIS
    product, and the bench gates it non-vacuous). ``w_packed``:
    ``[K, N/2]`` uint8 from :func:`pack_int4`; ``w_scale``: ``[N]``."""
    n = 2 * w_packed.shape[-1]
    w_q = unpack_int4(w_packed, n)
    return int8_weight_only_matmul(
        x, w_q, w_scale, bias=bias, quant_bits=4, block_m=block_m,
        block_n=block_n, block_k=block_k, interpret=interpret)


# ------------------------------------------------- int8 weight-only kernel
def _wo_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, qmax, k_steps):
    """Grid (M/bm, N/bn, K/bk): f32 VMEM accumulator, int8 weight tile
    dequantized in-register, per-out-channel scale applied once at the
    epilogue (the matmul is linear in the weight, so scaling the
    accumulated column equals scaling every tile)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...] * (s_ref[:] / qmax)).astype(
            o_ref.dtype)


def _wo_pallas(x2, w_int8, scale, qmax, out_dtype, bm, bn, bk,
               interpret):
    M, K = x2.shape
    N = w_int8.shape[1]
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_wo_kernel, qmax=qmax, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="int8_weight_only_matmul",
    )(x2, w_int8, scale.reshape(1, N))


def wo_supported(m: int, k: int, n: int, bm: int = DEFAULT_BLOCK_M,
                 bn: int = DEFAULT_BLOCK_N,
                 bk: int = DEFAULT_BLOCK_K) -> bool:
    """Pallas path needs block-aligned operands (the XLA fallback
    serves ragged shapes with identical numerics)."""
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    return m % bm == 0 and k % bk == 0 and n % bn == 0


def int8_weight_only_matmul(x, w_int8, w_scale, bias=None,
                            quant_bits: int = 8,
                            block_m: int = DEFAULT_BLOCK_M,
                            block_n: int = DEFAULT_BLOCK_N,
                            block_k: int = DEFAULT_BLOCK_K,
                            interpret: Optional[bool] = None):
    """``x @ dequant(w_int8)`` with per-out-channel scales: the Pallas
    weight-only kernel on TPU for aligned shapes, the equivalent XLA
    dequant-matmul elsewhere. ``x``: ``[..., K]`` float; ``w_int8``:
    ``[K, N]``; ``w_scale``: ``[N]``."""
    qmax = float(2 ** (quant_bits - 1) - 1)
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w_int8.shape[1]
    m = 1
    for d in lead:
        m *= int(d)
    interpret = _pallas_interpret(
        wo_supported(m, K, N, block_m, block_n, block_k), interpret,
        "int8_weight_only_matmul")
    if interpret is not None:
        x2 = x.reshape(m, K)
        # with a bias the kernel keeps its epilogue in f32 so the bias
        # folds in BEFORE the single output cast — the same rounding
        # order as the XLA fallback below (casting first would make
        # the two lowerings diverge at the last ulp for bf16)
        out_dtype = jnp.float32 if bias is not None else x.dtype
        out = _wo_pallas(x2, w_int8, jnp.asarray(w_scale, jnp.float32),
                         qmax, out_dtype, min(block_m, m),
                         min(block_n, N), min(block_k, K), interpret)
        out = out.reshape(lead + (N,))
        if bias is not None:
            out = (out + bias).astype(x.dtype)
        return out
    w = w_int8.astype(jnp.float32) * (
        jnp.asarray(w_scale, jnp.float32) / qmax)
    out = jax.lax.dot_general(
        x.astype(jnp.float32), w,
        (((x.ndim - 1,), (0,)), ((), ())))
    if bias is not None:
        # bias folds in at f32 BEFORE the output cast — the exact
        # order of the pre-kernel WeightOnlyLinear lowering
        out = out + bias
    return out.astype(x.dtype)


# --------------------------------------------------- int8 x int8 kernel
def _i8i8_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps):
    """int8 x int8 -> int32 MXU accumulation (v5e runs this at 2x the
    bf16 rate); dequant happens OUTSIDE (caller owns both scales)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
    # precision pinned: the package-wide "highest" default is an f32
    # notion the chip's compiler refuses on integer operands
    acc_ref[...] += jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...]


def int8_matmul(x_int8, w_int8,
                block_m: int = DEFAULT_BLOCK_M,
                block_n: int = DEFAULT_BLOCK_N,
                block_k: int = DEFAULT_BLOCK_K,
                interpret: Optional[bool] = None):
    """Full-int8 ``[M, K] @ [K, N] -> int32``: the Pallas twin of
    ``QuantedInferenceLinear``'s dot (TPU, aligned), XLA
    ``dot_general`` with int32 accumulation elsewhere."""
    M, K = x_int8.shape
    N = w_int8.shape[1]
    interpret = _pallas_interpret(
        wo_supported(M, K, N, block_m, block_n, block_k), interpret,
        "int8_matmul")
    if interpret is None:
        return jax.lax.dot_general(
            x_int8, w_int8, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_i8i8_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="int8_matmul",
    )(x_int8, w_int8)


# ------------------------------------------------------- collective matmul
# Tensor-parallel projections spend their ICI time on the tp all-gather
# that feeds (or follows) the matmul. A collective matmul decomposes the
# gather into CHUNK-granular transfers interleaved with chunk-granular
# MXU work, so each transfer rides under a dot it is independent of —
# the latency-hiding scheduler (flags.apply_multichip_xla_env) then
# hides the ICI time inside the MXU time instead of serializing
# gather -> matmul. Two standard forms, both PURE SCHEDULE SHAPES
# (bitwise identical to the unfused gather-then-matmul, gated on the
# virtual mesh):
#
# * :func:`allgather_matmul` — input form (sequence-parallel Megatron):
#   ``all_gather(x, tp) @ w`` as a ring; each step runs the chunk it
#   holds through the dot while ``ppermute`` brings the next chunk in.
# * :func:`matmul_allgather` — EPILOGUE form (column-parallel output
#   re-replication): ``all_gather(x @ w_shard, tp)`` with the gather
#   issued per OUTPUT TILE from the epilogue, so tile t's wire time
#   overlaps tile t+1's dot.
#
# The per-chunk dot is pluggable (``matmul_fn``): the int8/int4
# weight-only Pallas kernels above slot straight in, composing the
# PR 10 quantized paths with the collective schedule. Cost accounting
# goes through :func:`collective_matmul_traffic`: the gather's wire
# bytes enter the model marked OVERLAPPABLE, which is exactly what the
# cost model's exposed-vs-hidden overlap split prices.


def _resolve_axis_size(axis_name, axis_size: Optional[int]) -> int:
    if axis_size is not None:
        return int(axis_size)
    from ..distributed import mesh as _mesh  # lazy: avoid import cycle
    return _mesh.traced_axis_size(axis_name)


def allgather_matmul(x_shard, w, axis_name: str,
                     axis_size: Optional[int] = None,
                     matmul_fn=None):
    """Ring collective matmul of the INPUT all-gather (shard_map
    context): computes ``all_gather(x_shard, axis) @ w`` — ``x_shard``
    is this rank's ``[rows/tp, K]`` slice — as ``tp`` chunk dots, each
    independent of the in-flight ``ppermute`` bringing the next chunk,
    so the gather's ICI time hides inside MXU time. Bitwise identical
    to the unfused path: every output row block is produced by the
    same-shaped dot on the same values, and the ring only moves data.
    ``matmul_fn(chunk, w) -> [rows/tp, N]`` swaps the per-chunk dot
    (e.g. a weight-only Pallas kernel); default is a plain ``@``."""
    n = _resolve_axis_size(axis_name, axis_size)
    dot = matmul_fn if matmul_fn is not None else (lambda c, ww: c @ ww)
    if n == 1:
        return dot(x_shard, w)
    r = jax.lax.axis_index(axis_name)
    rows = x_shard.shape[0]
    first = dot(x_shard, w)
    out = jnp.zeros((n * rows,) + first.shape[1:], first.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(out, first, r * rows, 0)
    # descending ring: after k hops this rank holds rank (r + k) % n's
    # original shard
    perm = [(i, (i - 1) % n) for i in range(n)]
    cur = x_shard
    for step in range(1, n):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        src = (r + step) % n
        y = dot(cur, w)
        out = jax.lax.dynamic_update_slice_in_dim(out, y, src * rows, 0)
    return out


def matmul_allgather(x, w_shard, axis_name: str,
                     axis_size: Optional[int] = None,
                     tiles: int = 1, matmul_fn=None):
    """Column-parallel matmul with the tp all-gather of the OUTPUT
    fused into the epilogue: computes
    ``all_gather(x @ w_shard, axis)`` (rank-major column blocks,
    ``[..., tp * N_shard]``) but issues the gather per output TILE —
    ``tiles`` column tiles per rank, each gathered as soon as its dot
    finishes, so tile t's wire time overlaps tile t+1's MXU work.
    Bitwise identical to the unfused gather: column tiles of a dot are
    independent K-reductions and the gather only places blocks. (Keep
    tiles MODERATE — a degenerate 1-wide column tile can change the
    XLA CPU dot's reduction grouping by ~1 ulp, the same effect PR 9
    pinned for gemm row counts; the acceptance tests run 1/2/4 tiles.)
    ``matmul_fn(x, w_tile) -> [..., tile]`` swaps the per-tile dot."""
    n = _resolve_axis_size(axis_name, axis_size)
    dot = matmul_fn if matmul_fn is not None else (lambda xx, ww: xx @ ww)
    nl = w_shard.shape[-1]
    t = max(1, min(int(tiles), nl))
    if nl % t:
        raise ValueError(
            f"matmul_allgather: {t} tiles must divide the local "
            f"out-channel count {nl}")
    bn = nl // t
    y0 = dot(x, w_shard[..., :bn])
    out = jnp.zeros(y0.shape[:-1] + (n * nl,), y0.dtype)
    for ti in range(t):
        y_t = y0 if ti == 0 else dot(
            x, w_shard[..., ti * bn:(ti + 1) * bn])
        if n == 1:
            g = y_t[None]
        else:
            # leading rank dim [n, ..., bn]: rank r's tile block
            g = jax.lax.all_gather(y_t, axis_name)
        for rank in range(n):
            out = jax.lax.dynamic_update_slice_in_dim(
                out, g[rank], rank * nl + ti * bn, out.ndim - 1)
    return out


def collective_matmul_traffic(payload_bytes: float, tp: int,
                              axes, traffic=None):
    """Price one collective matmul's gather into a
    :class:`~paddle2_tpu.observability.cost_model.CollectiveTraffic`
    (created if not given): the all-gather's wire bytes enter the model
    marked OVERLAPPABLE — hidden under the step's MXU time up to the
    compute budget by the cost model's exposed-vs-hidden overlap split,
    which is the whole point of fusing the gather into the matmul. The
    unfused comparison prices the same bytes non-overlappable."""
    from ..observability.cost_model import CollectiveTraffic
    t = traffic if traffic is not None else CollectiveTraffic()
    t.add("all_gather", float(payload_bytes), axes=tuple(axes),
          group_size=int(tp), overlappable=True)
    return t


__all__ = ["channel_absmax", "quantize_channelwise",
           "weight_quant_error_bound", "int8_weight_only_matmul",
           "int4_weight_only_matmul", "pack_int4", "unpack_int4",
           "int8_matmul", "wo_supported",
           "allgather_matmul", "matmul_allgather",
           "collective_matmul_traffic",
           "DEFAULT_BLOCK_M", "DEFAULT_BLOCK_N", "DEFAULT_BLOCK_K"]
