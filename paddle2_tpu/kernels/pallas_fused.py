"""Fused elementwise Pallas kernels (reference paddle/phi/kernels/fusion/:
fused_adam_kernel.cu multi-tensor Adam, fused_rope, rms_norm fusions).

On TPU, XLA already fuses elementwise chains aggressively, so each kernel
here ships with a microbench against the XLA-fused baseline
(tests/test_pallas_fused.py asserts parity; .bench notes record measured
wins/losses). The kernels keep ONE HBM pass over every operand with
explicit VMEM tiling — the win over XLA appears when the compiler splits
the chain across fusions (large multi-tensor updates) or when layout
choices force relayouts (rope's interleaved pairs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._platform import interpret_default


# ------------------------------------------------------------ fused adamw

def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, mst_ref, sc_ref,
                  p_out, m_out, v_out, mst_out):
    """One pass: read (p, g, m, v, master), write (p, m, v, master).
    sc_ref (SMEM) carries [lr, beta1, beta2, eps, wd, bc1, bc2]."""
    lr = sc_ref[0]
    b1 = sc_ref[1]
    b2 = sc_ref[2]
    eps = sc_ref[3]
    wd = sc_ref[4]
    bc1 = sc_ref[5]
    bc2 = sc_ref[6]
    g = g_ref[:].astype(jnp.float32)
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    mhat = m / bc1
    vhat = v / bc2
    mw = mst_ref[:]
    mw = mw - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * mw)
    p_out[:] = mw.astype(p_out.dtype)
    m_out[:] = m
    v_out[:] = v
    mst_out[:] = mw


def fused_adamw(param, grad, m, v, master, lr, beta1=0.9, beta2=0.999,
                eps=1e-8, weight_decay=0.01, step=1, block=None,
                interpret=None):
    """Decoupled-weight-decay Adam on FLAT arrays in one kernel pass
    (fused_adam_kernel.cu parity): param bf16/f32, master+moments f32.
    Returns (new_param, new_m, new_v, new_master)."""
    if interpret is None:
        interpret = interpret_default()
    n = param.size
    flat = lambda a: a.reshape(-1)
    p1, g1, m1, v1, w1 = (flat(a) for a in (param, grad, m, v, master))
    blk = block or min(n, 1 << 17)
    # pad to a block multiple (lane-aligned)
    npad = -(-n // blk) * blk
    if npad != n:
        pad = lambda a: jnp.concatenate(
            [a, jnp.zeros(npad - n, a.dtype)])
        p1, g1, m1, v1, w1 = (pad(a) for a in (p1, g1, m1, v1, w1))
    t = jnp.float32(step)
    sc = jnp.stack([jnp.float32(lr), jnp.float32(beta1), jnp.float32(beta2),
                    jnp.float32(eps), jnp.float32(weight_decay),
                    1.0 - jnp.float32(beta1) ** t,
                    1.0 - jnp.float32(beta2) ** t])
    grid = (npad // blk,)
    spec = pl.BlockSpec((blk,), lambda i: (i,))
    po, mo, vo, wo = pl.pallas_call(
        _adamw_kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[spec, spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((npad,), param.dtype),
            jax.ShapeDtypeStruct((npad,), jnp.float32),
            jax.ShapeDtypeStruct((npad,), jnp.float32),
            jax.ShapeDtypeStruct((npad,), jnp.float32),
        ],
        name="fused_adamw",
        interpret=interpret,
    )(p1, g1, m1, v1, w1, sc)
    unflat = lambda a, like: a[:n].reshape(param.shape).astype(like.dtype) \
        if a.dtype != like.dtype else a[:n].reshape(param.shape)
    return (po[:n].reshape(param.shape), mo[:n].reshape(param.shape),
            vo[:n].reshape(param.shape), wo[:n].reshape(param.shape))


# ----------------------------------------------- fused optimizer STEP
# Bitwise twins of the eager Optimizer update rules: unlike
# fused_adamw above (which fuses the decay into one multiply-add —
# fast, but a different rounding order), these kernels replicate the
# EXACT op sequence of optimizer/optimizers.py `_update_one` +
# `_apply_one`, so the fused step is provably a pure layout/fusion
# change — the bench gate asserts params AND moments bitwise equal to
# the eager path on f32 state. One kernel pass reads (p, g, m, v) and
# writes (p, m, v) with input_output_aliases pinning the update in
# place — none of the transpose/copy staging XLA inserts around the
# multi-op eager chain.

def _pad_flat(arrs, blk):
    n = arrs[0].size
    npad = -(-n // blk) * blk
    out = []
    for a in arrs:
        f = a.reshape(-1)
        if npad != n:
            f = jnp.concatenate([f, jnp.zeros(npad - n, f.dtype)])
        out.append(f)
    return out, n, npad


def _adamw_step_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                       p_out, m_out, v_out, *, apply_wd):
    """sc: [lr, b1, 1-b1, b2, 1-b2, eps, wd, bc1, bc2]. The 1-b* and
    bc* values are computed OUTSIDE exactly as the eager expressions
    compute them (python-f64 constants, runtime pow) — recomputing
    1-b1 here in f32 would round differently and break bitwise."""
    lr = sc_ref[0]
    b1 = sc_ref[1]
    om1 = sc_ref[2]
    b2 = sc_ref[3]
    om2 = sc_ref[4]
    eps = sc_ref[5]
    wd = sc_ref[6]
    bc1 = sc_ref[7]
    bc2 = sc_ref[8]
    g = g_ref[:]
    p = p_ref[:]
    m = b1 * m_ref[:] + om1 * g
    v = b2 * v_ref[:] + om2 * jnp.square(g)
    mhat = m / bc1
    vhat = v / bc2
    new_p = p - lr * mhat / (jnp.sqrt(vhat) + eps)
    if apply_wd:
        # decoupled decay against the PRE-update param, as a separate
        # subtract — the eager AdamW order
        new_p = new_p - lr * wd * p
    p_out[:] = new_p
    m_out[:] = m
    v_out[:] = v


def adamw_step_supported(work, grad) -> bool:
    """The bitwise-fused path serves f32 math only: f32 working param
    (plain f32, or the multi-precision master) and an f32 grad (the
    master path casts explicitly, matching eager). A bf16 grad without
    a master promotes through bf16 intermediates on the eager path —
    that rounding order is not worth replicating in-kernel, so it
    falls back."""
    return (work.dtype == jnp.float32 and grad.dtype == jnp.float32)


def fused_adamw_step(param, grad, m, v, lr, step, beta1=0.9,
                     beta2=0.999, eps=1e-8, weight_decay=0.0,
                     block=None, interpret=None):
    """One-pass eager-order AdamW: returns (new_param, new_m, new_v)
    BITWISE equal to `Adam._update_one` + decoupled decay on f32
    state. `lr`/`step` are traced scalars; betas/eps/wd python floats.
    `weight_decay=0.0` skips the decay subtract entirely (the eager
    `if wd and decay` branch)."""
    if interpret is None:
        interpret = interpret_default()
    t = step.astype(jnp.float32)
    # eager-twin scalar staging: 1-b computed in python f64 (the eager
    # closure constant), bias corrections at runtime from the weak-f32
    # pow — identical HLO to `1 - b1 ** t`
    sc = jnp.stack([
        lr.astype(jnp.float32), jnp.float32(beta1),
        jnp.float32(1 - beta1), jnp.float32(beta2),
        jnp.float32(1 - beta2), jnp.float32(eps),
        jnp.float32(weight_decay),
        (1 - beta1 ** t).astype(jnp.float32),
        (1 - beta2 ** t).astype(jnp.float32)])
    blk = block or min(param.size, 1 << 17)
    flats, n, npad = _pad_flat([param, grad, m, v], blk)
    p1, g1, m1, v1 = flats
    grid = (npad // blk,)
    spec = pl.BlockSpec((blk,), lambda i: (i,))
    po, mo, vo = pl.pallas_call(
        functools.partial(_adamw_step_kernel,
                          apply_wd=bool(weight_decay)),
        grid=grid,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((npad,), jnp.float32)] * 3,
        # layout pinning: update in place — no staging copies
        input_output_aliases={0: 0, 2: 1, 3: 2},
        name="fused_adamw_step",
        interpret=interpret,
    )(p1, g1, m1, v1, sc)
    shape = param.shape
    return (po[:n].reshape(shape), mo[:n].reshape(shape),
            vo[:n].reshape(shape))


def _momentum_step_kernel(p_ref, g_ref, v_ref, sc_ref, p_out, v_out,
                          *, nesterov, apply_wd):
    """sc: [lr, momentum, wd]. Eager-order Momentum (l2 decay folded
    into the grad BEFORE the velocity update, like `_apply_one`)."""
    lr = sc_ref[0]
    mom = sc_ref[1]
    wd = sc_ref[2]
    g = g_ref[:]
    p = p_ref[:]
    if apply_wd:
        g = g + wd * p
    v = mom * v_ref[:] + g
    if nesterov:
        new_p = p - lr * (g + mom * v)
    else:
        new_p = p - lr * v
    p_out[:] = new_p
    v_out[:] = v


def fused_momentum_step(param, grad, velocity, lr, momentum=0.9,
                        nesterov=False, weight_decay=0.0, block=None,
                        interpret=None):
    """One-pass eager-order (possibly Nesterov) momentum: bitwise
    equal to `Momentum._update_one` (+ the pre-update l2 fold) on f32
    state."""
    if interpret is None:
        interpret = interpret_default()
    sc = jnp.stack([lr.astype(jnp.float32), jnp.float32(momentum),
                    jnp.float32(weight_decay)])
    blk = block or min(param.size, 1 << 17)
    flats, n, npad = _pad_flat([param, grad, velocity], blk)
    p1, g1, v1 = flats
    grid = (npad // blk,)
    spec = pl.BlockSpec((blk,), lambda i: (i,))
    po, vo = pl.pallas_call(
        functools.partial(_momentum_step_kernel,
                          nesterov=bool(nesterov),
                          apply_wd=bool(weight_decay)),
        grid=grid,
        in_specs=[spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((npad,), jnp.float32)] * 2,
        input_output_aliases={0: 0, 2: 1},
        name="fused_momentum",
        interpret=interpret,
    )(p1, g1, v1, sc)
    shape = param.shape
    return po[:n].reshape(shape), vo[:n].reshape(shape)


# ------------------------------------------------------------ fused rmsnorm

def _rmsnorm_fwd_kernel(x_ref, w_ref, o_ref, r_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(ms + eps)
    o_ref[:] = (x * r * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    r_ref[:] = jnp.broadcast_to(r, r_ref.shape)


def _rmsnorm_fwd(x, w, eps, block_rows, interpret):
    R, H = x.shape
    br = min(block_rows, R)
    while R % br:
        br //= 2
    grid = (R // br,)
    o, r = pl.pallas_call(
        functools.partial(_rmsnorm_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((br, H), lambda i: (i, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((br, H), lambda i: (i, 0)),
                   pl.BlockSpec((br, 128), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, H), x.dtype),
                   jax.ShapeDtypeStruct((R, 128), jnp.float32)],
        name="rmsnorm_fwd",
        interpret=interpret,
    )(x, w.reshape(1, H))
    return o, r[:, 0]


def _rmsnorm_bwd_kernel(x_ref, w_ref, r_ref, do_ref, dx_ref, dwp_ref):
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    r = r_ref[:][:, :1]
    do = do_ref[:].astype(jnp.float32)
    xhat = x * r
    dy = do * w
    # d rms: dx = r * (dy - xhat * mean(dy * xhat))
    mean_term = jnp.mean(dy * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (r * (dy - xhat * mean_term)).astype(dx_ref.dtype)
    # per-block dw partial, broadcast over an 8-row sublane tile
    dwp_ref[:] = jnp.broadcast_to(
        jnp.sum(do * xhat, axis=0, keepdims=True), dwp_ref.shape)


def _rmsnorm_bwd(x, w, r, do, block_rows, interpret):
    R, H = x.shape
    br = min(block_rows, R)
    while R % br:
        br //= 2
    grid = (R // br,)
    r2 = jnp.broadcast_to(r[:, None], (R, 128))
    dx, dw_part = pl.pallas_call(
        _rmsnorm_bwd_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, H), lambda i: (i, 0)),
                  pl.BlockSpec((1, H), lambda i: (0, 0)),
                  pl.BlockSpec((br, 128), lambda i: (i, 0)),
                  pl.BlockSpec((br, H), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, H), lambda i: (i, 0)),
                   pl.BlockSpec((8, H), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, H), x.dtype),
                   jax.ShapeDtypeStruct((R // br * 8, H), jnp.float32)],
        name="rmsnorm_bwd",
        interpret=interpret,
    )(x, w.reshape(1, H), r2, do)
    return dx, dw_part[::8].sum(axis=0).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm(x, w, eps, block_rows, interpret):
    o, _ = _rmsnorm_fwd(x, w, eps, block_rows, interpret)
    return o


def _rmsnorm_vjp_fwd(x, w, eps, block_rows, interpret):
    o, r = _rmsnorm_fwd(x, w, eps, block_rows, interpret)
    return o, (x, w, r)


def _rmsnorm_vjp_bwd(eps, block_rows, interpret, res, g):
    x, w, r = res
    dx, dw = _rmsnorm_bwd(x, w, r, g, block_rows, interpret)
    return dx, dw


_rmsnorm.defvjp(_rmsnorm_vjp_fwd, _rmsnorm_vjp_bwd)

_JIT_CACHE: dict = {}


def fused_rms_norm(x, weight, epsilon=1e-6, block_rows=512, interpret=None):
    """RMSNorm over the last dim in one pallas pass (fwd + custom bwd);
    any leading shape. Differentiable. A block holds ``block_rows`` rows
    of up to 2048 lanes; wider rows take fewer of them (the block and
    its f32 temporaries share the scoped VMEM)."""
    if interpret is None:
        interpret = interpret_default()
    shape = x.shape
    H = shape[-1]
    while block_rows > 8 and block_rows * H > 512 * 2048:
        block_rows //= 2
    key = ("rmsnorm", float(epsilon), int(block_rows), bool(interpret))
    fn = _JIT_CACHE.get(key)
    if fn is None:
        def rmsnorm(x2, w):
            return _rmsnorm(x2, w, float(epsilon), int(block_rows),
                            bool(interpret))
        fn = jax.jit(rmsnorm)
        _JIT_CACHE[key] = fn
    return fn(x.reshape(-1, H), weight).reshape(shape)


# --------------------------------------------------------------- fused rope

def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)          # (rows, H, D)
    cos = cos_ref[:].astype(jnp.float32)[:, None, :]   # (rows, 1, D)
    sin = sin_ref[:].astype(jnp.float32)[:, None, :]
    D = x.shape[-1]
    x1 = x[..., : D // 2]
    x2 = x[..., D // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    o_ref[:] = (x * cos + rot * sin).astype(o_ref.dtype)


def fused_rope(x, cos, sin, block_rows=256, interpret=None):
    """Rotary embedding (half-split convention) in one pass over
    [B, S, H, D] (the reference fused_rope layout, fused_rope kernel).
    cos/sin: [S, D] or pre-gathered [B*S, D] (position_ids path). The
    per-(b,s) angle rows broadcast across heads INSIDE the kernel, so the
    HBM traffic for angles is H-fold smaller than the activations.
    Differentiable (linear op; jax transposes the pallas call via its
    jvp/transpose of the underlying computation is not available — use
    the custom vjp below)."""
    if interpret is None:
        interpret = interpret_default()
    B, S, H, D = x.shape
    rows = B * S
    x2 = x.reshape(rows, H, D)
    if cos.shape[0] != rows:
        cos = jnp.tile(cos.reshape(-1, D), (B, 1))
        sin = jnp.tile(sin.reshape(-1, D), (B, 1))
    br = min(block_rows, rows)
    while rows % br:
        br //= 2
    key = ("rope", rows, H, D, str(x.dtype), int(br), bool(interpret))
    fn = _JIT_CACHE.get(key)
    if fn is None:
        xspec = pl.BlockSpec((br, H, D), lambda i: (i, 0, 0))
        cspec = pl.BlockSpec((br, D), lambda i: (i, 0))

        def call(a, c, s):
            return pl.pallas_call(
                _rope_kernel,
                grid=(rows // br,),
                in_specs=[xspec, cspec, cspec],
                out_specs=xspec,
                out_shape=jax.ShapeDtypeStruct((rows, H, D), a.dtype),
                name="rope",
                interpret=interpret,
            )(a, c, s)

        @jax.custom_vjp
        def roped(a, c, s):
            return call(a, c, s)

        def fwd(a, c, s):
            return call(a, c, s), (c, s)

        def bwd(res, g):
            c, s = res
            # transpose of the rotation: rotate by -theta (cos, -sin)
            return call(g, c, -s), None, None

        roped.defvjp(fwd, bwd)
        fn = jax.jit(roped)
        _JIT_CACHE[key] = fn
    return fn(x2, cos, sin).reshape(B, S, H, D)
