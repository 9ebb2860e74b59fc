"""Compiled SPMD pipeline: the whole microbatch pipeline as ONE XLA
program over the 'pp' mesh axis.

The eager executor in pipeline_parallel.py emulates per-rank schedules in
Python; this module is the TPU-native execution path for HOMOGENEOUS
stages (e.g. a transformer block stack): stage parameters live stacked on
a leading axis sharded over 'pp' (each device holds its stage), and a
single `shard_map`-ped scan runs the classic GPipe wavefront — every tick
each device applies its stage and `lax.ppermute`s the activation to the
next device over ICI. Forward AND backward are differentiated/compiled by
XLA as one program, so there is no per-microbatch Python dispatch at all.

Parity target: the reference's per-rank NCCL p2p pipeline
(fleet/meta_parallel/pipeline_parallel.py) — re-expressed as a collective
program the way the scaling-book prescribes for TPU pipelining.

Compiled schedules: GPipe wavefront (pipeline_spmd), hand-scheduled 1F1B
(pipeline_spmd_1f1b, closed-form ticks, S+1 activation bound, hybrid
TP+PP via param_specs, dp_axis data parallelism), interleaved
virtual-pipeline (pipeline_spmd_vpp). Zero-bubble (ZB-H1) ships on the
EAGER executor only (pipeline_parallel.py schedule="ZB"): its point —
filling bubbles with deferred weight-grad W ops — is a scheduling
freedom XLA's latency-hiding scheduler already holds inside the
compiled program. That claim is pinned structurally (r5):
test_compiled_1f1b_cotangent_send_independent_of_weight_grads walks the
1F1B backward-branch jaxpr and asserts the upstream cotangent dx (what
the ppermute sends) neither produces nor consumes the weight-grad
accumulation — the compiler is free to issue the send first and slot dW
into the bubble, which is ZB-H1's whole schedule. Wall-clock bubble
A/B is not measurable in this environment (one host core timeshares
the 8 virtual devices, and the single real chip cannot run pp>1);
revisit with a hand-scheduled compiled ZB only if a multi-chip profile
ever shows dx sends serialized behind dW.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import mesh as mesh_mod

__all__ = ["pipeline_spmd", "pipeline_spmd_1f1b", "pipeline_spmd_vpp"]

def _vary_over(v, axes):
    """Mark every leaf of ``v`` device-varying over those of ``axes``
    it is not ALREADY varying over (dp-sharded inputs arrive
    dp-varying; ``pcast`` rejects redundant axes)."""
    def leaf(x):
        missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
        return jax.lax.pcast(x, missing, to="varying") if missing else x
    return jax.tree_util.tree_map(leaf, v)


def _axis_size(name):
    return mesh_mod.traced_axis_size(name)


def _claim_mean(g, axis):
    """Finalize a grad whose cross-``axis`` reduction the
    varying-transpose auto-psum already performed: the values are equal
    across the axis and pmean merely CLAIMS the invariance for the
    out_specs."""
    return jax.lax.pmean(g, axis)


def _local_body(params, x_micro, *, stage_fn, n_stages, n_micro, axis):
    """Per-device program. params: this device's stage params (leading
    stage axis already sliced to size 1 by shard_map). x_micro:
    [M, B, ...] microbatches (stage 0's input; other stages ignore it).
    Returns [M, B, ...] outputs (valid on the LAST stage's shard)."""
    s = jax.lax.axis_index(axis)
    S, M = n_stages, n_micro
    T = M + S - 1
    p_local = jax.tree_util.tree_map(lambda a: a[0], params)
    zero = jnp.zeros_like(x_micro[0])
    outs0 = jnp.zeros_like(x_micro)
    perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t):
        act, outs = carry
        m = t - s                       # microbatch index at this stage
        valid = (m >= 0) & (m < M)
        m_c = jnp.clip(m, 0, M - 1)
        inp = jnp.where(s == 0, x_micro[jnp.clip(t, 0, M - 1)], act)
        y = stage_fn(p_local, inp)
        y = jnp.where(valid, y, zero)
        outs = jnp.where(valid & (s == S - 1),
                         outs.at[m_c].set(y), outs)
        act_next = jax.lax.ppermute(y, axis, perm)
        return (act_next, outs), None

    # the carry becomes device-varying (ppermute / stage writes): mark the
    # replicated initial values as varying so scan's carry types match
    def _varying(v):
        return _vary_over(v, (axis,))

    (act, outs), _ = jax.lax.scan(tick, (_varying(zero), _varying(outs0)),
                                  jnp.arange(T))
    # only the LAST stage wrote outputs; everyone else holds zeros — the
    # psum replicates the result across the ring (one all-reduce of the
    # final activations, the cross-stage "gather" of the reference's p2p)
    return jax.lax.psum(outs, axis)


def pipeline_spmd(stage_fn: Callable, stacked_params, x_micro,
                  mesh_axis: str = "pp"):
    """Run `stage_fn(stage_params, x) -> y` as a compiled GPipe pipeline.

    stacked_params: pytree whose leaves have a leading stage axis of size
    S (the 'pp' mesh degree) — sharded over `mesh_axis` inside the
    program, so each device computes with ONLY its stage's weights.
    x_micro: [M, B, ...] microbatches. Returns [M, B, ...] outputs of the
    last stage. Differentiable end-to-end (scan + ppermute transpose).
    """
    mesh = mesh_mod.get_mesh()
    S = int(mesh.shape[mesh_axis])
    M = int(x_micro.shape[0])
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != S:
            raise ValueError(
                f"stacked param leading axis {leaf.shape[0]} != pipeline "
                f"degree {S} (mesh axis {mesh_axis!r}); each device must "
                "hold exactly one stage")

    # compiled-program cache (repo pattern: collective.py _kernel_cache) —
    # repeat calls with the same geometry reuse the jitted executable
    treedef = jax.tree_util.tree_structure(stacked_params)
    avals = tuple((tuple(l.shape), str(l.dtype))
                  for l in jax.tree_util.tree_leaves(stacked_params))
    key = (id(mesh), mesh_axis, stage_fn, treedef, avals,
           tuple(x_micro.shape), str(x_micro.dtype))
    fn = _PIPE_CACHE.get(key)
    if fn is None:
        param_specs = jax.tree_util.tree_map(
            lambda a: P(mesh_axis, *([None] * (a.ndim - 1))),
            stacked_params)
        body = partial(_local_body, stage_fn=stage_fn, n_stages=S,
                       n_micro=M, axis=mesh_axis)
        fn = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P()))
        _PIPE_CACHE[key] = fn
    return fn(stacked_params, x_micro)


_PIPE_CACHE: Dict[Tuple, Any] = {}


# ---------------------------------------------------------------------------
# compiled 1F1B: hand-scheduled forward+backward in ONE scan
# ---------------------------------------------------------------------------
#
# Closed-form schedule (derived from the reference's 1F1B rank loop,
# fleet/meta_parallel/pipeline_parallel.py:575, re-indexed as global ticks):
#   warmup  F_m at stage s: tick t = s + m          (m < S - s)
#   steady  F_m at stage s: tick t = 2m + s         (m >= S - s)
#   B_i     at stage s:     tick t = 2S - 1 - s + 2i
# Properties (checked in tests): at most one op per (stage, tick); a
# forward activation ppermuted at its producer's tick arrives EXACTLY at
# the consumer's tick (1-tick stage offset), and likewise for backward
# cotangents — so no in-flight queues are needed; live activations per
# stage never exceed S+1 microbatches (the 1F1B memory bound, vs GPipe's
# M). One exception needs a register: each stage's warmup->steady boundary
# microbatch (m = S - s) arrives at tick S but is consumed at tick 2S - s,
# so it is latched into a one-slot `pend` register at arrival. Backward
# recomputes the stage forward from the saved INPUT (the standard TPU
# recompute-1F1B), so only inputs are buffered.

def _f1b_body(params, shared, x_micro, labels_micro, *, stage_fn, loss_fn,
              n_stages, n_micro, axis, tp_axes=(), grad_extra=None,
              dp_axis=None, grad_bucket_bytes=None):
    # pvary over the pipeline axis PLUS any TP axes the param specs name
    # PLUS the data-parallel axis when batches are dp-sharded: a
    # hybrid-TP stage_fn (psum over 'mp') makes some switch-branch
    # outputs mp-varying, and lax.switch requires identical vma types
    vaxes = (axis,) + tuple(tp_axes) + ((dp_axis,) if dp_axis else ())

    def _vary(v):
        return _vary_over(v, vaxes)

    tp_scale = 1.0
    for a in tp_axes:
        tp_scale = tp_scale / _axis_size(a)
    if dp_axis is not None:
        # params are dp-INVARIANT while data is dp-varying: the vjp
        # auto-inserts a dp-psum into their cotangents (pvary transpose),
        # so seed each dp shard with 1/D to make that psum the dp-MEAN
        # of the per-shard grads — the reference's averaged allreduce
        tp_scale = tp_scale / _axis_size(dp_axis)
    s = jax.lax.axis_index(axis)
    S, M = n_stages, n_micro
    T = 2 * (M + S) - 2           # last op: B_{M-1} at stage 0, t = 2S+2M-3
    p_local = jax.tree_util.tree_map(lambda a: a[0], params)
    zero = jnp.zeros_like(x_micro[0])
    BUF = S + 1

    def apply_stage(x):
        return stage_fn(p_local, shared, x, s)

    perm_fwd = [(i, (i + 1) % S) for i in range(S)]
    perm_bwd = [((i + 1) % S, i) for i in range(S)]

    g0 = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape[1:], jnp.float32), params)

    def tick(carry, t):
        x_buf, grads, act_in, ct_in, losses, pend = carry
        # all switch branches must agree on varying-manual-axes types:
        # zeros emitted by idle/fwd/bwd are explicitly device-varying
        vzero = _vary(zero)
        d = t - s
        # op selection per the closed forms above
        warm_f = (0 <= d) & (d < jnp.minimum(S - s, M)) & (t < S)
        m_steady = (t - s) // 2
        steady_f = ((t >= S) & ((t - s) % 2 == 0)
                    & (m_steady >= S - s) & (m_steady < M))
        i_b = (t + s + 1 - 2 * S) // 2
        is_b = (((t + s) % 2 == 1) & (t >= 2 * S - 1 - s)
                & (i_b >= 0) & (i_b < M))
        m_f = jnp.where(warm_f, jnp.clip(d, 0, M - 1),
                        jnp.clip(m_steady, 0, M - 1))
        is_f = warm_f | steady_f

        def do_fwd(x_buf, grads, losses):
            # the boundary microbatch was latched at tick S (see header)
            src = jnp.where(m_f == S - s, pend, act_in)
            x = jnp.where(s == 0, x_micro[m_f], src)
            y = apply_stage(x)
            x_buf = x_buf.at[m_f % BUF].set(x)
            return x_buf, grads, losses, y, vzero

        def do_bwd(x_buf, grads, losses):
            i_c = jnp.clip(i_b, 0, M - 1)
            x = x_buf[i_c % BUF]
            is_last = s == S - 1

            # one vjp yields BOTH param and input cotangents; the last
            # stage seeds from the loss, others from the arriving ct
            def f(p, x):
                y = stage_fn(p, shared, x, s)
                lo = loss_fn(y, labels_micro[i_c])
                return lo, y

            (lo, _y), vjp = jax.vjp(f, p_local, x)
            # a replicated scalar's cotangent seeded on EVERY TP rank
            # gets psum'd at the first invariant point (pvary transpose
            # = psum), so divide by the TP degree; also promote the vma
            # type to match lo's (hybrid-TP stage_fns make lo vary over
            # more axes than the pipeline axis)
            dlo = jnp.where(is_last, (1.0 / M) * tp_scale,
                            0.0).astype(lo.dtype)
            dlo = dlo + _vary(jnp.zeros((), lo.dtype))
            dy = jnp.where(is_last, jnp.zeros_like(ct_in), ct_in)
            dp, dx = vjp((dlo, dy))
            grads = jax.tree_util.tree_map(
                lambda g, d: g + d.astype(jnp.float32), grads, dp)
            losses = jnp.where(is_last,
                               losses.at[i_c].set(lo.astype(jnp.float32)),
                               losses)
            return x_buf, grads, losses, vzero, dx

        def do_idle(x_buf, grads, losses):
            return x_buf, grads, losses, vzero, vzero

        op = jnp.where(is_f, 1, 0) + jnp.where(is_b, 2, 0)
        x_buf, grads, losses, y_out, dx_out = jax.lax.switch(
            op, [do_idle, do_fwd, do_bwd], x_buf, grads, losses)

        pend = jnp.where(t == S, act_in, pend)
        act_next = jax.lax.ppermute(y_out, axis, perm_fwd)
        ct_next = jax.lax.ppermute(dx_out, axis, perm_bwd)
        return (x_buf, grads, act_next, ct_next, losses, pend), None

    x_buf0 = jnp.zeros((BUF,) + zero.shape, zero.dtype)
    losses0 = jnp.zeros((M,), jnp.float32)
    carry0 = (_vary(x_buf0),
              jax.tree_util.tree_map(_vary, g0),
              _vary(zero), _vary(zero), _vary(losses0),
              _vary(zero))
    (x_buf, grads, _, _, losses, _p), _ = jax.lax.scan(
        tick, carry0, jnp.arange(T))
    # losses live on the last stage, grads on their own stage: reduce the
    # losses across the ring; grads keep per-stage placement
    losses = jax.lax.psum(losses, axis)
    for a in tp_axes:
        # mp ranks computed identical losses (post-psum activations are
        # replicated across mp) — pmean restores the invariant vma type
        losses = jax.lax.pmean(losses, a)
    if grad_extra is not None:
        # grads of TP-replicated leaves (norm gains etc.) are identical
        # across the TP axes their spec does not shard — pmean both
        # claims the invariance and averages any numeric jitter
        def _unvary(g, extra):
            for a in extra:
                g = _claim_mean(g, a)
            return g
        grads = jax.tree_util.tree_map(
            _unvary, grads, grad_extra,
            is_leaf=lambda x: isinstance(x, jnp.ndarray))
    if dp_axis is not None:
        # each dp shard holds the local-mean losses; the global loss is
        # their dp-mean. Grads are already the dp-mean via the scaled
        # seed + auto-psum above — the pmean only claims the (equal-
        # valued) dp invariance for the out_specs.
        losses = jax.lax.pmean(losses, dp_axis)
        if grad_bucket_bytes:
            # fused, size-targeted buckets instead of one collective per
            # param leaf: fewer dispatches, and each bucket is an
            # independent op the latency-hiding scheduler can overlap
            # with the update math of already-reduced buckets. Bitwise
            # identical (pmean of a concatenation == concatenation of
            # pmeans).
            from ..bucket import bucketed_pmean
            fused = bucketed_pmean
            grads = fused(grads, dp_axis, float(grad_bucket_bytes))
        else:
            grads = jax.tree_util.tree_map(
                lambda g: _claim_mean(g, dp_axis), grads)
    grads = jax.tree_util.tree_map(lambda g: g[None], grads)
    return jnp.sum(losses) / M, grads


# ---------------------------------------------------------------------------
# compiled interleaved-VPP: V model chunks per device, virtual-stage ring
# ---------------------------------------------------------------------------
#
# Measured note (r5, virtual mesh, matched per-device work at V=2/S=4/
# M=8): compiled-VPP temp footprint 0.16 MB vs compiled-1F1B 0.18 MB —
# the "V*M chunk inputs vs S+1 in-flight buffers" residual distinction
# is second-order next to the vjp residuals of the stage body itself;
# pick VPP for bubble shape, not memory. (Step-time bubble A/B is not
# measurable here: one host core timeshares all virtual devices.)
#
# Virtual stage vs = v*S + s lives as chunk v on device s (Megatron/the
# reference's PipelineParallelWithInterleave placement,
# meta_parallel/pipeline_parallel.py:1174). Forward runs the wavefront
# F(vs, m) at tick t = vs + m over P = V*S virtual stages: several of a
# device's chunks can be active in the SAME tick (they are independent —
# the compiled program runs them in parallel; the eager executor
# serializes them in Python). Activation routing per tick is one stacked
# ppermute: chunk v's output on device s becomes chunk v's input on
# device s+1, and on the ring wrap (device S-1 -> 0) it becomes chunk
# v+1's input. Backward mirrors the wavefront in reverse, recomputing
# each chunk forward from its SAVED INPUT (recompute-1F1B style), so the
# per-device residual footprint is exactly the V*M chunk inputs — not
# every intermediate of an autodiffed forward. (The eager executor keeps
# the interleaved warmup/steady tick interleave; this compiled schedule
# is F-then-B over virtual stages, which XLA overlaps freely.)

def _vpp_body(params, shared, x_micro, labels_micro, *, stage_fn, loss_fn,
              n_stages, n_chunks, n_micro, axis, dp_axis=None,
              grad_bucket_bytes=None):
    s = jax.lax.axis_index(axis)
    S, V, M = n_stages, n_chunks, n_micro
    P = V * S
    T = M + P - 1
    p_chunks = jax.tree_util.tree_map(lambda a: a[:, 0], params)  # [V,...]
    zero = jnp.zeros_like(x_micro[0])
    perm_fwd = [(i, (i + 1) % S) for i in range(S)]
    perm_bwd = [((i + 1) % S, i) for i in range(S)]
    vaxes = (axis,) + ((dp_axis,) if dp_axis else ())
    # params are dp-INVARIANT while data is dp-varying: the vjp
    # auto-inserts a dp-psum into their cotangents (pvary transpose), so
    # seed each dp shard with 1/D to make that psum the dp-MEAN of the
    # per-shard grads — the same scaled-seed trick as _f1b_body
    seed_scale = 1.0
    if dp_axis is not None:
        seed_scale = seed_scale / _axis_size(dp_axis)

    def _varying(v):
        return _vary_over(v, vaxes)

    def chunk_params(v):
        return jax.tree_util.tree_map(lambda a: a[v], p_chunks)

    # ---- forward wavefront: save chunk inputs --------------------------
    def ftick(carry, t):
        acts, x_save = carry            # acts: [V, B...] per-chunk input
        ys = []
        new_save = x_save
        for v in range(V):
            vs = v * S + s
            m = t - vs
            valid = (m >= 0) & (m < M)
            m_c = jnp.clip(m, 0, M - 1)
            x = jnp.where((v == 0) & (s == 0), x_micro[m_c], acts[v])
            y = stage_fn(chunk_params(v), shared, x, vs)
            y = jnp.where(valid, y, _varying(zero))
            new_save = jnp.where(
                valid, new_save.at[v, m_c].set(x), new_save)
            ys.append(y)
        moved = jax.lax.ppermute(jnp.stack(ys), axis, perm_fwd)
        # ring wrap: what device 0 receives from device S-1 belongs to
        # the NEXT chunk; other devices keep the chunk index
        shifted = jnp.roll(moved, 1, axis=0)
        acts_next = jnp.where(s == 0, shifted, moved)
        return (acts_next, new_save), None

    x_save0 = jnp.zeros((V, M) + zero.shape, zero.dtype)
    acts0 = jnp.zeros((V,) + zero.shape, zero.dtype)
    (acts, x_save), _ = jax.lax.scan(
        ftick, (_varying(acts0), _varying(x_save0)), jnp.arange(T))

    # ---- backward wavefront: recompute-from-input vjp per chunk --------
    def btick(carry, u):
        cts, grads, losses = carry      # cts: [V, B...] out-cotangents
        dxs = []
        for v in range(V):
            vs = v * S + s
            i = u - (P - 1 - vs)
            valid = (i >= 0) & (i < M)
            i_c = jnp.clip(i, 0, M - 1)
            x = x_save[v, i_c]
            is_last = vs == P - 1

            def f(p, x):
                y = stage_fn(p, shared, x, vs)
                lo = loss_fn(y, labels_micro[i_c])
                return lo, y

            (lo, _y), vjp = jax.vjp(f, chunk_params(v), x)
            dlo = jnp.where(is_last, (1.0 / M) * seed_scale,
                            0.0).astype(lo.dtype)
            dlo = dlo + _varying(jnp.zeros((), lo.dtype))
            dy = jnp.where(is_last, jnp.zeros_like(cts[v]), cts[v])
            dp, dx = vjp((dlo, dy))
            gsel = jnp.float32(valid)
            grads = jax.tree_util.tree_map(
                lambda g, d, _v=v: g.at[_v].add(
                    d.astype(jnp.float32) * gsel), grads, dp)
            losses = jnp.where(valid & is_last,
                               losses.at[i_c].set(lo.astype(jnp.float32)),
                               losses)
            dxs.append(jnp.where(valid, dx, _varying(zero)))
        moved = jax.lax.ppermute(jnp.stack(dxs), axis, perm_bwd)
        # reverse ring wrap: what device S-1 receives from device 0
        # belongs to the PREVIOUS chunk
        shifted = jnp.roll(moved, -1, axis=0)
        cts_next = jnp.where(s == S - 1, shifted, moved)
        return (cts_next, grads, losses), None

    grads0 = jax.tree_util.tree_map(
        lambda a: jnp.zeros((V,) + a.shape[1:], jnp.float32), p_chunks)
    losses0 = jnp.zeros((M,), jnp.float32)
    (cts, grads, losses), _ = jax.lax.scan(
        btick, (_varying(acts0), _varying(grads0), _varying(losses0)),
        jnp.arange(T))
    losses = jax.lax.psum(losses, axis)
    if dp_axis is not None:
        # each dp shard holds the local-mean losses of ITS batch shard:
        # this pmean is a REAL reduction to the global mean. Grads are
        # already the dp-mean via the scaled seed + auto-psum above, so
        # their reduction below only claims the (equal-valued) dp
        # invariance for the out_specs — exactly like _f1b_body
        losses = jax.lax.pmean(losses, dp_axis)
        if grad_bucket_bytes:
            from ..bucket import bucketed_pmean
            fused = bucketed_pmean
            grads = fused(grads, dp_axis, float(grad_bucket_bytes))
        else:
            grads = jax.tree_util.tree_map(
                lambda g: _claim_mean(g, dp_axis), grads)
    grads = jax.tree_util.tree_map(lambda g: g[:, None], grads)
    return jnp.sum(losses) / M, grads


def pipeline_spmd_vpp(stage_fn: Callable, stacked_params, x_micro,
                      labels_micro, loss_fn: Callable, n_chunks: int,
                      shared_params=None, mesh_axis: str = "pp",
                      dp_axis: str = None, grad_bucket_bytes=None):
    """Compiled interleaved virtual-pipeline (reference
    PipelineParallelWithInterleave, meta_parallel/pipeline_parallel.py:
    1174, as a single SPMD program). Each device holds ``n_chunks`` model
    chunks; virtual stage v*S + s is chunk v on device s.

    stacked_params: pytree with leaves [V, S, ...] (chunk-major, stage
    axis second — sharded over the mesh's pp axis).
    stage_fn(chunk_params, shared_params, x, virtual_stage_idx) -> y.
    Returns (mean loss, grads with the same [V, S, ...] leading axes).
    Backward recomputes each chunk from its saved input, so per-device
    residuals are the V*M chunk inputs only.

    ``dp_axis`` / ``grad_bucket_bytes`` compose data parallelism the
    same way ``pipeline_spmd_1f1b`` does: microbatches shard their
    batch dim over ``dp_axis``, returned loss/grads are dp-means, and
    the in-program dp grad reduction optionally coalesces into the
    deterministic ``distributed.bucket`` plan.
    """
    mesh = mesh_mod.get_mesh()
    S = int(mesh.shape[mesh_axis])
    M = int(x_micro.shape[0])
    V = int(n_chunks)
    if shared_params is None:
        shared_params = ()
    if dp_axis is not None:
        if dp_axis not in mesh.shape or dp_axis == mesh_axis:
            raise ValueError(
                f"dp_axis {dp_axis!r} must name a mesh axis distinct "
                f"from {mesh_axis!r}; mesh has {tuple(mesh.shape)}")
        D = int(mesh.shape[dp_axis])
        if x_micro.shape[1] % D != 0:
            raise ValueError(
                f"microbatch size {x_micro.shape[1]} not divisible by "
                f"{dp_axis!r} degree {D}")
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != V or leaf.shape[1] != S:
            raise ValueError(
                f"stacked param leading axes {leaf.shape[:2]} != "
                f"(V={V}, S={S})")

    treedef = jax.tree_util.tree_structure((stacked_params, shared_params))
    avals = tuple((tuple(l.shape), str(l.dtype)) for l in
                  jax.tree_util.tree_leaves((stacked_params,
                                             shared_params)))
    key = ("vpp", id(mesh), mesh_axis, stage_fn, loss_fn, V, treedef,
           avals, tuple(x_micro.shape), str(x_micro.dtype), dp_axis,
           None if not grad_bucket_bytes else float(grad_bucket_bytes))
    fn = _PIPE_CACHE.get(key)
    if fn is None:
        param_specs = jax.tree_util.tree_map(
            lambda a: P(None, mesh_axis, *([None] * (a.ndim - 2))),
            stacked_params)
        shared_specs = jax.tree_util.tree_map(lambda a: P(), shared_params)
        body = partial(_vpp_body, stage_fn=stage_fn, loss_fn=loss_fn,
                       n_stages=S, n_chunks=V, n_micro=M, axis=mesh_axis,
                       dp_axis=dp_axis,
                       grad_bucket_bytes=grad_bucket_bytes)
        data_spec = P() if dp_axis is None else P(None, dp_axis)
        fn = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(param_specs, shared_specs, data_spec, data_spec),
            out_specs=(P(), param_specs)))
        _PIPE_CACHE[key] = fn
    loss, grads = fn(stacked_params, shared_params, x_micro, labels_micro)
    return loss, grads


def pipeline_spmd_1f1b(stage_fn: Callable, stacked_params, x_micro,
                       labels_micro, loss_fn: Callable, shared_params=None,
                       mesh_axis: str = "pp", param_specs=None,
                       dp_axis: str = None, grad_bucket_bytes=None,
                       virtual_stages: int = 1):
    """Compiled 1F1B: mean loss + stacked parameter grads in ONE program.

    stage_fn(stage_params, shared_params, x, stage_idx) -> y. Stage
    heterogeneity (embedding first / LM head last) is expressed inside
    stage_fn by branching on `stage_idx` and reading `shared_params`
    (replicated on every stage — e.g. tied embedding tables).
    loss_fn(y_last, label_micro) -> scalar per-microbatch loss; returns
    (mean loss over microbatches, stacked f32 grads with the 1F1B
    activation bound of S+1 in-flight microbatches instead of GPipe's M).

    ``param_specs`` (optional pytree of PartitionSpec, default
    ``P(mesh_axis, None, ...)``) lets hybrid TP+PP shard further weight
    dims over other mesh axes (e.g. ``P('pp', None, 'mp')`` for a
    column-parallel weight); stage_fn then works on the LOCAL TP shard
    and reduces with ``jax.lax.psum(..., 'mp')`` — the mp_layers
    semantics inside the compiled pipeline. Each spec's first axis must
    be ``mesh_axis``.

    ``dp_axis`` composes data parallelism (and therefore ZeRO sharding
    of the optimizer states over that axis — reference
    fleet/base/topology.py: the sharding axis coexists with pipe):
    microbatches shard their batch dim over ``dp_axis``, each dp shard
    pipelines its sub-batch, and the returned loss/grads are dp-means —
    the grad all-reduce over the dp group, fused into the same program.

    ``grad_bucket_bytes`` (with ``dp_axis``) coalesces the per-leaf dp
    grad reduction into deterministic size-targeted fused buckets
    (``distributed.bucket``): fewer collective dispatches, overlappable
    with the update math, bitwise identical to the per-leaf path.

    ``virtual_stages`` (v, the Megatron interleaved-VPP knob) places
    ``v`` model chunks on each pipeline device: stacked_params grow a
    leading VIRTUAL-stage axis of size ``v * S`` (virtual stage
    ``vs = v_chunk * S + s`` is chunk ``v_chunk`` on device ``s``) and
    the schedule interleaves the chunks, shrinking the pipeline bubble
    from ``(p-1)/m`` toward ``(p-1)/(v*m)``
    (``cost_model.pipeline_bubble_fraction``). The interleaving is a
    PURE SCHEDULE SHAPE: at any ``v`` the returned loss/grads are
    bitwise identical to the non-interleaved run of the same
    ``v * S``-virtual-stage model (every virtual stage applies the same
    math and accumulates its microbatch grads in the same order) — the
    bench gate executes exactly that comparison on the virtual mesh.
    ``virtual_stages > 1`` composes with ``dp_axis`` /
    ``grad_bucket_bytes`` but not (yet) with ``param_specs`` TP
    sharding. Grads come back with the ``[v * S, ...]`` leading axis of
    the stacked input.
    """
    v = int(virtual_stages)
    if v < 1:
        raise ValueError(f"virtual_stages must be >= 1, got {v}")
    mesh = mesh_mod.get_mesh()
    S = int(mesh.shape[mesh_axis])
    M = int(x_micro.shape[0])
    if v > 1:
        if param_specs is not None:
            raise NotImplementedError(
                "pipeline_spmd_1f1b: virtual_stages > 1 does not "
                "compose with param_specs TP sharding yet — shard the "
                "stage body manually or run v=1")
        for leaf in jax.tree_util.tree_leaves(stacked_params):
            if leaf.shape[0] != v * S:
                raise ValueError(
                    f"stacked param leading axis {leaf.shape[0]} != "
                    f"virtual_stages * pipeline degree = {v}*{S}="
                    f"{v * S}")
        # chunk-major placement: [v*S, ...] -> [V, S, ...] (virtual
        # stage vs = chunk * S + s, i.e. contiguous runs of S virtual
        # stages form one chunk ring lap)
        chunked = jax.tree_util.tree_map(
            lambda a: a.reshape((v, S) + tuple(a.shape[1:])),
            stacked_params)
        loss, grads = pipeline_spmd_vpp(
            stage_fn, chunked, x_micro, labels_micro, loss_fn,
            n_chunks=v, shared_params=shared_params,
            mesh_axis=mesh_axis, dp_axis=dp_axis,
            grad_bucket_bytes=grad_bucket_bytes)
        grads = jax.tree_util.tree_map(
            lambda g: g.reshape((v * S,) + tuple(g.shape[2:])), grads)
        return loss, grads
    if shared_params is None:
        shared_params = ()
    if dp_axis is not None:
        if dp_axis not in mesh.shape or dp_axis == mesh_axis:
            raise ValueError(
                f"dp_axis {dp_axis!r} must name a mesh axis distinct "
                f"from {mesh_axis!r}; mesh has {tuple(mesh.shape)}")
        D = int(mesh.shape[dp_axis])
        if x_micro.shape[1] % D != 0:
            raise ValueError(
                f"microbatch size {x_micro.shape[1]} not divisible by "
                f"{dp_axis!r} degree {D}")
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != S:
            raise ValueError(
                f"stacked param leading axis {leaf.shape[0]} != pipeline "
                f"degree {S}")
    if param_specs is not None:
        for spec in jax.tree_util.tree_leaves(
                param_specs, is_leaf=lambda x: isinstance(x, P)):
            if tuple(spec)[:1] != (mesh_axis,):
                raise ValueError(
                    f"param_specs leading axis must be {mesh_axis!r}, "
                    f"got {spec}")

    treedef = jax.tree_util.tree_structure((stacked_params, shared_params))
    avals = tuple((tuple(l.shape), str(l.dtype)) for l in
                  jax.tree_util.tree_leaves((stacked_params, shared_params)))
    spec_key = None if param_specs is None else tuple(
        str(s) for s in jax.tree_util.tree_leaves(
            param_specs, is_leaf=lambda x: isinstance(x, P)))
    key = ("1f1b", id(mesh), mesh_axis, stage_fn, loss_fn, treedef, avals,
           tuple(x_micro.shape), str(x_micro.dtype), spec_key, dp_axis,
           None if not grad_bucket_bytes else float(grad_bucket_bytes))
    fn = _PIPE_CACHE.get(key)
    if fn is None:
        if param_specs is None:
            param_specs = jax.tree_util.tree_map(
                lambda a: P(mesh_axis, *([None] * (a.ndim - 1))),
                stacked_params)
        shared_specs = jax.tree_util.tree_map(lambda a: P(), shared_params)
        def _spec_axes(spec):
            out = []
            for e in tuple(spec):
                out.extend(e if isinstance(e, (tuple, list))
                           else ([] if e is None else [e]))
            return out

        tp_axes = tuple(sorted({a for spec in jax.tree_util.tree_leaves(
            param_specs, is_leaf=lambda x: isinstance(x, P))
            for a in _spec_axes(spec) if a != mesh_axis}))
        grad_extra = jax.tree_util.tree_map(
            lambda spec: tuple(a for a in tp_axes
                               if a not in _spec_axes(spec)),
            param_specs, is_leaf=lambda x: isinstance(x, P))
        body = partial(_f1b_body, stage_fn=stage_fn, loss_fn=loss_fn,
                       n_stages=S, n_micro=M, axis=mesh_axis,
                       tp_axes=tp_axes, grad_extra=grad_extra,
                       dp_axis=dp_axis, grad_bucket_bytes=grad_bucket_bytes)
        data_spec = P() if dp_axis is None else P(None, dp_axis)
        fn = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(param_specs, shared_specs, data_spec, data_spec),
            out_specs=(P(), param_specs)))
        _PIPE_CACHE[key] = fn
    loss, grads = fn(stacked_params, shared_params, x_micro, labels_micro)
    return loss, grads
