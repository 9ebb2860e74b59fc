"""Fused training step: forward + backward + optimizer in ONE executable.

The TPU-native answer to the reference's fused-optimizer + program-cache
stack (paddle/phi/kernels/fusion/fused_adam_kernel.cu multi-tensor update;
paddle/fluid/framework/new_executor/ program caching;
python/paddle/jit/dy2static/partial_program.py:146 forward/backward program
pair). Instead of three executables per step (forward-with-residuals,
vjp-apply, optimizer) the whole training step — loss, gradients, fused
optimizer update — is traced into a single XLA program with parameter and
optimizer-state buffers DONATED, so XLA updates weights and Adam moments in
place (no ~3x-model-size HBM copy per step) and schedules backward and
update together.

Usage::

    step = paddle.jit.train_step(train_fn, optimizer)   # train_fn -> loss
    for batch in loader:
        loss = step(ids, labels)      # one device dispatch, updated params

`train_fn` must return a scalar loss Tensor (or a tuple whose FIRST element
is the scalar loss). Gradient clipping, weight decay, multi-precision
master weights, and LR schedulers all flow through the optimizer's fused
update as in eager `opt.step()`, with ONE semantic difference: params the
loss does not reach get an all-zeros gradient here (value_and_grad), so
weight decay and moment updates still apply to them — the eager path skips
params whose `.grad is None` entirely. Exclude such params from the
optimizer if they must stay untouched.

Unlike the eager path (which only donates optimizer states), this API also
donates the parameter buffers themselves: do not hold `detach()`/view
aliases of parameter arrays across steps while using it.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from ..framework import random as fr
from ..framework.tensor import Tensor
from ..observability import metrics as _metrics
from ..profiler import (build as _build_span, launch as _launch,
                        launched as _launched, span as _span)
from .functional import (_collect_state, _guard_key, _rebound_call,
                         _split_tensors, _trace_lock)

__all__ = ["train_step", "TrainStepProgram"]

# the HLO module of the fused step ("jit_" + ``step_fn.__name__``): the
# key of its launch ordinals (``profiler.launch``)
STEP_MODULE = "jit_p2t_train_step"


class TrainStepProgram:
    """Guarded cache of compiled fused-train-step executables.

    Optimizer wrappers fuse too (round-3 verdict lifted the restriction):
    - ZeRO ``ShardedOptimizer`` — its whole policy is buffer placement;
      states (and params at stage 3) are placed once after creation and
      the executable's ``out_shardings`` pin them there, so the donated
      single-program path IS the sharded step (GSPMD inserts the gathers
      and reduce-scatters the placements imply).
    - gradient-accumulation ``_ShardOptimizer`` — grads accumulate into a
      donated f32 buffer for k-1 calls (params/states pass through), and
      the k-th call folds the average into the fused update. Two compiled
      variants (accumulate / apply) share the cache entry.
    """

    def __init__(self, fn: Callable, optimizer, layers: Sequence = (),
                 instrument: bool = False):
        self.fn = fn
        self.optimizer = optimizer
        # instrument=True fuses the reliability plane INTO the donated
        # executable: the program additionally returns ONE packed
        # uint32[4] auxiliary output (non-finite count + SDC
        # fingerprint triple over the gradients the update consumed,
        # numerics.packed_step_sentinel) stashed on `self.last_aux` —
        # never read here, so the clean path pays zero extra host
        # syncs; the ReliableTrainStep wrapper decides when (and
        # whether) to pay the single packed readback
        self._instrument = bool(instrument)
        # optional GradScaler (set by the reliability wrapper): the
        # program scales the loss and unscales the grads IN-PROGRAM
        # (scale rides in as a runtime scalar — no recompile when it
        # moves) and makes the fused update conditional on the packed
        # found_inf lane, so an overflow step is skipped inside the
        # executable exactly like eager GradScaler.step would
        self._scaler = None
        self.last_aux = None
        # compile/MTTR accounting (instrumented path): wall time of the
        # most recent build+first-execution of a NEW cache entry, and
        # whether the persistent XLA cache served it (None = no fresh
        # build happened on the last call / no cache dir configured)
        self.last_build_s: Optional[float] = None
        self.last_build_cache_hit: Optional[bool] = None
        # bench hook: when set, a fresh build also runs XLA
        # cost_analysis on the lowered program (deterministic op
        # accounting — no wall clock) into last_cost_flops, and stashes
        # the entry + abstract (donation-safe) call args so the
        # observability cost model can re-lower it later
        self.collect_cost = False
        self.last_cost_flops: Optional[float] = None
        self.last_cost: Optional[Dict[str, float]] = None
        self.last_entry = None
        self.last_abstract_args = None
        # pure-function fault hook threaded through the builder (the
        # chaos drill's seam into the jitted step): a callable polled
        # once per dispatch returning None or a hashable spec for
        # chaos.apply_compiled_grad_fault. Per-PROGRAM, so an
        # in-process multi-replica drill can corrupt one replica while
        # the env-gated FLAGS_chaos path serves real gangs
        self.grad_fault_hook: Optional[Callable] = None
        # unwrap the wrapper chain down to the plain Optimizer that owns
        # update math and state storage
        self._accum_k = 1
        self._accum_avg = True
        self._zero = None
        inner = optimizer
        from ..optimizer.optimizer import Optimizer
        while not isinstance(inner, Optimizer):
            kind = type(inner).__name__
            if kind == "_ShardOptimizer":
                self._accum_k = max(1, int(inner._k))
                self._accum_avg = bool(getattr(inner, "_avg", True))
            elif kind == "ShardedOptimizer":
                self._zero = inner
            else:
                raise TypeError(
                    f"jit.train_step cannot fuse optimizer wrapper "
                    f"{kind}; supported: plain Optimizer, "
                    "dist.shard_optimizer (gradient accumulation), "
                    "sharding.ShardedOptimizer (ZeRO)")
            inner = inner._inner
        self.inner_optimizer = inner
        self.layers = list(layers)
        self._compiled: Dict[Any, Any] = {}
        self._micro_calls = 0
        self._accum_buffers: Optional[list] = None
        self._zero_placed = False

    @property
    def program_cache_size(self):
        return len(self._compiled)

    def __call__(self, *args, **kwargs) -> Tensor:
        with _trace_lock, _span("train.step") as step_span:
            return self._call(args, kwargs, step_span)

    # -- internals -------------------------------------------------------
    def _call(self, args, kwargs, step_span):
        # host spans (profiler.span; PERF.md lists them): train.prepare
        # -> train.dispatch (holding `build` on a new program) ->
        # train.rebind, all inside the caller's train.step
        with _span("train.prepare"):
            (entry, built_now, call_args, opt, opt_params, buffers,
             args_t, has_scaler) = self._prepare(args, kwargs)
        step_span.set_metadata(built=int(built_now))

        pl = _metrics._ACTIVE
        # `launch`: the ordinal of the execution enqueued here, which
        # joins this span to the device's module event of that name
        with _span("train.dispatch", program=STEP_MODULE,
                   launch=_launched(STEP_MODULE)):
            if pl is not None:
                pl.phase_enter("compute")
            try:
                if built_now:
                    out = self._first_call(entry, call_args)
                else:
                    out = entry(*call_args)
                _launch(STEP_MODULE)
            finally:
                if pl is not None:
                    pl.phase_exit()

        with _span("train.rebind"):
            if self._instrument:
                (loss, aux, new_params, new_states, post_buffers,
                 new_accum) = out
                self.last_aux = aux
            else:
                loss, new_params, new_states, post_buffers, new_accum = out
            for p, a in zip(opt_params, new_params):
                p._replace_data(a)
            for p, s in zip(opt_params, new_states):
                opt._states[id(p)] = s
            for b, a in zip(buffers, post_buffers):
                b._replace_data(a)
            if self._accum_k > 1:
                self._accum_buffers = list(new_accum)
            if pl is not None:
                self._note_step_metrics(pl, args_t, has_scaler)
        return Tensor(loss, stop_gradient=True)

    def _prepare(self, args, kwargs):
        """Everything the host does before the dispatch: gather state,
        split the arguments, key the program cache (building the entry
        on a miss) and make the step's scalars."""
        opt = self.inner_optimizer
        all_params, buffers = _collect_state(self.layers)
        opt_params = [p for p in opt._parameter_list()
                      if p is not None and p.trainable]
        opt_ids = {id(p) for p in opt_params}
        # layer params the optimizer does not own (frozen) ride along as
        # non-differentiated state, like buffers
        frozen = [p for p in all_params if id(p) not in opt_ids]
        for p in opt_params:
            opt._ensure_state(p)
        if self._zero is not None and not self._zero_placed:
            # ZeRO is placement: shard the freshly-created states (and
            # stage-3 params) once; out_shardings keep them there
            self._zero._shard_states()
            self._zero._place_params_and_grads()
            self._zero_placed = True
        states = [opt._states[id(p)] for p in opt_params]

        template, args_t = _split_tensors(args, kwargs)
        # mesh-placed params + single-device args cannot share a jit
        # computation: promote stragglers to mesh-replicated (writes back)
        from ..ops.dispatch import _harmonize_placements
        _harmonize_placements(list(opt_params) + list(frozen)
                              + list(buffers) + list(args_t))
        arg_arrays = [t._data for t in args_t]

        need_clip = tuple(bool(getattr(p, "need_clip", True))
                          for p in opt_params)
        decay_flags = tuple(not getattr(p, "no_weight_decay", False)
                            for p in opt_params)
        from ..flags import flag_value
        donate = bool(flag_value("donate_optimizer_buffers"))

        k = self._accum_k
        self._micro_calls += 1
        apply_update = k == 1 or (self._micro_calls % k == 0)
        if k > 1 and self._accum_buffers is None:
            self._accum_buffers = [
                jnp.zeros(p._data.shape, jnp.float32) for p in opt_params]
            if self._zero is not None:
                # accumulated grads follow the ZeRO GRAD placement: at
                # stage >= 2 grads are sharded even though params are
                # replicated — a param-placed bank would hold a full
                # f32 grad copy per device
                from ..distributed.sharding import _place, _shard_spec
                axis = self._zero._axis
                if self._zero._level >= 2:
                    self._accum_buffers = [
                        _place(a, _shard_spec(a, axis))
                        for a in self._accum_buffers]
                else:
                    self._accum_buffers = [
                        jax.device_put(a, p._data.sharding)
                        if hasattr(p._data, "sharding") else a
                        for a, p in zip(self._accum_buffers, opt_params)]
        accum = self._accum_buffers if k > 1 else []

        # instrumented extras decided PER DISPATCH: a firing chaos drill
        # compiles a one-off variant (the spec keys the cache); the
        # clean path sees a single module-attribute check
        fault = None
        has_scaler = False
        if self._instrument:
            from ..distributed.fault_tolerance import chaos as _chaos
            has_scaler = (self._scaler is not None
                          and self._scaler.is_enable())
            if self.grad_fault_hook is not None:
                fault = self.grad_fault_hook()
            if fault is None:
                fault = _chaos.compiled_grad_fault(amp=has_scaler)
            if has_scaler and k > 1:
                raise NotImplementedError(
                    "jit.train_step: GradScaler inside an instrumented "
                    "gradient-accumulation step is not supported — "
                    "run AMP without dist.shard_optimizer accumulation")

        # ZeRO-3 prefetch is a schedule shape baked into the trace —
        # toggling it must key a distinct cache entry
        prefetch = (self._zero is not None and self._zero._level >= 3
                    and getattr(self._zero, "_prefetch", False))
        prefetch_depth = (getattr(self._zero, "_prefetch_depth", 1)
                          if prefetch else 0)
        # searched remat policies are resolved BEFORE the key is
        # computed (layers expose the _prepare_remat protocol — the
        # GPT trunk runs the cost-model search against this call's
        # batch shape) and the resolved plan keys the cache: two
        # models differing only in searched policy trace different
        # programs
        remat_tokens = tuple(
            l._prepare_remat(arg_arrays)
            if hasattr(l, "_prepare_remat")
            else getattr(l, "_remat_token", None)
            for l in self.layers)
        key = _guard_key(template, arg_arrays, self.layers) + (
            len(opt_params), need_clip, decay_flags, donate, k,
            apply_update, self._accum_avg, self._instrument,
            has_scaler, fault, prefetch, prefetch_depth, remat_tokens,
            opt._use_fused_step())
        entry = self._compiled.get(key)
        built_now = entry is None
        if built_now:
            entry = self._build(template, opt_params, frozen, buffers,
                                need_clip, decay_flags, donate,
                                apply_update, states, accum,
                                has_scaler, fault)
            self._compiled[key] = entry

        if apply_update:
            opt._step_count += 1
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        step_no = jnp.asarray(max(1, opt._step_count), jnp.int32)
        rng_key = fr.next_key()

        call_args = (
            [p._data for p in opt_params],
            states,
            [p._data for p in frozen],
            [b._data for b in buffers],
            arg_arrays, rng_key, lr, step_no, accum)
        if self._instrument:
            scale = jnp.asarray(
                self._scaler.get_loss_scaling() if has_scaler else 1.0,
                jnp.float32)
            call_args = call_args + (scale,)

        self.last_build_s = None
        self.last_build_cache_hit = None
        return (entry, built_now, call_args, opt, opt_params, buffers,
                args_t, has_scaler)

    def _note_step_metrics(self, pl, args_t, has_scaler: bool) -> None:
        """Close this dispatch's step window: tokens/samples inferred
        from the first batch argument (exactly-2-D int16/32/64 ids ->
        B*S tokens; uint8 image batches and >2-D int features must not
        masquerade as token counts, and int8 is EXCLUDED outright —
        2-D int8 first args are quantized payloads, e.g. a serving
        engine's int8 KV blocks, never plausible token ids; serving
        reports its token counts explicitly via step_end(tokens=...)),
        loss scale when AMP is fused, program-cache gauge. Reads
        NOTHING off the device — host-known values only."""
        tokens = samples = None
        if args_t:
            shp = tuple(args_t[0].shape)
            if shp:
                samples = int(shp[0])
            if (len(shp) == 2
                    and str(args_t[0].dtype) in
                    ("int16", "int32", "int64")):
                tokens = int(shp[0]) * int(shp[1])
        scale = (self._scaler.get_loss_scaling()
                 if has_scaler and self._scaler is not None else None)
        pl.set_gauge("train_step_program_cache_size",
                     len(self._compiled))
        pl.step_end(tokens=tokens, samples=samples, loss_scale=scale)

    def _first_call(self, entry, call_args):
        """First use of a FRESHLY BUILT entry, inside the ``build``
        span: the call traces, lowers and compiles (or reads the
        persistent cache), and the span's record in
        ``profiler.builds()`` says how long each took, from JAX's own
        monitoring events. With ``collect_cost`` the program is lowered
        once more for ``cost_analysis`` afterwards (``build.cost``). The
        instrumented path also BLOCKS on the result: compile + first
        step is pure MTTR on every respawn, kept in ``last_build_s``
        with ``last_build_cache_hit`` (None = the cache was not
        consulted — "unknown" is never reported as a hit). Steady state
        never re-enters."""
        import time as _time
        _metrics.inc("train_step_compiles_total")
        sig = "x".join(str(d) for d in call_args[4][0].shape) \
            if call_args[4] else ""
        if self.collect_cost:
            from ..observability import cost_model as _cm
            self.last_entry = entry
            # taken BEFORE the call: it donates its arguments
            self.last_abstract_args = _cm.abstractify(call_args)
        with _build_span("train_step", sig) as b:
            t0 = _time.perf_counter()
            out = entry(*call_args)
            if self._instrument:
                jax.block_until_ready(out)
                self.last_build_s = _time.perf_counter() - t0
            if self.collect_cost:
                # AFTER the call, as model_runner.decode has it: the
                # call's tracing and lowering are booked as trace_s /
                # lower_s, and cost_s is the second lowering alone
                with b.cost():
                    self.last_cost = _cm.program_cost(
                        entry, self.last_abstract_args)
                    self.last_cost_flops = (
                        None if not self.last_cost
                        else self.last_cost.get("flops"))
        if self._instrument:
            self.last_build_cache_hit = b.record["cache_hit"]
        return out

    def _build(self, template, opt_params, frozen, buffers, need_clip,
               decay_flags, donate, apply_update, states, accum,
               has_scaler=False, fault=None):
        fn = self.fn
        k, avg = self._accum_k, self._accum_avg
        instrument = self._instrument
        update = self.inner_optimizer._build_update(need_clip, decay_flags)
        state_tensors = list(opt_params) + list(frozen) + list(buffers)

        # ZeRO-3: the forward re-gather of sharded params is made
        # EXPLICIT — one all-gather (replicated constraint) per module
        # group — on BOTH schedules, so the model math always sees the
        # same gathered values and eager-vs-prefetch stays bitwise by
        # construction (GSPMD left to regather implicitly may partition
        # the consuming matmuls differently — a rounding-order change).
        # prefetch=False: gathers unchained (gather-all, scheduler
        # free). prefetch=True: barrier-chained so gather i waits only
        # on gather i-depth (never on compute) — the latency-hiding
        # scheduler overlaps it with the previous layer's math while
        # replicated live memory stays bounded to ~depth groups.
        prefetch_groups = None
        prefetch_depth = 0
        if self._zero is not None and self._zero._level >= 3:
            from ..distributed.sharding import layer_param_groups
            prefetch_groups = layer_param_groups(self.layers, opt_params)
            if getattr(self._zero, "_prefetch", False):
                prefetch_depth = self._zero._prefetch_depth

        def run_model(param_arrays, frozen_arrays, buffer_arrays,
                      arg_arrays, rng_key):
            if prefetch_groups is not None:
                from ..distributed.sharding import prefetch_gather
                param_arrays = prefetch_gather(
                    list(param_arrays), prefetch_groups, prefetch_depth)
            out, post_buffers = _rebound_call(
                fn, state_tensors,
                list(param_arrays) + list(frozen_arrays)
                + list(buffer_arrays),
                template, arg_arrays, rng_key, buffers)
            loss = out[0] if isinstance(out, (tuple, list)) else out
            if isinstance(loss, Tensor):
                loss = loss._data
            if loss.ndim != 0 and loss.size == 1:
                loss = loss.reshape(())
            if loss.ndim != 0:
                raise ValueError(
                    "jit.train_step: train_fn must return a scalar loss "
                    f"(got shape {loss.shape})")
            return loss, post_buffers

        def pure_step(param_arrays, states, frozen_arrays, buffer_arrays,
                      arg_arrays, rng_key, lr, step_no, accum):
            def loss_of(p_arrays):
                loss, post_b = run_model(p_arrays, frozen_arrays,
                                         buffer_arrays, arg_arrays, rng_key)
                return loss.astype(jnp.float32), post_b
            (loss, post_buffers), grads = jax.value_and_grad(
                loss_of, has_aux=True)(list(param_arrays))
            if k > 1:
                totals = [a + g.astype(jnp.float32)
                          for a, g in zip(accum, grads)]
                if not apply_update:
                    # accumulation-only microstep: params/states ride
                    # through untouched, grads bank into the f32 buffer
                    return (loss, list(param_arrays), states, post_buffers,
                            totals)
                scale = 1.0 / k if avg else 1.0
                grads = [(t * scale).astype(g.dtype)
                         for t, g in zip(totals, grads)]
                new_accum = [jnp.zeros_like(a) for a in accum]
            else:
                new_accum = []
            new_params, new_states = update(list(param_arrays), grads,
                                            states, lr, step_no)
            return loss, new_params, new_states, post_buffers, new_accum

        def pure_step_instrumented(param_arrays, states, frozen_arrays,
                                   buffer_arrays, arg_arrays, rng_key,
                                   lr, step_no, accum, loss_scale):
            """The reliability plane fused into the donated executable:
            AMP loss scale/unscale, injected chaos faults, the
            non-finite sentinel and the SDC fingerprint all become part
            of THIS program — one dispatch, one packed uint32[4] aux
            output, no extra host round-trips on the clean path."""
            from ..distributed.fault_tolerance import chaos as _chaos
            from ..distributed.fault_tolerance import numerics as _num

            def loss_of(p_arrays):
                loss, post_b = run_model(p_arrays, frozen_arrays,
                                         buffer_arrays, arg_arrays,
                                         rng_key)
                l32 = loss.astype(jnp.float32)
                scaled = l32 * loss_scale if has_scaler else l32
                return scaled, (l32, post_b)
            (_, (loss, post_buffers)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(list(param_arrays))
            if has_scaler:
                # fused unscale-and-check: the eager GradScaler's
                # unscale_ multiply, traced into the step (the sentinel
                # below then sees the UNSCALED f32 values, matching
                # numerics.grads_nonfinite_flag(optimizer, inv))
                inv = 1.0 / loss_scale
                grads = [(g.astype(jnp.float32) * inv).astype(g.dtype)
                         for g in grads]
            # chaos parity: flip_bits:grads / poison_grads land INSIDE
            # the jitted step (pure transform, baked per firing call)
            grads = _chaos.apply_compiled_grad_fault(fault, grads)

            def sentinel(gs):
                aux = _num.packed_step_sentinel(gs)
                return (jnp.zeros((4,), jnp.uint32) if aux is None
                        else aux)

            def guard_loss(l, aux):
                # fold the grad sentinel into the loss so the wrapper's
                # DEFERRED loss check (free — the loss materializes for
                # logging anyway) sees grad corruption with zero extra
                # readbacks. With a scaler the flag means "skip", not
                # "retry": the update below absorbs it instead.
                if has_scaler:
                    return l
                return jnp.where(aux[0] > 0, jnp.full_like(l, jnp.nan),
                                 l)
            if k > 1:
                totals = [a + g.astype(jnp.float32)
                          for a, g in zip(accum, grads)]
                if not apply_update:
                    # microstep: fingerprint THIS microstep's grads (the
                    # contribution being banked — what replicas must
                    # agree on) and bank them untouched
                    aux = sentinel(grads)
                    return (guard_loss(loss, aux), aux,
                            list(param_arrays), states, post_buffers,
                            totals)
                scale = 1.0 / k if avg else 1.0
                grads = [(t * scale).astype(g.dtype)
                         for t, g in zip(totals, grads)]
                new_accum = [jnp.zeros_like(a) for a in accum]
            else:
                new_accum = []
            # sentinel + fingerprint over the grads the update CONSUMES
            # (post-unscale, post-fold) — the same capture point as
            # SDCGuard's wrapped optimizer.step on the eager path
            aux = sentinel(grads)
            new_params, new_states = update(list(param_arrays), grads,
                                            states, lr, step_no)
            if has_scaler:
                # in-program skip: non-finite grads keep params/states
                # bit-identical (eager GradScaler.step's "don't step"),
                # decided on device — the host learns from the packed
                # flag, deferred, without a second readback
                found = aux[0] > 0

                def keep(new, old):
                    return jnp.where(found, old, new)
                new_params = [keep(n, o) for n, o
                              in zip(new_params, list(param_arrays))]
                new_states = jax.tree_util.tree_map(keep, new_states,
                                                    states)
            return (guard_loss(loss, aux), aux, new_params, new_states,
                    post_buffers, new_accum)

        out_shardings = None
        if self._zero is not None:
            # pin the ZeRO placements across steps: without this, GSPMD
            # may choose to materialize updated states replicated and the
            # memory savings silently evaporate after step 1
            sh = lambda a: getattr(a, "sharding", None)
            out_shardings = (
                None,
                [sh(p._data) for p in opt_params],
                jax.tree_util.tree_map(sh, states),
                None,
                [sh(a) for a in accum] if accum else [],
            )
            if instrument:
                out_shardings = (out_shardings[0], None) + out_shardings[1:]
        step_fn = pure_step_instrumented if instrument else pure_step
        # the HLO module's name in a device trace: jit_p2t_train_step
        step_fn.__name__ = "p2t_train_step"
        return jax.jit(step_fn,
                       donate_argnums=(0, 1, 3, 8) if donate else (),
                       out_shardings=out_shardings)


def train_step(fn: Callable, optimizer, layers: Optional[Sequence] = None,
               reliability: Any = None):
    """Compile `fn` (returning a scalar loss) plus `optimizer`'s update
    into one donated XLA executable. Layers are discovered from `fn`'s
    closure/globals like `to_static` when not given explicitly.

    Accepts a plain Optimizer, a ZeRO ``ShardedOptimizer``, or a
    gradient-accumulation ``dist.shard_optimizer`` wrapper (in any
    nesting) — wrapper policies are folded INTO the donated executable:
    ZeRO as buffer placements + pinned out_shardings, accumulation as a
    donated f32 grad bank with a k-th-call fused update. Unknown wrapper
    types raise.

    ``reliability`` folds the fault-tolerance plane INTO the compiled
    step and returns a
    :class:`~paddle2_tpu.distributed.fault_tolerance.compiled_step.ReliableTrainStep`
    instead: the non-finite sentinel and the SDC gradient fingerprint
    are computed inside the donated executable (one packed aux output,
    zero extra host readbacks on the clean path), snapshots are
    scheduled donation-safely before each submit, and ReliableStep's
    rewind+replay, flight-recorder events, buddy replication, and
    quarantine self-eviction all apply to the compiled program. Pass
    ``True`` for defaults, a
    :class:`~paddle2_tpu.distributed.fault_tolerance.compiled_step.ReliabilityConfig`,
    or a dict of its kwargs."""
    if layers is None:
        from .api import _discover_layers
        layers = _discover_layers(fn)
    if reliability is None or reliability is False:
        return TrainStepProgram(fn, optimizer, layers)
    from ..distributed.fault_tolerance.compiled_step import (
        ReliabilityConfig, ReliableTrainStep)
    if reliability is True:
        config = ReliabilityConfig()
    elif isinstance(reliability, dict):
        config = ReliabilityConfig(**reliability)
    elif isinstance(reliability, ReliabilityConfig):
        config = reliability
    else:
        raise TypeError(
            "reliability must be True, a ReliabilityConfig, or a dict "
            f"of its kwargs; got {type(reliability).__name__}")
    program = TrainStepProgram(fn, optimizer, layers, instrument=True)
    return ReliableTrainStep(program, config)
