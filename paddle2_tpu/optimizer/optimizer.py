"""Optimizer base (python/paddle/optimizer/optimizer.py:127 parity).

Redesigned for XLA: each step() call runs ONE jitted pytree update over all
parameters (params, grads, states are flat lists → a single fused TPU kernel
per optimizer, the equivalent of the reference's fused/multi_tensor adam
kernels) instead of per-parameter kernel launches. The update rule itself is
a pure function `_update_one(param, grad, state, lr)` supplied by subclasses.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import core
from ..framework.tensor import Parameter, Tensor
from .lr import LRScheduler


def _clip_arrays(grad_clip, grads, need_clip_flags):
    """Gradient clipping over the clippable subset — shared by the
    generic and fused update builders (one owner, identical ops)."""
    if grad_clip is None:
        return grads
    clippable = [g for g, c in zip(grads, need_clip_flags) if c]
    clipped = grad_clip.apply_arrays(clippable)
    it = iter(clipped)
    return [next(it) if c else g
            for g, c in zip(grads, need_clip_flags)]


def _in_optimizer_scope(update):
    """``update`` traced under ``jax.named_scope("optimizer")``: clip's
    global norm and the per-parameter update carry that scope in a
    device trace. Metadata only — the arithmetic is untouched."""
    def scoped(params, grads, states, lr, step):
        with jax.named_scope("optimizer"):
            return update(params, grads, states, lr, step)
    return scoped


class Optimizer:
    _hyper: Dict[str, float] = {}

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False, **kwargs):
        if parameters is None:
            raise ValueError(
                "parameters is required in dygraph mode "
                "(pass model.parameters())")
        # param groups: list of Parameter or list of dicts {'params': [...]}
        self._param_groups: List[Dict[str, Any]] = []
        params_list = list(parameters)
        if params_list and isinstance(params_list[0], dict):
            for g in params_list:
                g = dict(g)
                g["params"] = list(g["params"])
                self._param_groups.append(g)
        else:
            self._param_groups.append({"params": params_list})
        self._lr = learning_rate
        self._weight_decay = self._wd_value(weight_decay)
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._states: Dict[int, Any] = {}
        self._step_count = 0
        self._jit_cache: Dict[Any, Any] = {}

    @staticmethod
    def _wd_value(weight_decay):
        """Returns (kind, coeff): kind is 'l2' or 'l1'."""
        if weight_decay is None:
            return ("l2", 0.0)
        if isinstance(weight_decay, (int, float)):
            return ("l2", float(weight_decay))
        coeff = float(getattr(weight_decay, "_coeff",
                              getattr(weight_decay, "coeff", 0.0)))
        kind = "l1" if type(weight_decay).__name__ == "L1Decay" else "l2"
        return (kind, coeff)

    # -- lr --------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return self._lr()
        return float(self._lr)

    def set_lr(self, value: float):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = float(value)

    def set_lr_scheduler(self, scheduler: LRScheduler):
        self._lr = scheduler

    @property
    def _learning_rate(self):
        return self._lr

    # -- state -----------------------------------------------------------
    def _init_state(self, p: Parameter):
        """Return the initial state pytree for one parameter (subclass)."""
        return ()

    def _ensure_state(self, p: Parameter):
        key = id(p)
        if key not in self._states:
            state = self._init_state(p)
            if self._multi_precision and p._data.dtype in (jnp.bfloat16,
                                                           jnp.float16):
                state = {"master": p._data.astype(jnp.float32),
                         "inner": state}
            self._states[key] = state
        return self._states[key]

    # -- the pure update -------------------------------------------------
    def _update_one(self, param, grad, state, lr, step):
        raise NotImplementedError

    def _decoupled_wd(self) -> bool:
        return False  # AdamW overrides

    def _use_fused_step(self) -> bool:
        """Opt-in Pallas fused-step routing: the explicit ``fused=``
        ctor kwarg wins, else FLAGS_fused_optimizer_step."""
        explicit = getattr(self, "_fused_step", None)
        if explicit is not None:
            return bool(explicit)
        from ..flags import flag_value
        return bool(flag_value("fused_optimizer_step"))

    def _fused_update_builder(self, need_clip_flags, decay_flags):
        """Subclasses with a Pallas one-pass kernel (AdamW, Momentum)
        return a drop-in `update` here; None falls back to the generic
        per-op chain. Any fused update MUST be bitwise equal to the
        generic path — it is a layout/fusion change, never a numerics
        change (bench --single-chip-speed gates this)."""
        return None

    def _fused_paramwise_builder(self, need_clip_flags, decay_flags,
                                 kernel):
        """ONE owner for the fused-update scaffolding every subclass
        shares: clipping, multi-precision master unwrap/re-wrap, the
        explicit f32 grad cast, and the per-tensor fallback to
        `_apply_one`. ``kernel(work, g, inner, lr, step, wd_eff)``
        returns ``(new_work, new_inner)`` or None when this tensor is
        unsupported (then the generic chain serves it, still bitwise
        by construction). l1 decay falls back wholesale — the kernels
        implement the l2 fold only."""
        wd_kind, wd = self._weight_decay
        if wd and wd_kind != "l2":
            return None
        grad_clip = self._grad_clip
        multi_prec = self._multi_precision
        apply_one = self._apply_one

        def update(params, grads, states, lr, step):
            grads = _clip_arrays(grad_clip, grads, need_clip_flags)
            new_params, new_states = [], []
            for p, g, s, decay in zip(params, grads, states,
                                      decay_flags):
                master = None
                inner = s
                if multi_prec and isinstance(s, dict) and "master" in s:
                    master, inner = s["master"], s["inner"]
                work = master if master is not None else p
                g_eff = g.astype(jnp.float32) if master is not None \
                    else g
                res = kernel(work, g_eff, inner, lr, step,
                             wd if (wd and decay) else 0.0)
                if res is None:
                    np_, ns_ = apply_one(p, g, s, lr, step, decay)
                    new_params.append(np_)
                    new_states.append(ns_)
                    continue
                np_, ns_ = res
                if master is not None:
                    new_params.append(np_.astype(p.dtype))
                    new_states.append({"master": np_, "inner": ns_})
                else:
                    new_params.append(np_)
                    new_states.append(ns_)
            return new_params, new_states
        return update

    def _apply_one(self, p, g, s, lr, step, decay):
        """The per-parameter update body (weight decay + _update_one +
        multi-precision master handling) shared by the generic update
        and, as the per-tensor fallback, the fused paths."""
        wd_kind, wd = self._weight_decay
        decoupled = self._decoupled_wd()
        master = None
        inner = s
        if self._multi_precision and isinstance(s, dict) \
                and "master" in s:
            master, inner = s["master"], s["inner"]
            work_p = master
            g = g.astype(jnp.float32)
        else:
            work_p = p
        if wd and decay and not decoupled:
            reg = jnp.sign(work_p) if wd_kind == "l1" else work_p
            g = g + wd * reg
        np_, ns_ = self._update_one(work_p, g, inner, lr, step)
        if wd and decay and decoupled:
            reg = jnp.sign(work_p) if wd_kind == "l1" else work_p
            np_ = np_ - lr * wd * reg
        if master is not None:
            return np_.astype(p.dtype), {"master": np_, "inner": ns_}
        return np_, ns_

    def _build_update(self, need_clip_flags, decay_flags):
        """The pure fused update `(params, grads, states, lr, step) ->
        (new_params, new_states)` over flat lists — the TPU analog of the
        reference's multi_tensor/fused optimizer kernels
        (paddle/phi/kernels/fusion/fused_adam_kernel.cu): one traced
        program updates every parameter. Used jitted-with-donation by
        step() and inlined by jit.train_step's single-executable path.

        With the fused-step opt-in, subclasses may swap the per-param
        op chain for a one-pass Pallas kernel (bitwise-identical by
        contract); everything else — clipping, decay flags, master
        weights — is unchanged."""
        if self._use_fused_step():
            fused = self._fused_update_builder(need_clip_flags,
                                               decay_flags)
            if fused is not None:
                return _in_optimizer_scope(fused)
        apply_one = self._apply_one
        grad_clip = self._grad_clip

        def update(params, grads, states, lr, step):
            grads = _clip_arrays(grad_clip, grads, need_clip_flags)
            new_params, new_states = [], []
            for p, g, s, decay in zip(params, grads, states, decay_flags):
                np_, ns_ = apply_one(p, g, s, lr, step, decay)
                new_params.append(np_)
                new_states.append(ns_)
            return new_params, new_states
        return _in_optimizer_scope(update)

    def _make_update_fn(self, need_clip_flags, decay_flags, donate: bool):
        # donate the OPTIMIZER STATES (master weights + moments, ~3x model
        # size in f32): XLA aliases their update in place. Parameter arrays
        # are NOT donated on this eager path — Tensor.detach()/views may
        # alias them across steps (jit.train_step, an explicit opt-in API,
        # donates params too). Grads are never donated — clear_grad owns
        # their lifetime.
        return jax.jit(self._build_update(need_clip_flags, decay_flags),
                       donate_argnums=(2,) if donate else ())

    # -- step ------------------------------------------------------------
    @core.no_grad
    def step(self):
        self._step_count += 1
        all_params: List[Parameter] = []
        for group in self._param_groups:
            for p in group["params"]:
                if p is not None and p.trainable and p.grad is not None:
                    all_params.append(p)
        if not all_params:
            return
        params = [p._data for p in all_params]
        grads = [p.grad._data for p in all_params]
        states = [self._ensure_state(p) for p in all_params]
        need_clip = tuple(bool(getattr(p, "need_clip", True))
                          for p in all_params)
        decay_flags = tuple(not getattr(p, "no_weight_decay", False)
                            for p in all_params)
        lr = jnp.asarray(self.get_lr(), jnp.float32)
        step = jnp.asarray(self._step_count, jnp.int32)
        from ..flags import flag_value
        donate = bool(flag_value("donate_optimizer_buffers"))
        cache_key = (len(params), need_clip, decay_flags, donate,
                     self._use_fused_step(),
                     tuple(p.shape + (str(p.dtype),) for p in params))
        fn = self._jit_cache.get(cache_key)
        if fn is None:
            fn = self._make_update_fn(need_clip, decay_flags, donate)
            self._jit_cache[cache_key] = fn
        new_params, new_states = fn(params, grads, states, lr, step)
        for p, np_, ns_ in zip(all_params, new_params, new_states):
            p._replace_data(np_)
            self._states[id(p)] = ns_

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        loss.backward()
        self.step()
        return None, None

    def clear_grad(self, set_to_zero: bool = False):
        for group in self._param_groups:
            for p in group["params"]:
                p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    # -- checkpointing ---------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"_step_count": self._step_count}
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        idx = 0
        for group in self._param_groups:
            for p in group["params"]:
                key = p.name or f"param_{idx}"
                if id(p) in self._states:
                    # snapshot COPIES: live state buffers are donated to the
                    # next fused update, which would invalidate shared refs
                    out[key] = jax.tree_util.tree_map(
                        lambda a: Tensor(jnp.array(a, copy=True))
                        if isinstance(a, jnp.ndarray) else a,
                        self._states[id(p)])
                idx += 1
        return out

    def set_state_dict(self, state_dict: Dict[str, Any]):
        self._step_count = int(state_dict.get("_step_count", 0))
        if isinstance(self._lr, LRScheduler) and "LR_Scheduler" in state_dict:
            self._lr.set_state_dict(state_dict["LR_Scheduler"])
        idx = 0
        for group in self._param_groups:
            for p in group["params"]:
                key = p.name or f"param_{idx}"
                if key in state_dict:
                    # copy on load: the restored arrays become donation
                    # candidates, which must not delete the caller's
                    # data. The numpy branch must copy EXPLICITLY too —
                    # jnp.asarray may alias a suitably-aligned host
                    # buffer on the CPU backend, and a donated alias of
                    # a rollback snapshot frees the snapshot itself (a
                    # second restore of the same step would then read
                    # freed memory)
                    self._states[id(p)] = jax.tree_util.tree_map(
                        lambda a: jnp.array(a._data, copy=True)
                        if isinstance(a, Tensor)
                        else jnp.array(a, copy=True)
                        if isinstance(a, np.ndarray) else a,
                        state_dict[key])
                else:
                    # the snapshot predates this param's lazily-created
                    # state (e.g. taken before the first step): restore
                    # means UNINITIALIZED, not "keep whatever moments
                    # accumulated since" — stale moments make a
                    # rolled-back Adam step diverge bitwise from the
                    # original, which the SDC fingerprint vote would
                    # then misread as corruption
                    self._states.pop(id(p), None)
                idx += 1

    def _parameter_list(self):
        out = []
        for g in self._param_groups:
            out.extend(g["params"])
        return out
