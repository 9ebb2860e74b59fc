"""The training driver: one compiled step with its state, driven from
the seed through its first steps (which the reference follows), then
handed to the window. Everything a cell is comes from its files."""

from __future__ import annotations

import gc
import time

import numpy as np

import checks
import trafficgen
from common import (Spans, load_module, memory_peak_bytes, note, resolve,
                    start_trace, stop_trace)
from drivers import program

CHECK_STEPS = 3


class Trainer:
    """The program's model, optimizer and fused step, and the feed."""

    def __init__(self, cell: dict, seed: int, spans: Spans):
        import jax
        import paddle2_tpu as paddle
        wl, cfg = cell["workload"], cell["config"]
        self.cell, self.seed, self.spans = cell, seed, spans
        self.traffic = cell["traffic"]
        self.reference = load_module("reference", cfg["reference"])
        self.mesh = None
        if wl.get("mesh"):
            self.mesh, _ = resolve(wl["mesh"]["call"])(**wl["mesh"]["kwargs"])
        model, _ = program.build_model(cfg, wl["program"]["config_overrides"])
        amp = wl["program"].get("amp")
        if amp:
            model = paddle.amp.decorate(model, **amp)
        program.set_weights(model, cfg, wl["program"]["layout"],
                            self.reference, seed)
        gc.collect()        # the initialiser's own arrays go now
        opt = wl["optimizer"]
        self.hp = opt["kwargs"]
        self.optimizer = resolve(opt["class"])(
            parameters=model.parameters(), **opt["kwargs"],
            **opt.get("program_kwargs", {}))
        self.model = model

        def train_fn(ids, labels):
            return model(ids, labels=labels)[-1]

        self.step = paddle.jit.train_step(train_fn, self.optimizer)
        self.vocab = cfg[cfg["program"]["token_vocab_key"]]
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            axes = wl["mesh"]["feed_axes"]

            def put(a):
                spec = P(*axes[:a.ndim])
                return paddle.Tensor(jax.device_put(
                    a, NamedSharding(self.mesh, spec)))
            self._put = put
        else:
            self._put = paddle.to_tensor

    def make_batch(self, index: int):
        with self.spans.span("make_batch"):
            return trafficgen.batch(self.traffic, self.seed, index,
                                    self.vocab)

    def one_step(self, index: int):
        """The window's own call and feed: batch ``index`` from the
        seed, to the device, one dispatch of the step. Returns the loss
        still on the device."""
        arrays = self.make_batch(index)
        with self.spans.span("h2d"):
            tensors = [self._put(a) for a in arrays]
        with self.spans.span("step_dispatch"):
            loss = self.step(*tensors)
        return loss

    def block(self, loss) -> float:
        import jax
        with self.spans.span("block"):
            jax.block_until_ready(loss._data)
        return loss

    # -- what the check reads of the program's state -------------------
    def _state_norms(self, what: str, minus: dict = None) -> dict:
        """{reference leaf: norms} of the optimizer's state, read
        through its one public accessor, ``state_dict()``: ``what`` is
        ``m`` (first moment) or ``master`` (the float32 weights the
        optimizer updates; a parameter kept in float32 is its own
        master), found by its key wherever the parameter's entry holds
        it, so a new nesting of the state does not break the check.
        ``state_dict()`` copies every state (4.2 GB beside a 5 GB
        model, and that copy is then part of the run's
        ``memory_peak_bytes``: PERF.md section 7); each leaf is reduced
        to its norms at once and the copy dropped before the next
        step. ``minus`` = {leaf: array} is subtracted first."""
        import jax
        import jax.numpy as jnp
        from reference.common import leaf_norms
        where = program.leaf_of_param(
            self.cell["config"], self.cell["workload"]["program"]["layout"])
        stacked = frozenset(self.reference.STACKED)
        states = self.optimizer.state_dict()
        out = {}
        for i, (name, p) in enumerate(self.model.named_parameters()):
            leaf, layer = where[name]
            if layer is not None:
                raise ValueError("optimizer state is read leaf by leaf: "
                                 "the layout must be one to one")
            found = _find_key(states.pop(p.name or f"param_{i}"), what)
            if found is None and what == "master":
                found = p
            arr = found._data
            if minus is not None:
                arr = arr - jax.device_put(
                    minus[leaf], arr.sharding).astype(jnp.float32)
            out[leaf] = np.asarray(leaf_norms({leaf: arr}, stacked)[leaf])
        return out

    def grad_norms(self) -> dict:
        """Per-leaf norms of the first gradient as the optimizer got
        it: after one step the first moment is (1 - beta1) x gradient."""
        b1 = self.hp["beta1"]
        return {k: v / (1.0 - b1)
                for k, v in self._state_norms("m").items()}

    def update_norms(self) -> dict:
        """Per-leaf norms of master weights minus the seeded start (made
        again from the seed, in bf16: the values are bf16's own)."""
        from weights import make_weights
        init = make_weights(self.reference.leaf_specs(self.cell["config"]),
                            self.seed)
        return self._state_norms("master", minus=init)


def _find_key(tree, key: str):
    """The value under ``key`` anywhere in a nest of dicts, or None."""
    if isinstance(tree, dict):
        if key in tree:
            return tree[key]
        for v in tree.values():
            hit = _find_key(v, key)
            if hit is not None:
                return hit
    return None


def first_steps(tr: Trainer) -> dict:
    """Set-up's part of the check: the step object that the window will
    drive takes its first three steps, on batches 0..2 of the seed."""
    losses = []
    grad = None
    for i in range(CHECK_STEPS):
        loss = tr.block(tr.one_step(i))
        losses.append(float(np.asarray(loss._data)))
        if i == 0:
            grad = tr.grad_norms()
    return {"losses": losses, "grad_norms": grad,
            "update_norms": tr.update_norms()}


def measure(tr: Trainer, seconds: float, first_index: int,
            log_every: int) -> dict:
    """The window: steps dispatched back to back until ``seconds`` have
    passed, the loss waited for every ``log_every`` steps as a trainer
    that logs does, and at the end. Time runs until the last step is
    done, so the rate is over all the work and all the time."""
    t0 = time.perf_counter()
    n, loss = 0, None
    while time.perf_counter() - t0 < seconds:
        loss = tr.one_step(first_index + n)
        n += 1
        if n % log_every == 0:
            tr.block(loss)
    tr.block(loss)
    elapsed = time.perf_counter() - t0
    return {"steps": n, "elapsed_s": elapsed,
            "last_loss": float(np.asarray(loss._data))}


def traced(tr: Trainer, trace_dir: str, steps: int, first_index: int,
           log_every: int) -> dict:
    """A traced stretch of the steady state: exactly ``steps`` steps
    between two waits, so the trace holds whole steps and no others."""
    tr.spans.reset()
    start_trace(trace_dir)
    tr.spans.tracing = True
    t0 = time.perf_counter()
    with tr.spans.span("traced_window"):
        loss = None
        for n in range(1, steps + 1):
            loss = tr.one_step(first_index + n - 1)
            if n % log_every == 0:
                tr.block(loss)
        tr.block(loss)
    elapsed = time.perf_counter() - t0
    tr.spans.tracing = False
    stop_trace()
    return {"steps": steps, "elapsed_s": elapsed}


def run(cell: dict, args, t_start: float, tally) -> dict:
    import jax
    wl = cell["workload"]
    spans = Spans()
    t_a = time.perf_counter()
    tr = Trainer(cell, args.seed, spans)
    t_b = time.perf_counter()
    prog_side = first_steps(tr)
    note("setup_parts", imports_s=t_a - t_start, build_s=t_b - t_a,
         first_steps_s=time.perf_counter() - t_b)
    warm = wl["window"].get("extra_warmup_steps", 2)
    for i in range(warm):
        last = tr.one_step(CHECK_STEPS + i)
    tr.block(last)
    next_index = CHECK_STEPS + warm
    setup_tally = tally.take()
    spans.reset()
    setup_s = time.perf_counter() - t_start

    log_every = wl["window"]["log_every"]
    tokens = trafficgen.tokens_per_batch(cell["traffic"])
    chips = wl["chips"]
    result = {"metrics": {}, "context": {}}
    if args.trace:
        out = traced(tr, cell["trace_dir"], wl["trace"]["steps"],
                     next_index, log_every)
        result["context"] = {
            "spans": spans, "steps": out["steps"],
            "tokens_per_step": tokens, "elapsed_s": out["elapsed_s"],
            "tokens_per_s": out["steps"] * tokens / out["elapsed_s"]}
    else:
        out = measure(tr, args.seconds, next_index, log_every)
        rate = out["steps"] * tokens / out["elapsed_s"]
        result["metrics"]["train_tokens_per_chip_s"] = rate / chips
        note("train_window", steps=out["steps"], elapsed_s=out["elapsed_s"],
             step_s=out["elapsed_s"] / out["steps"], tokens_per_s=rate,
             last_loss=out["last_loss"],
             dispatch_ms_median=1e3 * float(np.median(
                 spans.durations["step_dispatch"])),
             samples=len(spans.durations["step_dispatch"]))
    window_tally = tally.take()
    result["setup_s"] = setup_s
    result["attempted"] = out["steps"]
    result["failed"] = 0
    result["memory_peak_bytes"] = memory_peak_bytes()
    note("compiles", setup=setup_tally, window=window_tally,
         programs=tr.step.program_cache_size,
         memory_stats={k: int(v) for k, v in
                       (jax.devices()[0].memory_stats() or {}).items()})

    # the program's state goes before the reference's is made
    batches = [trafficgen.batch(cell["traffic"], args.seed, i, tr.vocab)
               for i in range(CHECK_STEPS)]
    reference, cfg, hp = tr.reference, cell["config"], tr.hp
    del tr
    gc.collect()
    t_ref = time.perf_counter()
    ref_side = checks.reference_training(reference, cfg, hp, args.seed,
                                         batches)
    numbers = checks.training_numbers(prog_side, ref_side)
    numbers["window_compiles"] = float(window_tally["compiles"])
    result["correct"] = checks.verdict(numbers, wl["check"]["limits"])
    note("reference", seconds=time.perf_counter() - t_ref, **numbers)
    return result
