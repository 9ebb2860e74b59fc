"""The comparison that decides ``correct``.

Training: the reference follows the program's first three steps from
the same seeded weights on the same batches, in float32 at full size,
and four kinds of number are compared, each with a limit of its own
(the cell's file holds the limits and PERF.md the readings they were
set from):

* ``loss_gap``        |loss - ref| / |ref|, the worst of the three steps;
* ``grad_norm_gap``   worst leaf of the first gradient as the optimizer
                      got it (read back from its first moment);
* ``update_norm_gap`` the norm of the parameters' change after three
                      steps (read from the float32 master weights),
                      over the whole model.

A leaf's gap is |program's norm - reference's norm| over the larger of
the reference's norm of that leaf and of the median leaf. A leaf
stacked over layers counts once per layer. The update's gap is judged
on the whole model's norm and the worst leaf's is printed beside it:
Adam divides each gradient by its own size, so an all-but-zero gradient
(the key bias, to which softmax is blind) becomes full-size steps of
the sign of rounding noise, and that leaf's norm swings threefold from
seed to seed (0.12 and 0.37 on two seeds of one cell) while the whole
model's does not.

Serving: a seeded sample of the requests the window finished, with the
longest in it; the reference runs once over each prompt with its served
tokens and the number compared is the widest gap by which a served
token's reference logit lies below the reference's best at its
position (``token_logit_gap``).
"""

from __future__ import annotations

import functools
import math

import numpy as np


def worst_leaf_gap(prog: dict, ref: dict) -> tuple:
    """(gap, leaf) over {leaf: vector of norms}."""
    all_ref = np.concatenate([np.asarray(ref[k], np.float64).ravel()
                              for k in sorted(ref)])
    floor = float(np.median(all_ref))
    worst, where = 0.0, None
    for k in sorted(ref):
        r = np.asarray(ref[k], np.float64).ravel()
        p = np.asarray(prog[k], np.float64).ravel()
        gap = np.abs(p - r) / np.maximum(r, floor)
        if not np.all(np.isfinite(gap)):
            return float("inf"), k
        i = int(np.argmax(gap))
        if gap[i] >= worst:
            worst, where = float(gap[i]), f"{k}[{i}]" if r.size > 1 else k
    return worst, where


def verdict(numbers: dict, limits: dict) -> bool:
    """Print each number beside its limit; true when all are inside."""
    ok = True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's file")
        inside = bool(math.isfinite(value) and value <= limits[name])
        ok &= inside
        print(f"check {name}: {value:.6g} limit {limits[name]:.6g} "
              f"{'ok' if inside else 'OUTSIDE'}", flush=True)
    return ok


# ------------------------------------------------------------ training
def _place(a, axis, n_dev_divides: bool):
    """``a`` over all devices along ``axis`` where that divides, else
    whole on every device."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()), ("all",))
    spec = [None] * a.ndim
    if n_dev_divides:
        spec[axis] = "all"
    return jax.device_put(a, NamedSharding(mesh, P(*spec)))


def shard_tree(tree: dict) -> dict:
    """Place each leaf over all devices along its last axis where that
    divides, else whole on every device: the reference then fits where
    one chip could not hold it, with no word of sharding in its code."""
    import jax
    n = len(jax.devices())
    return {k: _place(a, -1, bool(a.ndim and a.shape[-1] % n == 0
                                  and a.size > 4096))
            for k, a in tree.items()}


def shard_batch(arrays) -> tuple:
    import jax
    n = len(jax.devices())
    return tuple(_place(a, 0, a.shape[0] % n == 0) for a in arrays)


def reference_training(reference, cfg: dict, hp: dict, seed: int,
                       batches, precision: str = "float32") -> dict:
    """The reference's side of the training check: losses of the steps,
    per-leaf norms of the first gradient and of the parameters' change.
    ``precision`` other than float32 is the control's."""
    import jax
    import jax.numpy as jnp
    from reference import common as rc
    from weights import make_weights
    mm = rc.MATMULS[precision]
    stacked = frozenset(reference.STACKED)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, batch, t):
        loss, grads = jax.value_and_grad(
            lambda p: reference.loss(p, batch, cfg, mm))(params)
        gnorm = rc.leaf_norms(grads, stacked)
        params, state = rc.adamw_update(params, grads, state, t, hp)
        return loss, gnorm, params, state

    @jax.jit
    def change(params, init):
        return rc.leaf_norms({k: params[k] - init[k] for k in params},
                             stacked)

    specs = reference.leaf_specs(cfg)
    with jax.default_matmul_precision("highest"):
        params = shard_tree(make_weights(specs, seed, jnp.float32))
        state = {k: shard_tree(v)
                 for k, v in rc.adamw_init(params).items()}
        losses, gnorm0 = [], None
        for t, b in enumerate(batches, start=1):
            loss, gnorm, params, state = step(
                params, state, shard_batch(b), jnp.int32(t))
            losses.append(float(loss))
            if gnorm0 is None:
                gnorm0 = {k: np.asarray(v) for k, v in gnorm.items()}
        init = shard_tree(make_weights(specs, seed, jnp.float32))
        dnorm = {k: np.asarray(v) for k, v in change(params, init).items()}
    return {"losses": losses, "grad_norms": gnorm0, "update_norms": dnorm}


def training_numbers(prog: dict, ref: dict) -> dict:
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    g, g_leaf = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    u_worst, u_leaf = worst_leaf_gap(prog["update_norms"],
                                     ref["update_norms"])
    whole_p, whole_r = (whole_norm(prog["update_norms"]),
                        whole_norm(ref["update_norms"]))
    u = abs(whole_p - whole_r) / whole_r if whole_r > 0 else float("inf")
    print(f"check worst leaves: grad {g_leaf}, update {u_leaf} "
          f"({u_worst:.4g}, not judged); losses {prog['losses']} vs "
          f"reference {ref['losses']}", flush=True)
    return {"loss_gap": loss_gap, "grad_norm_gap": g, "update_norm_gap": u}


def whole_norm(norms: dict) -> float:
    """The norm of the whole tree from its leaves' norms."""
    return float(np.sqrt(sum(float(np.sum(np.asarray(v, np.float64) ** 2))
                             for v in norms.values())))


# ------------------------------------------------------------- serving
def sample_finished(finished: list, seed: int, n: int) -> list:
    """``n`` of the finished requests drawn from the seed, the longest
    (prompt + served tokens) always among them."""
    if not finished:
        return []
    rng = np.random.default_rng([seed % (2 ** 32), 0xC0DE])
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i]["prompt"])
                  + len(finished[i]["tokens"]))
    rest = [i for i in range(len(finished)) if i != longest]
    pick = rng.permutation(rest)[:max(0, n - 1)].tolist()
    return [finished[i] for i in [longest] + pick]


def reference_token_gaps(reference, cfg: dict, seed: int, sample: list,
                         pad_to: int, served_pad: int,
                         precision: str = "float32") -> dict:
    """Per sampled request, how far each served token's reference logit
    lies below the reference's best at its position. With a
    ``precision`` below float32 (the control) the token judged is the
    one that precision puts first, not the served one."""
    import jax
    import jax.numpy as jnp
    from reference import common as rc
    from weights import make_weights
    f32, low = rc.MATMULS["float32"], rc.MATMULS[precision]

    @jax.jit
    def gaps(params, ids, start, served):
        # ids [1, T]; the logits that predict position start+1.. are
        # rows start.. of the forward
        lg = reference.logits(params, ids, cfg, f32)[0]
        rows = jax.lax.dynamic_slice_in_dim(lg, start, served.shape[0], 0)
        if precision == "float32":
            tok = served
        else:
            lo = reference.logits(params, ids, cfg, low)[0]
            tok = jnp.argmax(jax.lax.dynamic_slice_in_dim(
                lo, start, served.shape[0], 0), -1)
        best = rows.max(-1)
        return best - jnp.take_along_axis(rows, tok[:, None], -1)[:, 0]

    out = {"gaps": [], "tokens": 0}
    with jax.default_matmul_precision("highest"):
        params = make_weights(reference.leaf_specs(cfg), seed, jnp.float32)
        for r in sample:
            seq = list(r["prompt"]) + list(r["tokens"])
            n_out = len(r["tokens"])
            total = -(-len(seq) // pad_to) * pad_to
            ids = np.zeros((1, total), np.int32)
            ids[0, :len(seq)] = seq
            served = np.zeros((-(-n_out // served_pad) * served_pad,),
                              np.int32)
            served[:n_out] = r["tokens"]
            start = len(r["prompt"]) - 1
            # rows past the stream read padding: cut them off
            if start + served.shape[0] > total:
                served = served[:total - start]
            g = np.asarray(gaps(params, jnp.asarray(ids), jnp.int32(start),
                                jnp.asarray(served)))[:n_out]
            out["gaps"].append(g)
            out["tokens"] += n_out
    return out


def serving_numbers(ref: dict) -> dict:
    worst = max((float(g.max()) for g in ref["gaps"] if len(g)),
                default=float("inf"))
    return {"token_logit_gap": worst}
