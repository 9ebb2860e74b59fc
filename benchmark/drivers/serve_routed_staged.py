"""``drivers/serve_routed.py`` for a configuration whose float32
reference does not fit on the chip WHOLE (DeepSeek-V2's cut: 3.1 B
parameters are 12.6 GB in float32, beside 128 heads of a 6,144-token
sequence's activations): the same two numbers judged the same way, with
the reference computed STAGE BY STAGE — the embedding, each layer, the
head (``reference.stage_leaves``) — each stage's weights drawn from the
seed, used for every sampled request, and freed before the next
stage's are drawn. At most one layer's float32 weights (2.7 GB) stand
beside the requests' activations (126 MB a request).

The reference module's part: ``leaf_specs``, ``stage_leaves``,
``embed``, ``layer`` (which takes the experts the program chose and
gives back the deficit of that choice) and ``head``.

:func:`draw` makes a subset of ``weights.make_weights``' leaves with
the same values (a leaf's values depend on the seed, its place in the
sorted list of ALL leaves, and its shape). A control
(``control_staged.py``) puts a stand-in in the program's place: the
reference in a lower precision, with damaged weights, under a changed
configuration, or with a piece of its mathematics replaced."""

from __future__ import annotations

import functools
import json

import numpy as np

from drivers import serve, serve_routed


@functools.lru_cache(maxsize=None)
def _drawer():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _draw(kinds, seed, places, dtype):
        # weights._make, for leaves of `kinds` (shape, init, scale) at
        # `places` of its enumeration: the places are data, so layers
        # of one kind share one compiled program
        key = jax.random.PRNGKey(seed)
        out = []
        for j, (shape, init, scale) in enumerate(kinds):
            z = jax.random.normal(jax.random.fold_in(key, places[j]), shape,
                                  jnp.float32) * scale
            if init == "gain":
                z = 1.0 + z
            out.append(jax.lax.reduce_precision(z, 8, 7).astype(dtype))
        return out
    return _draw


def draw(specs: dict, seed: int, names, dtype) -> dict:
    """{leaf: array} for ``names`` only, equal to what
    ``weights.make_weights(specs, seed, dtype)`` holds under them."""
    import jax.numpy as jnp
    place = {k: i for i, k in enumerate(sorted(specs))}
    kinds = tuple((tuple(specs[n][0]), specs[n][1], float(specs[n][2]))
                  for n in names)
    arrays = _drawer()(kinds, jnp.uint32(seed % (2 ** 32)),
                       jnp.asarray([place[n] for n in names], jnp.uint32),
                       dtype)
    return dict(zip(names, arrays))


_PROGRAMS: dict = {}


def _stage_programs(reference, cfg: dict, mm, variant=()):
    """The jitted stages (embed, a dense layer, an expert layer, head)
    of ``reference`` under ``cfg`` and ``mm``, kept across calls: the
    sound pass, the judge of every control and a control that only
    damages weights are the same four programs. ``variant`` names what
    a control replaced in the reference module, which a traced program
    has baked in."""
    import jax
    key = (reference.__name__, json.dumps(cfg, sort_keys=True, default=str),
           mm, tuple(variant))
    if key not in _PROGRAMS:
        dense_at, moe_at = 0, cfg["first_k_dense_replace"]
        _PROGRAMS[key] = (
            jax.jit(reference.embed),
            jax.jit(lambda p, x: reference.layer(p, dense_at, x, cfg,
                                                 mm)[0]),
            jax.jit(lambda p, x, forced: reference.layer(
                p, moe_at, x, cfg, mm, forced)),
            jax.jit(lambda p, x: reference.head(p, x, cfg, mm)))
    return _PROGRAMS[key]


def staged_forward(reference, cfg: dict, seed: int, id_list: list, mm,
                   forced_list=None, damage=None, head_fn=None,
                   variant=()) -> list:
    """The reference over every ``ids [1, S]`` of ``id_list``, stage by
    stage. Per request ``(head_fn(logits [1, S, V], r) — the logits
    themselves without one —, experts used [1, S, expert layers, k],
    deficit [1, S, expert layers])``. ``forced_list[r]`` ``[1, S,
    expert layers, k]`` hands in the experts of request r; ``damage``
    (leaves of a stage -> leaves) damages each stage's weights;
    ``variant``: :func:`_stage_programs`."""
    import jax.numpy as jnp
    specs = reference.leaf_specs(cfg)
    dense_at, moe_at = 0, cfg["first_k_dense_replace"]
    embed, dense_layer, moe_layer, head = _stage_programs(
        reference, cfg, mm, variant)

    def as_layer(p, i, at):
        # one compiled program serves every layer of a kind: its leaves
        # are handed over under the first such layer's names
        return {f"l{at}_" + k[len(f"l{i}_"):]: v for k, v in p.items()}

    xs = [None] * len(id_list)
    used = [[] for _ in id_list]
    deficits = [[] for _ in id_list]
    out = [None] * len(id_list)
    for stage, leaves in reference.stage_leaves(cfg):
        p = draw(specs, seed, leaves, jnp.float32)
        if damage is not None:
            p = damage(p)
        for r, ids in enumerate(id_list):
            if stage == "embed":
                xs[r] = embed(p, ids)
            elif stage == "head":
                lg = head(p, xs[r])
                xs[r] = None
                out[r] = lg if head_fn is None else head_fn(lg, r)
            elif reference.is_dense(cfg, stage):
                xs[r] = dense_layer(as_layer(p, stage, dense_at), xs[r])
            else:
                e = len(used[r])
                forced = jnp.full(
                    ids.shape + (cfg["num_experts_per_tok"],), -1,
                    jnp.int32) if forced_list is None \
                    else jnp.asarray(forced_list[r][:, :, e])
                xs[r], idx, deficit = moe_layer(as_layer(p, stage, moe_at),
                                                xs[r], forced)
                used[r].append(idx)
                deficits[r].append(deficit)
        del p
    return [(out[r], jnp.stack(used[r], 2), jnp.stack(deficits[r], 2))
            for r in range(len(id_list))]


def staged_token_gaps(reference, cfg: dict, seed: int, sample: list,
                      pad_to: int, served_pad: int,
                      precision: str = "float32", damage=None,
                      stand_cfg=None, patched=None) -> dict:
    """``serve_routed.routed_token_gaps`` over :func:`staged_forward`.
    A stand-in (the control) is the reference in ``precision``, with
    ``damage`` to each stage's weights, under ``stand_cfg`` in place of
    ``cfg``, or with the module attributes ``patched`` replaced: ITS
    tokens and ITS experts are judged by the float32 reference."""
    import jax
    import jax.numpy as jnp
    from reference import common as rc
    f32, low = rc.MATMULS["float32"], rc.MATMULS[precision]
    stand_in = precision != "float32" or damage is not None \
        or stand_cfg is not None or patched is not None

    @jax.jit
    def served_rows(lg, start, served):
        rows = jax.lax.dynamic_slice_in_dim(lg[0], start, served.shape[0], 0)
        return jnp.argmax(rows, -1)

    @jax.jit
    def gap_rows(lg, start, tok):
        rows = jax.lax.dynamic_slice_in_dim(lg[0], start, tok.shape[0], 0)
        return rows.max(-1) - jnp.take_along_axis(rows, tok[:, None],
                                                  -1)[:, 0]

    reqs = []
    for r in sample:
        seq = list(r["prompt"]) + list(r["tokens"])
        n_out = len(r["tokens"])
        total = -(-len(seq) // pad_to) * pad_to
        ids = np.zeros((1, total), np.int32)
        ids[0, :len(seq)] = seq
        served = np.zeros((-(-n_out // served_pad) * served_pad,), np.int32)
        served[:n_out] = r["tokens"]
        start = len(r["prompt"]) - 1
        # rows past the stream read padding: cut them off
        reqs.append({"ids": jnp.asarray(ids), "start": jnp.int32(start),
                     "served": jnp.asarray(served[:total - start]),
                     "n_out": n_out, "n_routed": len(seq) - 1})
    id_list = [q["ids"] for q in reqs]
    out = {"gaps": [], "deficits": [], "tokens": 0}
    with jax.default_matmul_precision("highest"):
        if stand_in:
            with serve_routed._replaced(reference, **(patched or {})):
                stood = staged_forward(
                    reference, stand_cfg or cfg, seed, id_list, low,
                    damage=damage, variant=sorted(patched or ()),
                    head_fn=lambda lg, r: np.asarray(
                        served_rows(lg, reqs[r]["start"],
                                    reqs[r]["served"])))
            toks = [jnp.asarray(s[0]) for s in stood]
            forced = [np.asarray(s[1]) for s in stood]
        else:
            toks, forced = [q["served"] for q in reqs], []
            for q, r in zip(reqs, sample):
                f = np.full(q["ids"].shape + r["routed"].shape[1:], -1,
                            np.int32)
                f[0, :q["n_routed"]] = r["routed"][:q["n_routed"]]
                forced.append(f)
        judged = staged_forward(
            reference, cfg, seed, id_list, f32, forced,
            head_fn=lambda lg, r: np.asarray(
                gap_rows(lg, reqs[r]["start"], toks[r])))
        for q, (gaps, _, deficit) in zip(reqs, judged):
            out["gaps"].append(gaps[:q["n_out"]])
            out["deficits"].append(np.asarray(deficit[0])[:q["n_routed"]])
            out["tokens"] += q["n_out"]
    return out


class _StagedChecks(serve_routed._RoutedChecks):
    reference_token_gaps = staticmethod(staged_token_gaps)


def run(cell: dict, args, t_start: float, tally) -> dict:
    with serve_routed._replaced(serve, Load=serve_routed.Load,
                                checks=_StagedChecks):
        return serve.run(cell, args, t_start, tally)
