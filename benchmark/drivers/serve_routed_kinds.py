"""``drivers/serve_routed_staged.py`` for a configuration whose layers
are of MORE kinds than "dense, then experts" (Nemotron-H: a state-space
layer, an expert layer or an attention layer, as its pattern says) and
whose seeded weights are not all plain draws: the same two numbers
judged the same way (``token_logit_gap`` with the program's experts
handed to the reference, ``routing_score_gap``), with

* the seeded weights placed one leaf at a time through
  ``reference.PLACED`` (``serve_staged_dense.place_weights``: ``dt_bias``
  around its stated mean, the experts' matrices in the lanes the program
  stores them in), and
* the float32 reference computed STAGE BY STAGE after the engine is
  freed, ONE compiled program a layer KIND (:func:`kinds_forward`:
  ``reference.kind(cfg, i)`` names layer i's kind; a kind's program is
  traced at its first layer and every other layer of the kind hands its
  leaves over under that layer's names).

The reference module's part: ``leaf_specs``, ``stage_leaves``, ``kind``,
``embed``, ``layer`` (an expert layer takes the experts the program
chose and gives back the deficit of that choice; the other kinds give
None), ``head``, ``PLACED``. A control (``control_kinds.py``) puts a
stand-in in the program's place, as ``control_staged.py`` does."""

from __future__ import annotations

import json

from drivers import serve, serve_routed, serve_staged_dense
from drivers import serve_routed_staged as staged

_PROGRAMS: dict = {}


def _kind_programs(reference, cfg: dict, mm, variant=()):
    """(embed, {kind: (its first layer, the jitted layer)}, head) of
    ``reference`` under ``cfg`` and ``mm``, kept across calls;
    ``variant`` names what a control replaced in the reference module,
    which a traced program has baked in."""
    import jax
    key = (reference.__name__, json.dumps(cfg, sort_keys=True, default=str),
           mm, tuple(variant))
    if key not in _PROGRAMS:
        first = {}
        for i in range(cfg["num_hidden_layers"]):
            first.setdefault(reference.kind(cfg, i), i)
        _PROGRAMS[key] = (
            jax.jit(reference.embed),
            {kind: (at, jax.jit(lambda p, x, forced, at=at: reference.layer(
                p, at, x, cfg, mm, forced))) for kind, at in first.items()},
            jax.jit(lambda p, x: reference.head(p, x, cfg, mm)))
    return _PROGRAMS[key]


def kinds_forward(reference, cfg: dict, seed: int, id_list: list, mm,
                  forced_list=None, damage=None, head_fn=None,
                  variant=()) -> list:
    """``serve_routed_staged.staged_forward`` by layer kind: per request
    ``(head_fn(logits [1, S, V], r), experts used [1, S, expert layers,
    k], deficit [1, S, expert layers])``."""
    import jax.numpy as jnp
    specs = reference.leaf_specs(cfg)
    embed, layers, head = _kind_programs(reference, cfg, mm, variant)
    k = cfg["num_experts_per_tok"]
    xs = [None] * len(id_list)
    used = [[] for _ in id_list]
    deficits = [[] for _ in id_list]
    out = [None] * len(id_list)
    for stage, leaves in reference.stage_leaves(cfg):
        p = staged.draw(specs, seed, leaves, jnp.float32)
        if damage is not None:
            p = damage(p)
        routes = False
        if isinstance(stage, int):
            kind = reference.kind(cfg, stage)
            routes = kind == "moe"
            at, layer = layers[kind]
            p = {f"l{at}_" + name[len(f"l{stage}_"):]: v
                 for name, v in p.items()}
        for r, ids in enumerate(id_list):
            if stage == "embed":
                xs[r] = embed(p, ids)
            elif stage == "head":
                lg = head(p, xs[r])
                xs[r] = None
                out[r] = lg if head_fn is None else head_fn(lg, r)
            else:
                forced = None
                if routes:
                    forced = jnp.full(ids.shape + (k,), -1, jnp.int32) \
                        if forced_list is None \
                        else jnp.asarray(forced_list[r][:, :, len(used[r])])
                xs[r], idx, deficit = layer(p, xs[r], forced)
                if routes:
                    used[r].append(idx)
                    deficits[r].append(deficit)
        del p
    return [(out[r], jnp.stack(used[r], 2), jnp.stack(deficits[r], 2))
            for r in range(len(id_list))]


def kinds_token_gaps(*args, **kwargs) -> dict:
    """``serve_routed_staged.staged_token_gaps`` over
    :func:`kinds_forward`."""
    with serve_routed._replaced(staged, staged_forward=kinds_forward):
        return staged.staged_token_gaps(*args, **kwargs)


class _KindChecks(serve_routed._RoutedChecks):
    reference_token_gaps = staticmethod(kinds_token_gaps)


build_engine = serve_staged_dense.build_engine


def run(cell: dict, args, t_start: float, tally) -> dict:
    with serve_routed._replaced(serve, build_engine=build_engine,
                                Load=serve_routed.Load, checks=_KindChecks):
        return serve.run(cell, args, t_start, tally)
