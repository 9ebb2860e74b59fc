"""``drivers/serve.py`` for a DENSE configuration that fills the chip
(Falcon-H1's cut: 4.39 B parameters are 8.79 GB in bf16 and 17.6 GB in
float32): the same number judged the same way (``token_logit_gap``),
with two things done a piece at a time.

* The seeded weights are PLACED ONE LEAF AT A TIME (:func:`place_weights`):
  ``program.set_weights`` draws every leaf beside the parameter it
  replaces (twice the weights at the peak), which 8.79 GB does not
  survive on a 16 GB chip; here a leaf is drawn
  (``serve_routed_staged.draw``: the values ``weights.make_weights``
  gives), placed and let go before the next, so the peak is the weights
  plus one leaf. Where the reference says a parameter is not the leaf
  itself (``reference.PLACED``: Falcon-H1's ``dt_bias`` stands around a
  stated mean), its function is applied to the float32 leaf.
* The float32 reference is computed STAGE BY STAGE after the engine is
  freed (:func:`staged_logits`): the embedding, each layer, the head —
  each stage's weights drawn from the seed, used for every sampled
  request and freed before the next stage's are drawn (the head stage
  is 5.35 GB, a layer 1.72 GB).

The reference module's part: ``leaf_specs``, ``stage_leaves``,
``embed``, ``layer``, ``head``, ``PLACED``. A control
(``control_staged_dense.py``) puts a stand-in in the program's place:
the reference in a lower precision, or with one piece of its
mathematics replaced; ITS tokens are judged by the float32 reference."""

from __future__ import annotations

import json

import numpy as np

import checks
from drivers import program, serve
from drivers.serve_routed import _replaced
from drivers.serve_routed_staged import draw


def place_weights(model, cfg: dict, layout: str, reference,
                  seed: int) -> None:
    """``program.set_weights`` a leaf at a time: every parameter of
    ``model`` set from the seed, at most one drawn leaf alive. The host
    WAITS for each leaf (and first for the values the model was created
    with): enqueued ahead of the device, a leaf is allocated while the
    value it replaces still waits for its own producer, and "the
    weights plus one leaf" (11.48 GB at the peak, my chip run, PR 39)
    would hold only when the device happens to keep up."""
    import jax
    import jax.numpy as jnp
    import paddle2_tpu as paddle
    specs = reference.leaf_specs(cfg)
    where = program.leaf_of_param(cfg, layout)
    placed = getattr(reference, "PLACED", {})
    jax.block_until_ready([p._data for p in model.parameters()])
    for name, p in model.named_parameters():
        if name not in where:
            raise KeyError(f"parameter {name!r} has no leaf in layout "
                           f"{layout!r} of {cfg['name']}")
        leaf, _ = where[name]
        fn = next((f for end, f in placed.items() if leaf.endswith(end)),
                  None)
        if fn is None:
            arr = draw(specs, seed, (leaf,), p._data.dtype)[leaf]
        else:
            arr = fn(draw(specs, seed, (leaf,), jnp.float32)[leaf],
                     cfg).astype(p._data.dtype)
        p.set_value(paddle.Tensor(arr))
        del arr
        p._data.block_until_ready()


_serve_build_engine = serve.build_engine


def build_engine(cell: dict, seed: int):
    """``serve.build_engine`` with :func:`place_weights` for
    ``program.set_weights``."""
    with _replaced(program, set_weights=place_weights):
        return _serve_build_engine(cell, seed)


_PROGRAMS: dict = {}


def _stage_programs(reference, cfg: dict, mm, variant=()):
    """The jitted stages (embed, a layer, head) of ``reference`` under
    ``cfg`` and ``mm``, kept across calls; ``variant`` names what a
    control replaced in the reference module, which a traced program has
    baked in."""
    import jax
    key = (reference.__name__, json.dumps(cfg, sort_keys=True, default=str),
           mm, tuple(variant))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = (
            jax.jit(lambda p, ids: reference.embed(p, ids, cfg)),
            jax.jit(lambda p, x: reference.layer(p, 0, x, cfg, mm)),
            jax.jit(lambda p, x: reference.head(p, x, cfg, mm)))
    return _PROGRAMS[key]


def staged_logits(reference, cfg: dict, seed: int, id_list: list, mm,
                  head_fn, variant=()) -> list:
    """The reference over every ``ids [1, S]`` of ``id_list``, stage by
    stage; per request ``head_fn(logits [1, S, V], r)``."""
    import jax.numpy as jnp
    specs = reference.leaf_specs(cfg)
    embed, layer, head = _stage_programs(reference, cfg, mm, variant)
    xs = [None] * len(id_list)
    out = [None] * len(id_list)
    for stage, leaves in reference.stage_leaves(cfg):
        p = draw(specs, seed, leaves, jnp.float32)
        if isinstance(stage, int):
            # one compiled program serves every layer: its leaves are
            # handed over under the first layer's names
            p = {"l0_" + k[len(f"l{stage}_"):]: v for k, v in p.items()}
        for r, ids in enumerate(id_list):
            if stage == "embed":
                xs[r] = embed(p, ids)
            elif stage == "head":
                out[r] = head_fn(head(p, xs[r]), r)
                xs[r] = None
            else:
                xs[r] = layer(p, xs[r])
        del p
    return out


def staged_token_gaps(reference, cfg: dict, seed: int, sample: list,
                      pad_to: int, served_pad: int,
                      precision: str = "float32", stand_cfg=None,
                      patched=None, variant=()) -> dict:
    """``checks.reference_token_gaps`` over :func:`staged_logits`. A
    stand-in (the control) is the reference in ``precision``, under
    ``stand_cfg`` in place of ``cfg``, or with the module attributes
    ``patched`` replaced (``variant`` names the replacement: two
    controls may replace one attribute): ITS tokens are judged by the
    float32 reference."""
    import jax
    import jax.numpy as jnp
    from reference import common as rc
    f32, low = rc.MATMULS["float32"], rc.MATMULS[precision]
    stand_in = precision != "float32" or stand_cfg is not None \
        or patched is not None

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
                     "n_out": n_out})
    id_list = [q["ids"] for q in reqs]
    out = {"gaps": [], "tokens": 0}
    with jax.default_matmul_precision("highest"):
        toks = [q["served"] for q in reqs]
        if stand_in:
            with _replaced(reference, **(patched or {})):
                toks = [jnp.asarray(t) for t in staged_logits(
                    reference, stand_cfg or cfg, seed, id_list, low,
                    lambda lg, r: np.asarray(served_rows(
                        lg, reqs[r]["start"], reqs[r]["served"])),
                    variant=tuple(sorted(patched or ())) + tuple(variant))]
        judged = staged_logits(
            reference, cfg, seed, id_list, f32,
            lambda lg, r: np.asarray(gap_rows(lg, reqs[r]["start"],
                                              toks[r])))
        for q, gaps in zip(reqs, judged):
            out["gaps"].append(gaps[:q["n_out"]])
            out["tokens"] += q["n_out"]
    return out


class _StagedChecks:
    """``checks`` as ``serve.run`` uses it, the reference call replaced."""
    sample_finished = staticmethod(checks.sample_finished)
    verdict = staticmethod(checks.verdict)
    serving_numbers = staticmethod(checks.serving_numbers)
    reference_token_gaps = staticmethod(staged_token_gaps)


def run(cell: dict, args, t_start: float, tally) -> dict:
    with _replaced(serve, build_engine=build_engine, checks=_StagedChecks):
        return serve.run(cell, args, t_start, tally)
