"""The serving driver for a model whose layers ROUTE (top-k experts):
``drivers/serve.py`` as it stands, with the one reference call replaced
by a comparison that hands the reference the experts the served path
chose.

Why. Top-k of the router's scores is discontinuous. Where a token's
k-th and (k+1)-th scores lie closer than bf16 rounding of what came
before (one token in ten at 64 experts, 4 a token, in some layer), a
sound bf16 program and the float32 reference take different experts,
and everything after that differs by as much as a wrong expert would:
a plain comparison of served tokens then cannot tell a sound program
from an unsound one. So the program records which experts ran
(``ServingEngine.routed_experts``: they come back in the read-back that
brings the tokens), the reference runs with THOSE experts, and two
numbers are judged, each with a limit in the cell's file:

* ``token_logit_gap``   as in ``checks.py``: how far a served token's
                        reference logit lies below the reference's best
                        at its position — now with the same experts on
                        both sides, so it holds everything the experts
                        compute and everything else to the stated
                        precision;
* ``routing_score_gap`` how sound the program's choice was: the most by
                        which, in the reference's own biased scores, an
                        expert the program left out beats one it took
                        (0 where the program took the reference's own
                        top k). A tie within rounding reads a few
                        thousandths; a wrong router, a dropped bias or
                        a lower precision reads far more.

A control (``control_freed.py``) takes the program's place and is
handled the same way: its tokens AND its experts are judged.
"""

from __future__ import annotations

import contextlib

import numpy as np

import checks
from drivers import serve


class Load(serve.Load):
    """``serve.Load`` that keeps, with a finished request's tokens, the
    experts the engine chose while serving it."""

    def _stamp(self, t: float) -> None:
        n = len(self.live)
        super()._stamp(t)
        if len(self.live) < n:          # something finished
            for rec in self.records:
                if rec["done"] and "routed" not in rec:
                    rec["routed"] = self.engine.routed_experts(rec["rid"])


def routed_token_gaps(reference, cfg: dict, seed: int, sample: list,
                      pad_to: int, served_pad: int,
                      precision: str = "float32", damage=None) -> dict:
    """``checks.reference_token_gaps`` with the experts handed in. Per
    sampled request: the gaps of the served tokens, and the deficit of
    every routed position's choice in every expert layer. With a
    ``precision`` below float32 (the control) the reference in that
    precision stands in the program's place: ITS tokens and ITS experts
    are judged by the float32 reference. ``damage`` (weights -> weights:
    the control of a damaged model, ``control_freed.DAMAGES``) puts the
    reference with damaged weights in the program's place likewise."""
    import jax
    import jax.numpy as jnp
    from reference import common as rc
    from weights import make_weights
    f32, low = rc.MATMULS["float32"], rc.MATMULS[precision]
    stand_in = precision != "float32" or damage is not None

    @jax.jit
    def served_by_stand_in(stand, ids, start, served):
        lo, used, _ = reference.forward(stand, ids, cfg, low)
        rows = jax.lax.dynamic_slice_in_dim(lo[0], start,
                                            served.shape[0], 0)
        return jnp.argmax(rows, -1), used

    @jax.jit
    def judge(true, ids, forced, start, tok):
        lg, _, deficit = reference.forward(true, ids, cfg, f32, forced)
        rows = jax.lax.dynamic_slice_in_dim(lg[0], start, tok.shape[0], 0)
        best = rows.max(-1)
        return (best - jnp.take_along_axis(rows, tok[:, None], -1)[:, 0],
                deficit[0])

    def padded(r):
        seq = list(r["prompt"]) + list(r["tokens"])
        n_out = len(r["tokens"])
        total = -(-len(seq) // pad_to) * pad_to
        ids = np.zeros((1, total), np.int32)
        ids[0, :len(seq)] = seq
        served = np.zeros((-(-n_out // served_pad) * served_pad,), np.int32)
        served[:n_out] = r["tokens"]
        start = len(r["prompt"]) - 1
        # rows past the stream read padding: cut them off
        return (jnp.asarray(ids), jnp.int32(start),
                jnp.asarray(served[:total - start]), n_out, len(seq) - 1)

    out = {"gaps": [], "deficits": [], "tokens": 0}
    specs = reference.leaf_specs(cfg)
    with jax.default_matmul_precision("highest"):
        stood = []
        if stand_in:
            # the stand-in first, then it goes: two float32 copies of a
            # model that fills most of the chip do not fit together
            stand = make_weights(specs, seed, jnp.float32)
            if damage is not None:
                stand = damage(stand)
            for r in sample:
                ids, start, served, _, _ = padded(r)
                tok, used = served_by_stand_in(stand, ids, start, served)
                stood.append((np.asarray(tok), np.asarray(used)))
            del stand
        true = make_weights(specs, seed, jnp.float32)
        for i, r in enumerate(sample):
            ids, start, served, n_out, n_routed = padded(r)
            if stand_in:
                tok, forced = stood[i]
            else:
                tok = served
                forced = np.full((1, ids.shape[1]) + r["routed"].shape[1:],
                                 -1, np.int32)
                forced[0, :n_routed] = r["routed"][:n_routed]
            g, d = judge(true, ids, jnp.asarray(forced), start,
                         jnp.asarray(tok))
            out["gaps"].append(np.asarray(g)[:n_out])
            out["deficits"].append(np.asarray(d)[:n_routed])
            out["tokens"] += n_out
    return out


def routed_numbers(ref: dict) -> dict:
    numbers = checks.serving_numbers(ref)
    numbers["routing_score_gap"] = max(
        (float(d.max()) for d in ref["deficits"] if d.size),
        default=float("inf"))
    return numbers


class _RoutedChecks:
    """``checks`` as ``serve.run`` uses it, with the reference call and
    the numbers replaced."""
    sample_finished = staticmethod(checks.sample_finished)
    verdict = staticmethod(checks.verdict)
    reference_token_gaps = staticmethod(routed_token_gaps)
    serving_numbers = staticmethod(routed_numbers)


@contextlib.contextmanager
def _replaced(module, **names):
    old = {k: getattr(module, k) for k in names}
    try:
        for k, v in names.items():
            setattr(module, k, v)
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


def run(cell: dict, args, t_start: float, tally) -> dict:
    with _replaced(serve, Load=Load, checks=_RoutedChecks):
        return serve.run(cell, args, t_start, tally)
