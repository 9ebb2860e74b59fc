"""The serving driver for a model that generates by DIFFUSION OVER
BLOCKS (and routes: top-k experts): ``drivers/serve.py`` as it stands,
with the one reference call replaced by a comparison of what the timed
path did, pass by pass.

Why. Such a program does not emit a token a step. A block of B
positions is un-masked over S denoise passes (each fixes the B/S masked
positions of highest confidence at their argmax token) and then
committed; a token's VALUE and the ORDER in which positions are fixed
both come from the model, and the keys and values later blocks read are
the commit pass's. So the engine keeps, per request, the record of every
pass (``ServingEngine.block_passes``: the block after the pass, and the
experts chosen for every row — prefill and commit rows included), and
the float32 reference, with THOSE experts forced (top-k is
discontinuous: ``drivers/serve_routed.py``), is run ONCE PER PASS INDEX
over ``[clean ; noisy]`` — the committed sequence, then a copy of its
generated blocks in the state they were in before that pass, under the
published training mask (``reference/sdar_moe.clean_noisy``) — which
gives every block's logits at that pass in one forward. Three numbers
are judged, each with a limit in the cell's file:

* ``token_logit_gap``    how far a fixed token's reference logit lies
                         below the reference's best at that position, in
                         that pass's state;
* ``unmask_choice_gap``  in the reference's log-confidences, the most
                         by which a masked position the program left
                         beats one it fixed (0 where it took the
                         reference's own choice);
* ``routing_score_gap``  as in ``serve_routed``: the most by which, in
                         the reference's own probabilities, an expert
                         the program left out beats one it took — over
                         prefill, denoise and commit rows alike.

A skipped commit, a causal mask inside the block, positions fixed left
to right or a router without renormalisation each move one of them far
outside (``control_blockdiff.py``); a stand-in precision takes the
program's place as in ``control_freed.py``: ITS tokens, choice and
experts on the program's states are judged.
"""

from __future__ import annotations

import numpy as np

import checks
from drivers import serve
from drivers.serve_routed import _replaced, routed_numbers


class Load(serve.Load):
    """``serve.Load`` that keeps, with a finished request's tokens, the
    engine's record of its passes and its prefill's experts."""

    def _stamp(self, t: float) -> None:
        n = len(self.live)
        super()._stamp(t)
        if len(self.live) < n:          # something finished
            for rec in self.records:
                if rec["done"] and "passes" not in rec:
                    rec["passes"] = self.engine.block_passes(rec["rid"])
                    rec["routed"] = self.engine.routed_experts(rec["rid"])


def laid_out(r: dict, cfg: dict, reference, clean_pad: int,
             noisy_pad: int) -> dict:
    """One request's record as the reference's inputs: the clean ids,
    positions and mask of ``[clean ; noisy]`` padded to fixed sizes (a
    padded row sees itself alone), the forced experts of the clean rows,
    and per block its passes [(ids after, experts [B, layers, k])]."""
    B = cfg["block_length"]
    first = len(r["prompt"]) // B * B
    blocks = {}
    for start, row, chosen, _ in r["passes"]:
        blocks.setdefault(start, []).append((np.asarray(row), chosen))
    starts = sorted(blocks)
    clean = list(r["prompt"][:first])
    for s in starts:
        clean += blocks[s][-1][0].tolist()          # the commit's ids
    n, m = len(clean), len(clean) - first
    if n > clean_pad or m > noisy_pad:
        raise ValueError(f"a request of {n} positions, {m} generated, "
                         f"against pads {clean_pad}, {noisy_pad}")
    pos, sees = reference.clean_noisy(n, first, B)
    total = clean_pad + noisy_pad
    at = np.concatenate([np.arange(n), clean_pad + np.arange(m)])
    mask = np.eye(total, dtype=bool)
    mask[np.ix_(at, at)] = sees
    positions = np.zeros(total, np.int32)
    positions[at] = pos
    forced = np.full((total,) + r["routed"].shape[1:], -1, np.int32)
    forced[:first] = r["routed"][:first]
    for s in starts:
        forced[s:s + B] = blocks[s][-1][1]          # the commit's rows
    ids = np.zeros(total, np.int32)
    ids[:n] = clean
    return {"ids": ids, "positions": positions, "mask": mask,
            "forced": forced, "blocks": blocks, "starts": starts,
            "first": first, "clean_pad": clean_pad, "prompt": r["prompt"]}


def pass_inputs(lay: dict, j: int, cfg: dict):
    """The noisy copy before pass ``j`` of every block: (ids, forced)
    of the whole ``[clean ; noisy]`` row, and per block that HAS a pass
    j: (offset among the noisy rows, masked before, ids after)."""
    B, m_id = cfg["block_length"], cfg["mask_token_id"]
    ids, forced = lay["ids"].copy(), lay["forced"].copy()
    judged = []
    for s in lay["starts"]:
        passes = lay["blocks"][s]
        if j == 0:
            state = np.full(B, -1)
            left = lay["prompt"][s:s + B]
            state[:len(left)] = left
        else:
            state = passes[min(j, len(passes)) - 1][0]
        off = s - lay["first"]
        lo = lay["clean_pad"] + off
        ids[lo:lo + B] = np.where(state < 0, m_id, state)
        if j < len(passes):
            forced[lo:lo + B] = passes[j][1]
            if j < len(passes) - 1:     # a denoise pass: it fixed some
                judged.append((off, state < 0, passes[j][0]))
    return ids, forced, judged


def blockdiff_gaps(reference, cfg: dict, seed: int, sample: list,
                   pad_to: int, served_pad: int,
                   precision: str = "float32", damage=None) -> dict:
    """Per sampled request and pass index: the gaps of the tokens the
    program fixed, the gap of its choice of positions, and the deficit
    of every routed row's experts. With a ``precision`` below float32
    or a ``damage`` of the weights (the controls) the reference so
    changed stands in the program's place on the program's states: ITS
    argmax tokens, ITS choice by ITS confidences and ITS experts are
    judged by the float32 reference."""
    import jax
    import jax.numpy as jnp
    from reference import common as rc
    from weights import make_weights
    B = cfg["block_length"]
    f32, low = rc.MATMULS["float32"], rc.MATMULS[precision]
    stand_in = precision != "float32" or damage is not None
    # positions from the first generated block to the last one's end
    spans = [max(p[0] for p in r["passes"]) + B
             - len(r["prompt"]) // B * B for r in sample]
    noisy_pad = -(-max([served_pad] + spans) // B) * B
    clean_pad = -(-pad_to // B) * B

    def head(lg):
        lg = lg[0]
        best = lg.max(-1)
        return lg, best, best - jax.nn.logsumexp(lg, -1)

    @jax.jit
    def stand(params, ids, mask, positions):
        lg, used, _ = reference.forward(
            params, ids[None], cfg, low, mask=mask, positions=positions,
            head_from=clean_pad)
        lg, _, conf = head(lg)
        return jnp.argmax(lg, -1), conf, used[0]

    @jax.jit
    def judge(params, ids, mask, positions, forced, tok):
        lg, _, deficit = reference.forward(
            params, ids[None], cfg, f32, mask=mask, forced=forced[None],
            positions=positions, head_from=clean_pad)
        lg, best, conf = head(lg)
        return (best - jnp.take_along_axis(lg, tok[:, None], -1)[:, 0],
                conf, deficit[0])

    lays = [laid_out(r, cfg, reference, clean_pad, noisy_pad)
            for r in sample]
    depth = [max(len(v) for v in lay["blocks"].values()) for lay in lays]
    specs = reference.leaf_specs(cfg)
    out = {"gaps": [], "choice": [], "deficits": [], "tokens": 0}
    with jax.default_matmul_precision("highest"):
        stood = {}
        if stand_in:
            # the stand-in first, then it goes: two float32 copies of a
            # model that fills most of the chip do not fit together
            params = make_weights(specs, seed, jnp.float32)
            if damage is not None:
                params = damage(params)
            for i, lay in enumerate(lays):
                for j in range(depth[i]):
                    ids, _, _ = pass_inputs(lay, j, cfg)
                    stood[i, j] = [np.asarray(x) for x in stand(
                        params, jnp.asarray(ids), jnp.asarray(lay["mask"]),
                        jnp.asarray(lay["positions"]))]
            del params
        params = make_weights(specs, seed, jnp.float32)
        for i, lay in enumerate(lays):
            n_clean = lay["first"] + len(lay["starts"]) * B
            for j in range(depth[i]):
                ids, forced, judged = pass_inputs(lay, j, cfg)
                tok = np.zeros(noisy_pad, np.int32)
                fixed = np.zeros(noisy_pad, bool)
                if stand_in:
                    s_tok, s_conf, s_used = stood[i, j]
                    forced = s_used
                    for off, masked, after in judged:
                        n_fix = int((masked & (after >= 0)).sum())
                        take = reference.choose(s_conf[off:off + B], masked,
                                                n_fix)
                        fixed[off:off + B] = take
                    tok = np.where(fixed, s_tok, 0).astype(np.int32)
                else:
                    for off, masked, after in judged:
                        fixed[off:off + B] = masked & (after >= 0)
                        tok[off:off + B] = np.where(fixed[off:off + B],
                                                    after, 0)
                gap, conf, deficit = (np.asarray(x) for x in judge(
                    params, jnp.asarray(ids), jnp.asarray(lay["mask"]),
                    jnp.asarray(lay["positions"]), jnp.asarray(forced),
                    jnp.asarray(tok)))
                out["gaps"].append(gap[fixed])
                out["tokens"] += int(fixed.sum())
                for off, masked, _ in judged:
                    took = fixed[off:off + B]
                    left = masked & ~took
                    if left.any() and took.any():
                        out["choice"].append(max(0.0, float(
                            conf[off:off + B][left].max()
                            - conf[off:off + B][took].min())))
                # the clean rows once, this pass's noisy rows always
                rows = np.zeros(len(ids), bool)
                if j == 0:
                    rows[:n_clean] = True
                for s in lay["starts"]:
                    if j < len(lay["blocks"][s]):
                        lo = lay["clean_pad"] + s - lay["first"]
                        rows[lo:lo + B] = True
                out["deficits"].append(deficit[rows])
    return out


def blockdiff_numbers(ref: dict) -> dict:
    numbers = routed_numbers(ref)
    numbers["unmask_choice_gap"] = max(ref["choice"], default=0.0)
    return numbers


class _BlockdiffChecks:
    """``checks`` as ``serve.run`` uses it, with the reference call and
    the numbers replaced."""
    sample_finished = staticmethod(checks.sample_finished)
    verdict = staticmethod(checks.verdict)
    reference_token_gaps = staticmethod(blockdiff_gaps)
    serving_numbers = staticmethod(blockdiff_numbers)


def run(cell: dict, args, t_start: float, tally) -> dict:
    with _replaced(serve, Load=Load, checks=_BlockdiffChecks):
        return serve.run(cell, args, t_start, tally)
