"""Generation by diffusion over blocks: what the engine and the runner
need of it, apart from the model.

A block-diffusion decoder (``ModelFamily.block_length`` B) does not
append a token a step. A sequence's step is a PASS over a block of B
positions: ``S`` *denoise* passes, each a forward of the block against
the cache (the block's positions see each other in both directions; the
keys and values the pass writes are provisional) after which the
still-masked positions of highest confidence are fixed at their argmax
token, and then, when no mask is left, one *commit* pass, the forward
of the B final tokens whose keys and values are kept. Only then do the
block's tokens exist.

Under the STATIC schedule (``low_confidence_static``) pass ``j`` fixes
``B / S`` positions, so the host knows every count it needs without
reading anything back (:func:`fix_plan`): which pass a row is in, how
many positions it fixes, when its block is committed and the next
opens. Which positions, and which tokens, the model says — on the
device (:func:`unmask_low_confidence`), where the block in flight stays
(``PagedKVCache.block_ids`` / ``block_masked``) so that pass n+1 is
enqueued, fed by pass n's block where it lies, before the host has read
pass n.

Masked-ness is a bit kept beside the ids, never inferred from an id
being the ``[MASK]`` id: a prompt may hold that id.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

__all__ = ["BlockInFlight", "fix_plan", "unmask_low_confidence",
           "STRATEGIES"]

STRATEGIES = ("low_confidence_static",)


def fix_plan(block_length: int, steps: int, masked: int) -> Tuple[int, ...]:
    """Positions each denoise pass of a block fixes when ``masked`` of
    its ``block_length`` positions start masked: ``block_length / steps``
    a pass until none is left (a block that opens with prompt tokens in
    it takes fewer passes, and its last may fix fewer). The commit pass
    follows the last entry and fixes nothing."""
    per = block_length // steps
    return tuple(min(per, masked - done) for done in range(0, masked, per))


class BlockInFlight:
    """What the HOST knows of a sequence's block in flight, as of the
    last pass it read back: the block's first position ``start``, its
    ``ids`` and which are still ``masked`` (numpy, length B), the
    ``plan`` of its denoise passes and how many passes are ``done``
    (``done == len(plan)``: only the commit is left)."""

    __slots__ = ("start", "ids", "masked", "plan", "done")

    def __init__(self, start: int, fixed, block_length: int, steps: int):
        """``fixed``: the ids the block opens with (prompt tokens that
        did not fill a whole block; empty for every later block)."""
        self.start = int(start)
        self.ids = np.zeros(block_length, np.int32)
        self.ids[:len(fixed)] = fixed
        self.masked = np.arange(block_length) >= len(fixed)
        self.plan = fix_plan(block_length, steps,
                             block_length - len(fixed))
        self.done = 0

    def fixes(self, q: int) -> int:
        """Positions pass ``q`` fixes (0: the commit)."""
        return self.plan[q] if q < len(self.plan) else 0


def unmask_low_confidence(logits, ids, masked, n_fix):
    """One pass's choice, on the device: ``logits [R, B, V]`` f32,
    ``ids [R, B]``, ``masked [R, B]`` bool, ``n_fix [R]`` -> (ids,
    masked) after the pass. At every still-masked position the argmax
    token and its log-probability (the confidence); the ``n_fix[r]``
    masked positions of highest confidence (ties: the earlier position)
    take their token and lose their mask. A row with ``n_fix`` 0 (a
    commit pass) comes back as it went in."""
    import jax
    import jax.numpy as jnp
    B = ids.shape[1]
    tok = jax.lax.argmax(logits, 2, jnp.int32)
    top = jnp.max(logits, -1)
    conf = top - jax.nn.logsumexp(logits, axis=-1)
    score = jnp.where(masked, conf, -jnp.inf)
    # rank of each position among its row's B by falling confidence
    ahead = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None])
        & (jnp.arange(B)[None, None, :] < jnp.arange(B)[None, :, None]))
    rank = jnp.sum(ahead, -1)
    fix = masked & (rank < n_fix[:, None])
    return jnp.where(fix, tok, ids), masked & ~fix
