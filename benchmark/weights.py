"""Seeded weights, made on the device in one jitted call.

Every leaf is drawn in float32 from the seed and rounded to bfloat16,
the type the cells train and serve in. The program is handed the bf16
arrays; a reference draws them again from the same seed and widens them
to float32, so both start from the same values and neither takes
anything the other made."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(spec_items, seed, dtype):
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, (name, shape, init, scale) in enumerate(spec_items):
        z = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * scale
        if init == "gain":
            z = 1.0 + z
        # reduce_precision, not a cast there and back: XLA may drop a
        # float32 -> bfloat16 -> float32 round trip as excess precision
        out[name] = jax.lax.reduce_precision(z, 8, 7).astype(dtype)
    return out


def make_weights(specs: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """{leaf: array of ``dtype``} — bf16-representable values either
    way. ``seed`` is folded to 32 bits (the driver's seeds exceed 2**31)."""
    items = tuple((k, tuple(s[0]), s[1], float(s[2]))
                  for k, s in sorted(specs.items()))
    return _make(items, jnp.uint32(seed % (2 ** 32)), dtype)
