"""The EXAONE-MoE family's side of the serving seam (``model_runner.
ModelFamily``): what ``ExaoneMoeForCausalLM`` computes at prefill and at
one decode step. TWO kinds of keys and values in one model:

* a ``full_attention`` layer keeps every key and value in the paged
  pools (the pools' leading axis counts the global layers; nothing is
  rotated) and decodes through ``paged_attention_decode`` as every other
  family's attention does;
* a ``sliding_attention`` layer keeps a RING of ``sliding_window``
  positions a sequence, whatever the context: two state kinds
  (``ring_k``, ``ring_v``: ``[sliding layers, window, key/value heads x
  head_dim]`` a slot, in the cache's dtype; the slot id is the one the
  allocator hands out for any per-sequence state). The key and value of
  position ``p`` go to ring row ``p mod window``; keys are stored
  ROTATED, so the order of the rows does not matter, and ``min(p + 1,
  window)`` rows are live. A slot's ring viewed as consecutive pages
  lets the paged single-softmax body walk it (:func:`ring_walk`) — one
  copy of K and one of V a row — under a kernel name of its own
  (``window_decode``), so that the pattern ``paged_decode`` reads the
  global layers alone.

Prefill runs the whole prompt (a prefix hit and a re-prefill too), so it
rebuilds the ring: the last ``window`` rotated keys and values up to
``last_idx`` of each sliding layer, each at its row (scope
``state_write``); the global layers' go to their pages (``kv_write``);
positions past ``last_idx`` are neither routed nor written. Scopes:
``attn`` around both kinds, ``window`` inside it for a sliding layer.
"""

from __future__ import annotations

from ..incubate.moe import DroplessExperts
from .model_runner import ModelFamily
from .paged_attention import paged_attention_decode

__all__ = ["ExaoneMoeFamily", "ring_rows", "ring_walk"]

WINDOW_KERNEL = "window_decode"


def ring_rows(x, last_idx, window: int):
    """The ring after a prefill: x ``[P, W]`` (a sliding layer's keys or
    values, position-major) -> ``[window, W]``, row ``r`` holding the
    LAST position ``p <= last_idx`` with ``p mod window == r`` (zeros
    where the sequence has no such position yet)."""
    import jax.numpy as jnp
    r = jnp.arange(window)
    at = last_idx - (last_idx - r) % window
    rows = x[jnp.clip(at, 0, x.shape[0] - 1)]
    return jnp.where((at >= 0)[:, None], rows, jnp.zeros_like(rows))


def ring_walk(q, ring_k, ring_v, layer: int, slots, ctx, interpret=None):
    """Decode attention of sliding layer ``layer`` over the rows' rings
    ``[layers, slots + 1, window, W]``: a slot's ring seen as ``window /
    16`` consecutive pages of 16 rows, as the global pools' pages are (a
    window that 16 does not divide: one page), walked by the paged
    single-softmax body under the kernel name ``window_decode``."""
    import jax.numpy as jnp
    L, n_slots, window, width = ring_k.shape
    page = 16 if window % 16 == 0 else window
    pages = window // page
    as_pages = (L, n_slots * pages, page, width)
    tables = slots[:, None] * pages + jnp.arange(pages)[None]
    return paged_attention_decode(
        q, ring_k.reshape(as_pages), ring_v.reshape(as_pages), tables, ctx,
        interpret=interpret, layer=layer, name=WINDOW_KERNEL)


class ExaoneMoeFamily(ModelFamily):
    # engine features this family does not have yet
    unsupported = ("weight_only_int8", "weight_only_lm_head", "spec",
                   "enable_kv_spill")
    count_names = DroplessExperts.COUNT_NAMES

    def __init__(self, model):
        super().__init__(model)
        cfg = model.cfg
        layers = model.model.layers
        self.window = int(cfg.sliding_window)
        n_window = sum(layer.window is not None for layer in layers)
        self.attn_layers = len(layers) - n_window
        if not self.attn_layers:
            raise ValueError("serving needs at least one full_attention "
                             "layer (the paged pools count them)")
        self.window_layers = n_window
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.max_positions = cfg.max_position_embeddings
        width = self.num_kv_heads * self.head_dim
        ring = ((n_window, self.window, width), None)
        self.state_kinds = {"ring_k": ring, "ring_v": ring} \
            if n_window else None
        n_moe = sum(not layer.is_dense for layer in layers)
        self.routed = (n_moe, cfg.num_experts_per_tok) if n_moe else None

    def decode_counts(self, positions) -> dict:
        """``window_tokens``: the ring rows a step's real rows see, in
        each of ``window_layers`` layers."""
        import numpy as np
        return {"window_layers": self.window_layers,
                "window_tokens": int(np.minimum(positions + 1,
                                                self.window).sum())}

    def prefill(self, ids, last_idx, interpret):
        import jax
        import jax.numpy as jnp
        trunk = self.model.model
        P = ids.shape[1]
        # past the real last position nothing is routed
        valid = (jnp.arange(P) <= last_idx)[None]
        hidden, kvs, records = trunk.full(ids, valid, interpret)
        h_last = jax.lax.dynamic_index_in_dim(hidden[0], last_idx, 0)
        logits = trunk.head(h_last)                         # [1, V]
        full = [kv for kv, layer in zip(kvs, trunk.layers)
                if layer.window is None]
        sliding = [kv for kv, layer in zip(kvs, trunk.layers)
                   if layer.window is not None]
        with jax.named_scope("kv_write"):
            k_stack = jnp.stack([k[0] for k, _ in full])
            v_stack = jnp.stack([v[0] for _, v in full])
        state = None
        if sliding:
            with jax.named_scope("state_write"):
                state = tuple(jnp.stack([
                    ring_rows(kv[j][0].reshape(P, -1), last_idx, self.window)
                    for kv in sliding]) for j in (0, 1))
        return (logits, k_stack, v_stack, state,
                jnp.stack(records) if records else None)

    def decode(self, k_pool, v_pool, state_pools, ids, positions,
               block_tables, slots, block_size, interpret, split_pages):
        import jax
        import jax.numpy as jnp
        from .block_cache import PagedKVCache as _C
        trunk = self.model.model
        ring_k, ring_v = state_pools if state_pools else (None, None)
        B = ids.shape[0]
        phys = jnp.take_along_axis(
            block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
        slot = positions % block_size
        ctx = positions + 1
        ring_row = positions % self.window
        ring_ctx = jnp.minimum(ctx, self.window)
        # a padded row sits in the garbage slot: it is not routed
        valid = None if slots is None else slots > 0
        scope = jax.named_scope
        x = trunk.embed(ids[:, 0])                          # [B, H]
        wi = ai = 0
        records = []
        for layer in trunk.layers:
            with layer.attn_scope():
                q, k, v = layer.self_attn.qkv(
                    layer.attn_input(x)[:, None], positions[:, None])
                if layer.window is None:
                    with scope("kv_write"):
                        k_pool = _C.scatter_decode(k_pool, ai, phys, slot,
                                                   k[:, 0])
                        v_pool = _C.scatter_decode(v_pool, ai, phys, slot,
                                                   v[:, 0])
                    a = paged_attention_decode(
                        q, k_pool, v_pool, block_tables, ctx,
                        interpret=interpret, pages_per_split=split_pages,
                        layer=ai)
                    ai += 1
                else:
                    with scope("state_write"):
                        ring_k = ring_k.at[wi, slots, ring_row].set(
                            k.reshape(B, -1).astype(ring_k.dtype))
                        ring_v = ring_v.at[wi, slots, ring_row].set(
                            v.reshape(B, -1).astype(ring_v.dtype))
                    a = ring_walk(q, ring_k, ring_v, wi, slots, ring_ctx,
                                  interpret)
                    wi += 1
                x = x + layer.self_attn.project(a.reshape(B, -1))
            x, record = layer.feed(x, valid, interpret)
            if record is not None:
                records.append(record)
        return (trunk.head(x), k_pool, v_pool,
                None if state_pools is None else (ring_k, ring_v),
                jnp.stack(records) if records else None)
