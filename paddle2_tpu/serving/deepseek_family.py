"""The DeepSeek-V2 family's side of the serving seam (``model_runner.
ModelFamily``): what ``DeepseekV2ForCausalLM`` computes at prefill and
at one decode step over a LATENT cache.

The cache is ONE pool (``kv_widths = (row width, 0)``: no V pool, and
per-head keys and values are never stored): a token's row of a layer is
``[c | RoPE(k_rope) | zeros]`` — ``kv_lora_rank + qk_rope_head_dim``
numbers (512 + 64: 1,152 B in bf16) and zeros up to whole 128-lane
groups (640 lanes, 1,280 B: the chip's copies address the minor axis in
groups of 128), which is what the allocator's ledger and
``kv_pool_live_pct`` count.

* Prefill EXPANDS: per head ``[k_nope | v] = c W_kvb``, flash attention
  with query/key width ``qk_nope + qk_rope`` and value width
  ``v_head_dim`` (``models/deepseek.LatentAttention.full``), and hands
  back the rows to scatter.
* A decode step ABSORBS: ``q'_h = [q_nope W_UK^T | RoPE(q_rope)]``, the
  new token's row written in place, then ``paged_mla_decode`` — all
  query heads against the one shared head, each live page read once for
  scores and values —, ``a_h = o_h W_UV``, ``W_o``.

Both hand back each expert layer's routing record (counts, and the
experts chosen for every row), as the other routed families do.
"""

from __future__ import annotations

from ..incubate.moe import DroplessExperts
from .block_cache import GARBAGE_BLOCK
from .model_runner import ModelFamily
from .paged_attention import (coalesced_pages, mla_pages_per_block,
                              mla_pages_per_copy, mla_row_width,
                              paged_mla_decode)

__all__ = ["DeepseekV2Family"]


class DeepseekV2Family(ModelFamily):
    # engine features this family does not have yet
    unsupported = ("weight_only_int8", "weight_only_lm_head", "spec",
                   "enable_kv_spill")
    count_names = DroplessExperts.COUNT_NAMES

    def __init__(self, model):
        super().__init__(model)
        cfg = model.cfg
        self.attn_layers = cfg.num_hidden_layers
        self.num_heads = cfg.num_attention_heads
        # the one shared head the cache keeps of a token
        self.num_kv_heads = 1
        self.head_dim = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        self.kv_widths = (mla_row_width(cfg.kv_lora_rank,
                                        cfg.qk_rope_head_dim), 0)
        self.max_positions = cfg.max_position_embeddings
        self.routed = (cfg.num_hidden_layers - cfg.first_k_dense_replace,
                       cfg.num_experts_per_tok)

    def kernel_page_counts(self, cache, tables, live_pages, split_pages):
        shape = (tables.shape[1], cache.block_size, self.kv_widths[0],
                 cache.dtype)
        return dict(
            kernel_pages_per_block=mla_pages_per_block(*shape),
            # of the step's live pages, those the kernel fetches a run
            # of consecutive pages at a time
            coalesced_pages=coalesced_pages(
                tables[:len(live_pages)], live_pages,
                mla_pages_per_copy(*shape)))

    def _rows(self, c, k_rope):
        """The pool's rows ``[..., W]`` of latents ``c`` and rotary keys
        ``k_rope``."""
        import jax.numpy as jnp
        pad = self.kv_widths[0] - self.head_dim
        return jnp.concatenate(
            [c, k_rope, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)], -1)

    def prefill(self, ids, last_idx, interpret):
        import jax
        import jax.numpy as jnp
        P = ids.shape[1]
        # the padded tail is not routed: it would only cost expert time
        valid = (jnp.arange(P) <= last_idx)[None]
        hidden, latents, records = self.model.model.full(ids, valid,
                                                         interpret)
        h_last = jax.lax.dynamic_index_in_dim(hidden[0], last_idx, 0)
        logits = self.model.head(h_last)                    # [1, V]
        with jax.named_scope("kv_write"):
            stack = jnp.stack([self._rows(c[0], r[0])[:, None]
                               for c, r in latents])        # [L, P, 1, W]
        return (logits, stack, None, None,
                jnp.stack(records) if records else None)

    def decode(self, k_pool, v_pool, state_pools, ids, positions,
               block_tables, slots, block_size, interpret, split_pages):
        import jax
        import jax.numpy as jnp
        from .block_cache import PagedKVCache as _C
        model = self.model
        phys = jnp.take_along_axis(
            block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
        slot = positions % block_size
        ctx = positions + 1
        # a padded row's table is all garbage block: it is not routed
        valid = block_tables[:, 0] != GARBAGE_BLOCK
        scope = jax.named_scope
        x = model.model.embed(ids[:, 0])                    # [B, H]
        records = []
        for li, layer in enumerate(model.model.layers):
            attn = layer.self_attn
            with scope("attn"):
                u = layer.attn_norm(x)
                q_nope, q_rope = attn.queries(u, positions)
                c, k_rope = attn.latent(u, positions)
                with scope("kv_write"):
                    k_pool = _C.scatter_decode(
                        k_pool, li, phys, slot, self._rows(c, k_rope))
                o = paged_mla_decode(
                    attn.absorb(q_nope), q_rope, k_pool, block_tables, ctx,
                    attn.scale, interpret=interpret, layer=li)
                x = x + attn.project(attn.unabsorb(o))
            x, record = layer.feed(x, valid, interpret)
            if record is not None:
                records.append(record)
        return (model.head(x), k_pool, v_pool, state_pools,
                jnp.stack(records) if records else None)
