"""The SDAR-MoE family's side of the serving seam (``model_runner.
ModelFamily``): what ``SdarMoeForCausalLM`` computes at prefill and at
one PASS over a block of positions (``serving/blockdiff.py`` says what
the passes are for).

* Prefill runs the prompt's WHOLE blocks under the block-causal mask
  and hands back their keys and values — and no token: the first
  block's tokens come out of its passes like every other's.
* A pass takes ``[rows, B]`` ids (``[MASK]`` where a position is not
  fixed yet) at positions ``start .. start + B``: per layer it writes
  the block's keys and values in place at the block's slots (the NEXT
  pass overwrites them, and finally the commit's stay), then ONE paged
  kernel call attends the B query positions of a sequence over one walk
  of its pages, every position's context ending at the block's end. A
  block never straddles a page (the cache's block size is a multiple of
  B: the engine asserts it), so its slots are one row of the table.

Both hand back each layer's routing record (counts, and the experts
chosen for every row), as the LFM2-MoE family does.
"""

from __future__ import annotations

from ..incubate.moe import DroplessExperts
from .model_runner import ModelFamily
from .paged_attention import paged_attention_decode

__all__ = ["SdarMoeFamily"]


class SdarMoeFamily(ModelFamily):
    # engine features this family does not have yet
    unsupported = ("weight_only_int8", "weight_only_lm_head", "spec",
                   "enable_kv_spill")
    count_names = DroplessExperts.COUNT_NAMES

    def __init__(self, model):
        super().__init__(model)
        cfg = model.cfg
        self.attn_layers = cfg.num_hidden_layers
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.max_positions = cfg.max_position_embeddings
        self.routed = (cfg.num_hidden_layers, cfg.num_experts_per_tok)
        self.block_length = cfg.block_length
        self.mask_token_id = cfg.mask_token_id

    def prefill(self, ids, last_idx, interpret):
        import jax
        import jax.numpy as jnp
        # the padded tail is not routed: it would only cost expert time
        # (whole blocks only, so no real row sees it either)
        valid = (jnp.arange(ids.shape[1]) <= last_idx)[None]
        _, kvs, records = self.model.model.full(ids, valid, interpret)
        with jax.named_scope("kv_write"):
            k_stack = jnp.stack([k[0] for k, _ in kvs])
            v_stack = jnp.stack([v[0] for _, v in kvs])
        return None, k_stack, v_stack, None, jnp.stack(records)

    def decode_block(self, k_pool, v_pool, ids, starts, block_tables, live,
                     block_size, interpret, split_pages):
        import jax
        import jax.numpy as jnp
        trunk = self.model.model
        R, B = ids.shape
        positions = starts[:, None] + jnp.arange(B)[None]       # [R, B]
        phys = jnp.take_along_axis(
            block_tables, (starts // block_size)[:, None], axis=1)
        slot = positions % block_size
        ctx = starts + B
        valid = jnp.broadcast_to(live[:, None], (R, B))
        scope = jax.named_scope
        x = trunk.embed(ids)                                    # [R, B, H]
        records = []
        for li, layer in enumerate(trunk.layers):
            with scope("attn"):
                q, k, v = layer.self_attn.qkv(layer.attn_norm(x), positions)
                with scope("kv_write"):
                    k_pool = k_pool.at[li, phys, slot].set(
                        k.reshape(R, B, -1))
                    v_pool = v_pool.at[li, phys, slot].set(
                        v.reshape(R, B, -1))
                a = paged_attention_decode(
                    q, k_pool, v_pool, block_tables, ctx,
                    interpret=interpret, pages_per_split=split_pages,
                    layer=li)
                x = x + layer.self_attn.project(a.reshape(R, B, -1))
            x, record = layer.feed(x, valid, interpret)
            records.append(record)
        return self.model.head(x), k_pool, v_pool, jnp.stack(records)
