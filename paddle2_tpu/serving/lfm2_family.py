"""The LFM2-MoE family's side of the serving seam (``model_runner.
ModelFamily``): what ``Lfm2MoeForCausalLM`` computes at prefill and at
one decode step, over TWO kinds of per-sequence state.

* The attention layers (1 in 4) keep keys and values in the paged pools,
  ``num_key_value_heads`` heads a token: the pools' leading axis counts
  those layers only, and the paged kernel reads query head ``h``
  against key/value head ``h // group``.
* The convolution layers keep the last ``conv_L_cache - 1`` values of
  ``z = B * X`` per sequence: one slot of the cache's ``conv`` pool
  ``[conv layers, slots + 1, taps - 1, hidden]`` (the family's ONE state
  kind, ``state_kinds``, in the cache's dtype). Prefill returns each
  conv layer's ``z`` at the REAL last positions (a runtime index, not
  the padded tail); a decode step gathers the batch's slots, steps the
  convolution, and scatters the shifted state back (pool donated).

Both steps hand back, behind the sampled tokens, each expert layer's
routing record: the counts (``count_names``) and the experts chosen for
every row.
"""

from __future__ import annotations

from ..incubate.moe import DroplessExperts
from .model_runner import ModelFamily
from .paged_attention import paged_attention_decode

__all__ = ["Lfm2MoeFamily"]


class Lfm2MoeFamily(ModelFamily):
    # engine features this family does not have yet
    unsupported = ("weight_only_int8", "weight_only_lm_head", "spec",
                   "enable_kv_spill")
    count_names = DroplessExperts.COUNT_NAMES

    def __init__(self, model):
        super().__init__(model)
        cfg = model.cfg
        layers = model.model.layers
        self.attn_layers = sum(1 for l in layers if not l.is_conv)
        self.conv_layers = sum(1 for l in layers if l.is_conv)
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.max_positions = cfg.max_position_embeddings
        self.state_kinds = {"conv": (
            (self.conv_layers, cfg.conv_L_cache - 1, cfg.hidden_size),
            None)} if self.conv_layers else None
        self.routed = (sum(1 for l in layers if not l.is_dense),
                       cfg.num_experts_per_tok)

    def prefill(self, ids, last_idx, interpret):
        import jax
        import jax.numpy as jnp
        trunk = self.model.model
        taps = self.model.cfg.conv_L_cache
        P = ids.shape[1]
        # the padded tail is not routed: it would only cost expert time
        valid = (jnp.arange(P) <= last_idx)[None]
        hidden, kvs, zs, counts = trunk.full(ids, valid, interpret)
        h_last = jax.lax.dynamic_index_in_dim(hidden[0], last_idx, 0)
        logits = trunk.head(h_last)                         # [1, V]
        with jax.named_scope("kv_write"):
            k_stack = jnp.stack([k[0] for k, _ in kvs])
            v_stack = jnp.stack([v[0] for _, v in kvs])
        with jax.named_scope("state_write"):
            # z at positions last_idx - (taps - 2) .. last_idx; zeros
            # stand before the sequence, as in the convolution itself
            states = (jnp.stack([jax.lax.dynamic_slice_in_dim(
                jnp.pad(z[0], ((taps - 1, 0), (0, 0))), last_idx + 1,
                taps - 1, 0) for z in zs]),) if zs else None
        return (logits, k_stack, v_stack, states,
                jnp.stack(counts) if counts else None)

    def decode(self, k_pool, v_pool, state_pools, ids, positions,
               block_tables, slots, block_size, interpret, split_pages):
        import jax
        import jax.numpy as jnp
        from .block_cache import PagedKVCache as _C
        trunk = self.model.model
        state_pool = state_pools[0] if state_pools else None
        B = ids.shape[0]
        phys = jnp.take_along_axis(
            block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
        slot = positions % block_size
        ctx = positions + 1
        # a padded row sits in the garbage slot: it is not routed
        valid = None if slots is None else slots > 0
        scope = jax.named_scope
        x = trunk.embed(ids[:, 0])                          # [B, H]
        ai = ci = 0
        counts = []
        for layer in trunk.layers:
            with scope(layer.op_scope):
                u = layer.pre_norm(layer.operator_norm, x)
                if layer.is_conv:
                    op, new = layer.conv.step(u, state_pool[ci, slots])
                    with scope("state_write"):
                        state_pool = state_pool.at[ci, slots].set(
                            new.astype(state_pool.dtype))
                    ci += 1
                else:
                    q, k, v = layer.self_attn.qkv(u[:, None],
                                                  positions[:, None])
                    with scope("kv_write"):
                        k_pool = _C.scatter_decode(k_pool, ai, phys, slot,
                                                   k[:, 0])
                        v_pool = _C.scatter_decode(v_pool, ai, phys, slot,
                                                   v[:, 0])
                    a = paged_attention_decode(
                        q, k_pool, v_pool, block_tables, ctx,
                        interpret=interpret, pages_per_split=split_pages,
                        layer=ai)
                    op = layer.self_attn.project(a.reshape(B, -1))
                    ai += 1
                x = x + op
            x, c = layer.feed(x, valid, interpret)
            if c is not None:
                counts.append(c)
        return (trunk.head(x), k_pool, v_pool,
                None if state_pool is None else (state_pool,),
                jnp.stack(counts) if counts else None)
