"""The Falcon-H1 family's side of the serving seam (``model_runner.
ModelFamily``): what ``FalconH1ForCausalLM`` computes at prefill and at
one decode step. EVERY layer holds both mixers, so every layer keeps
three things per sequence:

* keys and values in the paged pools (``num_key_value_heads`` heads a
  token; the paged kernel reads query head ``h`` against key/value head
  ``h // group``, a group of 5 at the published sizes);
* state kind ``conv``: the last ``mamba_d_conv - 1`` values of ``xBC``
  (after the µP multipliers, before the convolution), in the cache's
  dtype;
* state kind ``ssm``: the recurrent state ``[heads, d_head, d_state]``
  in FLOAT32 whatever the cache's dtype is (4 MB a layer and sequence at
  the published sizes).

Prefill: a prompt is padded to its bucket, and a recurrence — unlike
causal attention — is not exact under padding: the step ``dt`` is set
to 0 past the real last position BEFORE the scan, so the state stands
still there and the scan's final state IS the state at ``last_idx``;
the convolution state is sliced at ``last_idx`` as the LFM2 family
slices its ``z``. Decode: per layer one K/V append + paged attention
and one convolution step + one ``ssm_state_step`` (the Pallas kernel
updates the donated pool in place, the rows' slots scalar-prefetched)
on the SAME normed input, summed. A decode step moves the state, so a
discarded step's rows are re-prefilled by the engine (ROADMAP D13).
"""

from __future__ import annotations

from ..kernels.ssd import ssm_state_step, state_step_counts
from .model_runner import ModelFamily
from .paged_attention import paged_attention_decode

__all__ = ["FalconH1Family"]


class FalconH1Family(ModelFamily):
    # engine features this family does not have yet
    unsupported = ("weight_only_int8", "weight_only_lm_head", "spec",
                   "enable_kv_spill")

    def __init__(self, model):
        super().__init__(model)
        cfg = model.cfg
        L = cfg.num_hidden_layers
        self.attn_layers = L
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.max_positions = cfg.max_position_embeddings
        self.state_kinds = {
            "conv": ((L, cfg.mamba_d_conv - 1, cfg.conv_dim), None),
            "ssm": ((L, cfg.mamba_n_heads, cfg.mamba_d_head,
                     cfg.mamba_d_state), "float32")}

    def prefill_counts(self, padded: int) -> dict:
        return {"scan_chunks": -(-padded // self.model.cfg.mamba_chunk_size)}

    def kernel_page_counts(self, cache, tables, live_pages,
                           split_pages) -> dict:
        # and what a grid step of the state step holds, over the bucket
        return dict(super().kernel_page_counts(cache, tables, live_pages,
                                               split_pages),
                    **state_step_counts(tables.shape[0],
                                        self.state_kinds["ssm"][0],
                                        self.model.cfg.mamba_n_groups))

    def prefill(self, ids, last_idx, interpret):
        import jax
        import jax.numpy as jnp
        trunk = self.model.model
        taps = self.model.cfg.mamba_d_conv
        P = ids.shape[1]
        # past the real last position the recurrence stands still
        valid = (jnp.arange(P) <= last_idx)[None]
        hidden, kvs, states = trunk.full(ids, valid)
        h_last = jax.lax.dynamic_index_in_dim(hidden[0], last_idx, 0)
        logits = trunk.head(h_last)                         # [1, V]
        with jax.named_scope("kv_write"):
            k_stack = jnp.stack([k[0] for k, _ in kvs])
            v_stack = jnp.stack([v[0] for _, v in kvs])
        with jax.named_scope("state_write"):
            # xBC at positions last_idx - (taps - 2) .. last_idx; zeros
            # stand before the sequence, as in the convolution itself
            conv = jnp.stack([jax.lax.dynamic_slice_in_dim(
                jnp.pad(xbc[0], ((taps - 1, 0), (0, 0))), last_idx + 1,
                taps - 1, 0) for xbc, _ in states])
            ssm = jnp.stack([H[0] for _, H in states])
        return logits, k_stack, v_stack, (conv, ssm), None

    def decode(self, k_pool, v_pool, state_pools, ids, positions,
               block_tables, slots, block_size, interpret, split_pages):
        import jax
        import jax.numpy as jnp
        from .block_cache import PagedKVCache as _C
        trunk = self.model.model
        conv_pool, ssm_pool = state_pools
        B = ids.shape[0]
        phys = jnp.take_along_axis(
            block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
        slot = positions % block_size
        ctx = positions + 1
        scope = jax.named_scope
        x = trunk.embed(ids[:, 0])                          # [B, H]
        for li, layer in enumerate(trunk.layers):
            u = layer.mixer_input(x)
            with scope("ssm"):
                def recur(xh, Bm, Cm, dt, A, D):
                    nonlocal ssm_pool
                    ssm_pool, y = ssm_state_step(
                        ssm_pool, li, slots, xh, Bm, Cm, dt, A, D,
                        interpret=interpret)
                    return y

                ssm, window = layer.mamba.step(u, conv_pool[li, slots],
                                               recur)
                with scope("state_write"):
                    conv_pool = conv_pool.at[li, slots].set(
                        window.astype(conv_pool.dtype))
            with scope("attn"):
                q, k, v = layer.self_attn.qkv(
                    layer.attn_input(u)[:, None], positions[:, None])
                with scope("kv_write"):
                    k_pool = _C.scatter_decode(k_pool, li, phys, slot,
                                               k[:, 0])
                    v_pool = _C.scatter_decode(v_pool, li, phys, slot,
                                               v[:, 0])
                a = paged_attention_decode(
                    q, k_pool, v_pool, block_tables, ctx,
                    interpret=interpret, pages_per_split=split_pages,
                    layer=li)
                attn = layer.self_attn.project(a.reshape(B, -1))
            x = layer.feed(layer.mix(x, ssm, attn))
        return trunk.head(x), k_pool, v_pool, (conv_pool, ssm_pool), None
