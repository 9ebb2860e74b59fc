"""The Nemotron-H family's side of the serving seam (``model_runner.
ModelFamily``): what ``NemotronHForCausalLM`` computes at prefill and at
one decode step. A layer is ONE mixer (``hybrid_override_pattern``), so
the three things the engine keeps count three DIFFERENT sets of layers:

* the ``*`` layers keep keys and values in the paged pools (the pools'
  leading axis counts them; ``num_key_value_heads`` heads a token, the
  paged kernel reads query head ``h`` against key/value head ``h //
  group``, a group of 16 at the published sizes; nothing is rotated);
* the ``M`` layers keep two state kinds a sequence: ``conv``, the last
  ``conv_kernel - 1`` values of ``xBC`` before the convolution, in the
  cache's dtype, and ``ssm``, the recurrent state ``[heads, d_head,
  d_state]`` in FLOAT32 whatever the cache's dtype is (2 MB a layer and
  sequence at the published sizes);
* the ``E`` layers keep NOTHING per sequence; each hands back its
  routing record (the counts, and the experts chosen for every row).

The decode loop walks the schedule with three running indices. Prefill:
the padded tail is neither routed (``valid``) nor stepped (``dt`` 0 past
``last_idx``, so the scan's final state IS the state at ``last_idx``, as
``falcon_h1_family.py``); the convolution state is sliced at
``last_idx``. A decode step moves the state, so a discarded step's rows
are re-prefilled by the engine (ROADMAP D13).
"""

from __future__ import annotations

from ..incubate.moe import DroplessExperts
from ..kernels.ssd import ssm_state_step, state_step_counts
from .model_runner import ModelFamily
from .paged_attention import paged_attention_decode

__all__ = ["NemotronHFamily"]


class NemotronHFamily(ModelFamily):
    # engine features this family does not have yet
    unsupported = ("weight_only_int8", "weight_only_lm_head", "spec",
                   "enable_kv_spill")
    count_names = DroplessExperts.COUNT_NAMES

    def __init__(self, model):
        super().__init__(model)
        cfg = model.cfg
        kinds = cfg.layer_kinds
        self.layer_counts = {f"{kind}_layers": kinds.count(kind)
                             for kind in ("ssm", "attn", "moe")}
        M = self.layer_counts["ssm_layers"]
        self.attn_layers = self.layer_counts["attn_layers"]
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.max_positions = cfg.max_position_embeddings
        self.state_kinds = {
            "conv": ((M, cfg.conv_kernel - 1, cfg.conv_dim), None),
            "ssm": ((M, cfg.mamba_num_heads, cfg.mamba_head_dim,
                     cfg.ssm_state_size), "float32")} if M else None
        self.routed = (self.layer_counts["moe_layers"],
                       cfg.num_experts_per_tok)

    def prefill_counts(self, padded: int) -> dict:
        return dict(self.layer_counts,
                    scan_chunks=-(-padded // self.model.cfg.chunk_size))

    def kernel_page_counts(self, cache, tables, live_pages,
                           split_pages) -> dict:
        counts = dict(super().kernel_page_counts(cache, tables, live_pages,
                                                 split_pages),
                      **self.layer_counts)
        if self.state_kinds:
            # what a grid step of the state step holds, over the bucket
            counts.update(state_step_counts(
                tables.shape[0], self.state_kinds["ssm"][0],
                self.model.cfg.n_groups))
        return counts

    def prefill(self, ids, last_idx, interpret):
        import jax
        import jax.numpy as jnp
        trunk = self.model.model
        taps = self.model.cfg.conv_kernel
        P = ids.shape[1]
        # past the real last position nothing is routed and the
        # recurrence stands still
        valid = (jnp.arange(P) <= last_idx)[None]
        hidden, kvs, states, records = trunk.full(ids, valid, interpret)
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
        return (logits, k_stack, v_stack, (conv, ssm),
                jnp.stack(records) if records else None)

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
        # a padded row sits in the garbage slot: it is not routed
        valid = slots > 0
        scope = jax.named_scope
        x = trunk.embed(ids[:, 0])                          # [B, H]
        si = ai = 0
        records = []
        for layer in trunk.layers:
            if layer.kind == "moe":
                x, record = layer.feed(x, valid, interpret)
                records.append(record)
                continue
            with scope(layer.kind):
                u = layer.mixer_input(x)
                if layer.kind == "ssm":
                    def recur(xh, Bm, Cm, dt, A, D, li=si):
                        nonlocal ssm_pool
                        ssm_pool, y = ssm_state_step(
                            ssm_pool, li, slots, xh, Bm, Cm, dt, A, D,
                            interpret=interpret)
                        return y

                    out, window = layer.mixer.step(
                        u, conv_pool[si, slots], recur)
                    with scope("state_write"):
                        conv_pool = conv_pool.at[si, slots].set(
                            window.astype(conv_pool.dtype))
                    si += 1
                else:
                    q, k, v = layer.mixer.qkv(u[:, None], positions[:, None])
                    with scope("kv_write"):
                        k_pool = _C.scatter_decode(k_pool, ai, phys, slot,
                                                   k[:, 0])
                        v_pool = _C.scatter_decode(v_pool, ai, phys, slot,
                                                   v[:, 0])
                    a = paged_attention_decode(
                        q, k_pool, v_pool, block_tables, ctx,
                        interpret=interpret, pages_per_split=split_pages,
                        layer=ai)
                    out = layer.mixer.project(a.reshape(B, -1))
                    ai += 1
                x = x + out
        return (trunk.head(x), k_pool, v_pool, (conv_pool, ssm_pool),
                jnp.stack(records) if records else None)
