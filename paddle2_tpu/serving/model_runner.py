"""Compiled prefill/decode programs for GPT-family models over the
paged KV cache.

The decode step cannot reuse ``GPTModel.decode_step`` (whose KV cache
is a growing per-layer concat — exactly the contiguous layout paging
replaces), so this runner re-wires one block step from the model's OWN
sublayers (ln_1 -> fused qkv -> paged append -> paged attention ->
out_proj -> mlp), mirroring ``GPTBlock.forward``'s head-major qkv
split. Prefill DOES go through ``decode_step`` (empty caches): it
computes every prompt position's K/V in one causal pass, and the
runner scatters them into the sequence's blocks.

Both paths are pure functions compiled with ``jax.jit``:

* weights ride as ARGUMENTS (the ``TracedProgram``/``_export_program``
  param-swap pattern) — never baked in as constants;
* the decode program is keyed by the scheduler's (batch, pages)
  bucket, so the program count is bounded by the bucket grid (the
  bench gate), and DONATES the KV pools for in-place append;
* prefill is keyed by the padded prompt length (rounded up to
  :data:`PREFILL_PAD`); causal masking makes the padded tail invisible
  to real rows, so padding is exact, and the real last position is a
  runtime index.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..profiler import build as _build_span, span as _span
from .paged_attention import paged_attention_decode

__all__ = ["PagedGPTRunner", "PREFILL_PAD"]

# prefill programs are compiled per padded length; 16-token rounding
# bounds their count at max_model_len/16 without wasting much compute
PREFILL_PAD = 16


class PagedGPTRunner:
    """Owns the compiled programs + the state plumbing for one
    ``GPTForCausalLM``. Greedy (argmax) decoding — sampling belongs to
    a later PR; greedy is what the eviction-exactness guarantee is
    stated for."""

    def __init__(self, model, num_heads: int, head_dim: int,
                 interpret: Optional[bool] = None,
                 split_pages: Optional[int] = None):
        from ..jit.functional import _collect_state
        self.model = model
        model.eval()
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.interpret = interpret
        # split-K width for the paged-attention kernel (None = the
        # kernel's VMEM-fit auto dispatch); rides into every compiled
        # decode program
        self.split_pages = split_pages
        params, buffers = _collect_state([model])
        self._state = params + buffers
        # hot-swap overlay: when set, these arrays (NOT the live model
        # tensors) ride as the programs' weight arguments — per-runner,
        # so engines sharing one model object swap independently
        self._swap_arrays: Optional[List] = None
        self._decode_programs: Dict[Tuple[int, int], object] = {}
        self._prefill_programs: Dict[int, object] = {}
        self._decode_costs: Dict[Tuple[int, int], Optional[dict]] = {}
        self._prefill_costs: Dict[int, Optional[dict]] = {}

    # -- state plumbing --------------------------------------------------
    def _weights(self) -> List:
        if self._swap_arrays is not None:
            return list(self._swap_arrays)
        return [t._data for t in self._state]

    def swap_weights(self, arrays) -> List:
        """Live weight hot-swap: replace the arrays every compiled
        program receives as its weight ARGUMENTS. Because weights ride
        as arguments (the ``TracedProgram`` pattern), a swap between
        decode steps is just different operands to the SAME compiled
        programs — no recompile, so the decode program census cannot
        grow (the zero-extra-programs half of the hot-swap gate).

        ``arrays`` must match the model state leaf-for-leaf (length,
        shape, dtype); any mismatch raises
        :class:`~.reliability.WeightSwapError` BEFORE anything is
        applied — a swap is atomic. Returns the previous weight list
        (the rollback payload)."""
        import jax.numpy as jnp
        from .reliability import WeightSwapError
        arrays = list(arrays)
        if len(arrays) != len(self._state):
            raise WeightSwapError(
                f"swap payload has {len(arrays)} leaves, model has "
                f"{len(self._state)}")
        staged = []
        for t, a in zip(self._state, arrays):
            a = jnp.asarray(a)
            if tuple(a.shape) != tuple(t._data.shape) \
                    or a.dtype != t._data.dtype:
                raise WeightSwapError(
                    f"swap leaf mismatch: got {a.shape}/{a.dtype}, "
                    f"model has {tuple(t._data.shape)}/{t._data.dtype}")
            staged.append(a)
        prev = self._weights()
        self._swap_arrays = staged
        return prev

    def _swapped(self, weight_arrays):
        """Context manager: point every model param/buffer at the
        traced arrays for the duration of a pure-function body."""
        runner = self

        class _Swap:
            def __enter__(self):
                self._orig = [t._data for t in runner._state]
                for t, a in zip(runner._state, weight_arrays):
                    t._data = a

            def __exit__(self, *exc):
                for t, a in zip(runner._state, self._orig):
                    t._data = a
                return False

        return _Swap()

    @property
    def num_decode_programs(self) -> int:
        return len(self._decode_programs)

    # -- prefill ---------------------------------------------------------
    @staticmethod
    def pad_len(n: int, max_pos: int) -> int:
        padded = -(-n // PREFILL_PAD) * PREFILL_PAD
        return min(padded, max_pos) if n <= max_pos else n

    def prefill_padded_len(self, n: int) -> int:
        """The padded length ``prefill`` will key its program/cost by —
        the ONE authoritative key (callers must not re-derive it with a
        different ceiling, or cost lookups silently miss)."""
        return self.pad_len(n, self.model.cfg.max_position_embeddings)

    def _build_prefill(self, padded_len: int):
        import jax
        import jax.numpy as jnp
        from ..framework import core
        from ..framework import random as fr
        from ..framework.tensor import Tensor
        model = self.model

        # the function's name is the HLO module's (jit_p2t_prefill);
        # the named scopes are the model's own plus head_ce / sample /
        # kv_write, as in the decode program below
        def p2t_prefill(weight_arrays, ids, last_idx):
            # ids: [1, padded_len] int32; last_idx: int32 scalar index
            # of the real last token (causal masking makes the padded
            # tail invisible to every real row)
            with self._swapped(weight_arrays), core.no_grad(), \
                    fr.scoped_rng(jax.random.PRNGKey(0)):
                n_layers = model.cfg.num_layers
                hidden, caches = model.gpt.decode_step(
                    Tensor(ids), [() for _ in range(n_layers)], 0)
                with jax.named_scope("head_ce"):
                    h_last = jnp.take_along_axis(
                        hidden._data, last_idx.reshape(1, 1, 1), axis=1)
                    logits = model._head(Tensor(h_last))
            with jax.named_scope("sample"):
                tok = jnp.argmax(logits._data[:, -1],
                                 axis=-1).astype(jnp.int32)
            with jax.named_scope("kv_write"):
                k_stack = jnp.stack([c[0]._data[0] for c in caches])
                v_stack = jnp.stack([c[1]._data[0] for c in caches])
            return tok, k_stack, v_stack        # [L, padded_len, H, D]

        return jax.jit(p2t_prefill)

    def prefill_dispatch(self, token_ids: List[int]):
        """Pad one sequence's prompt, move it to the device and call
        its prefill program (built, inside a ``build`` span, on first
        use of the padded length). Returns (first token ``[1]`` still
        on the device, k_stack, v_stack) with stacks ``[L, padded_len,
        H, D]`` — the caller scatters rows ``[:len(token_ids)]`` into
        blocks and reads the token back."""
        import jax.numpy as jnp
        n = len(token_ids)
        padded = self.prefill_padded_len(n)
        ids = np.zeros((1, padded), np.int32)
        ids[0, :n] = token_ids
        args = (self._weights(), jnp.asarray(ids),
                jnp.asarray(n - 1, jnp.int32))
        fn = self._prefill_programs.get(padded)
        if fn is not None:
            return fn(*args)
        fn = self._prefill_programs[padded] = self._build_prefill(padded)
        with _build_span("prefill", str(padded)) as b:
            out = fn(*args)
            with b.cost():
                self._prefill_costs[padded] = self._cost_of(fn, args)
        return out

    def prefill(self, token_ids: List[int]):
        """:meth:`prefill_dispatch` with the first token read back:
        (first_token:int, k_stack, v_stack)."""
        tok, k_stack, v_stack = self.prefill_dispatch(token_ids)
        return int(tok[0]), k_stack, v_stack

    # -- decode ----------------------------------------------------------
    def _build_decode(self, batch: int, n_pages: int, block_size: int):
        import jax
        import jax.numpy as jnp
        from ..framework import core
        from ..framework import random as fr
        from ..framework.tensor import Tensor
        model = self.model
        nh, hd = self.num_heads, self.head_dim

        def p2t_decode(weight_arrays, k_pool, v_pool, ids, positions,
                       block_tables):
            # ids [B,1] int32; positions [B] int32 (0-based slot of the
            # NEW token); block_tables [B,P] int32. Pools
            # [L, N, bs, H*D], donated.
            B = batch
            phys = jnp.take_along_axis(
                block_tables, (positions // block_size)[:, None],
                axis=1)[:, 0]
            slot = positions % block_size
            ctx = positions + 1
            scope = jax.named_scope     # GPTBlock.forward's names
            with self._swapped(weight_arrays), core.no_grad(), \
                    fr.scoped_rng(jax.random.PRNGKey(0)):
                with scope("embed"):
                    pos_t = Tensor(positions[:, None].astype(jnp.int32))
                    x = model.gpt.wte(Tensor(ids)) + model.gpt.wpe(pos_t)
                for li, block in enumerate(model.gpt.h):
                    with scope("attn"):
                        with scope("norm"):
                            ln1 = block.ln_1(x)
                        qkv = block.attn.qkv(ln1)
                        # head-major fused split, as GPTAttention.forward
                        qkv = qkv.reshape([B, 1, nh, 3, hd])
                        q, k, v = qkv.unbind(axis=3)
                    from .block_cache import PagedKVCache as _C
                    with scope("kv_write"):
                        k_pool = _C.scatter_decode(k_pool, li, phys, slot,
                                                   k._data[:, 0])
                        v_pool = _C.scatter_decode(v_pool, li, phys, slot,
                                                   v._data[:, 0])
                    with scope("attn"):
                        # the whole pool rides in; the layer is an index
                        # the kernel's copies take, never a sliced-out copy
                        attn = paged_attention_decode(
                            q._data, k_pool, v_pool, block_tables,
                            ctx, interpret=self.interpret,
                            pages_per_split=self.split_pages, layer=li)
                        a = block.attn.out_proj(
                            Tensor(attn.reshape(B, 1, nh * hd)))
                        x = x + block.dropout(a)
                    with scope("mlp"):
                        with scope("norm"):
                            ln2 = block.ln_2(x)
                        x = x + block.dropout(block.mlp(ln2))
                with scope("norm"):
                    x = model.gpt.ln_f(x)
                with scope("head_ce"):
                    logits = model._head(x)
            with scope("sample"):
                tok = jnp.argmax(logits._data[:, -1],
                                 axis=-1).astype(jnp.int32)
            return tok, k_pool, v_pool

        return jax.jit(p2t_decode, donate_argnums=(1, 2))

    def kernel_pages_per_block(self, cache, n_pages: int) -> int:
        """Pages the paged kernel gathers per step in the decode
        program of this page bucket (count on ``decode.dispatch``)."""
        from .paged_attention import kernel_pages_per_block
        return kernel_pages_per_block(
            n_pages, cache.k.shape[2], self.num_heads, self.head_dim,
            cache.k.dtype, self.split_pages)

    def decode(self, cache, ids, positions, block_tables):
        """One decode step over a bucketed batch: move it to the
        device, call its decode program (built, inside a ``build``
        span, on first use of the bucket), read the tokens back (span
        ``decode.readback``: the host waits out the step). ``cache`` is
        the :class:`~.block_cache.PagedKVCache` whose pools are donated
        and replaced. Returns int32 next tokens ``[B]``."""
        import jax.numpy as jnp
        B, n_pages = block_tables.shape
        key = (B, n_pages)
        args = (self._weights(), cache.k, cache.v,
                jnp.asarray(ids, jnp.int32),
                jnp.asarray(positions, jnp.int32),
                jnp.asarray(block_tables, jnp.int32))
        fn = self._decode_programs.get(key)
        if fn is not None:
            tok, cache.k, cache.v = fn(*args)
        else:
            from ..observability.cost_model import abstractify, program_cost
            fn = self._decode_programs[key] = self._build_decode(
                B, n_pages, cache.block_size)
            shapes = abstractify(args)      # the call donates the pools
            with _build_span("decode", f"{B}x{n_pages}") as b:
                tok, cache.k, cache.v = fn(*args)
                with b.cost():
                    self._decode_costs[key] = program_cost(fn, shapes)
        with _span("decode.readback"):
            return np.asarray(tok)

    # -- deterministic cost accounting (PR 7 cost model) -----------------
    @staticmethod
    def _cost_of(fn, args) -> Optional[dict]:
        from ..observability.cost_model import abstractify, program_cost
        return program_cost(fn, abstractify(args))

    def decode_cost(self, bucket: Tuple[int, int]) -> Optional[dict]:
        return self._decode_costs.get(tuple(bucket))

    def prefill_cost(self, padded_len: int) -> Optional[dict]:
        return self._prefill_costs.get(int(padded_len))
