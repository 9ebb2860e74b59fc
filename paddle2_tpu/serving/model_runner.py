"""Compiled prefill/decode programs over the paged KV cache: ONE copy
of the plumbing (:class:`PagedRunner`) and, per model family, the few
functions that say what the model computes (:class:`ModelFamily`).

The plumbing, the same for every family — both paths are pure
functions compiled with ``jax.jit``:

* weights ride as ARGUMENTS (the ``TracedProgram``/``_export_program``
  param-swap pattern) — never baked in as constants;
* the decode program is keyed by the scheduler's (batch, pages)
  bucket, so the program count is bounded by the bucket grid (the
  bench gate), and DONATES the KV pools (and the state pools, where the
  family keeps any) for in-place append;
* prefill is keyed by the padded prompt length (rounded up to
  :data:`PREFILL_PAD`); causal masking makes the padded tail invisible
  to real rows, so padding is exact, and the real last position is a
  runtime index;
* the family's step functions return LOGITS; sampling (greedy) is the
  runner's thin wrapper around them, so a test can call the family's
  step under :meth:`PagedRunner.bound` and compare logits;
* a decode step's tokens also stay ON THE DEVICE (``cache.tokens``),
  where the next step can take a row's input from them: the engine
  enqueues step n+1 before it has read step n back — and a prefill's
  first token likewise (``cache.firsts``): the step that consumes it is
  enqueued before the host reads it;
* a family that generates by diffusion over BLOCKS (``block_length``;
  ``serving/blockdiff.py``) has a decode program of another signature:
  a row is a sequence's block of B positions, the program's sampling is
  "argmax + confidence + choose + write back" (scope ``unmask``), and
  what stays on the device for the next pass is the block in flight
  (``cache.block_ids`` / ``cache.block_masked``); its prefill yields
  keys and values and no token;
* builds run inside ``build`` spans and leave cost records.

A family supplies its cache geometry (how many layers keep keys and
values, how many key/value heads of what size, and the kinds of
fixed-size per-sequence state with their shapes and types) and
``prefill`` / ``decode``.
:class:`GPTFamily` re-wires one GPT block step from the model's OWN
sublayers (ln_1 -> fused qkv -> paged append -> paged attention ->
out_proj -> mlp), mirroring ``GPTBlock.forward``'s head-major qkv
split, because ``GPTModel.decode_step``'s cache is a growing per-layer
concat — exactly the contiguous layout paging replaces. Its prefill
DOES go through ``decode_step`` (empty caches). The LFM2-MoE family is
in ``lfm2_family.py``, the SDAR-MoE family in ``sdar_family.py``, the
DeepSeek-V2 family (latent attention: one pool) in
``deepseek_family.py``, the Falcon-H1 family (a state-space mixer beside
attention in every layer: two state kinds) in ``falcon_h1_family.py``,
the Nemotron-H family (ONE mixer a layer: state, K/V and the routing
record count three different sets of layers) in
``nemotron_h_family.py``, the EXAONE-MoE family (sliding-window layers
keep a ring of keys and values as state, global layers page) in
``exaone_moe_family.py``.
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..profiler import build as _build_span, launch as _launch
from .paged_attention import paged_attention_decode

__all__ = ["ModelFamily", "GPTFamily", "PagedRunner", "PagedGPTRunner",
           "served_classes", "PREFILL_PAD"]

# the HLO modules of the two jitted entries ("jit_" + the function's
# name): what a device trace calls their executions, and the key their
# launch ordinals are counted under (``profiler.launch``)
PREFILL_MODULE = "jit_p2t_prefill"
DECODE_MODULE = "jit_p2t_decode"

# prefill programs are compiled per padded length; 16-token rounding
# bounds their count at max_model_len/16 without wasting much compute
PREFILL_PAD = 16


class ModelFamily:
    """What a model family tells the runner and the engine.

    Geometry: ``attn_layers`` (layers that keep keys and values — the
    pools' leading axis), ``num_heads`` / ``num_kv_heads`` /
    ``head_dim``, ``max_positions``, and ``state_kinds``: ``None``, or
    an ordered mapping ``name -> ((state layers, *per-layer shape),
    dtype or None for the cache's)`` of the fixed-size states every
    running sequence keeps beside its blocks (one slot, the same id in
    every kind's pool: ``PagedKVCache.states``). ``kv_widths``:
    ``None``, or the pools' row widths where they are not
    ``num_kv_heads * head_dim`` twice (``PagedKVCache``: a latent cache
    has ONE pool, ``(row width, 0)``, and its ``v`` stack and pool are
    ``None`` everywhere below). ``unsupported`` names the
    :class:`EngineConfig` features the family cannot serve yet (the
    engine refuses them at construction).

    Steps, traced inside the runner's programs with the weights bound
    (``interpret`` / ``split_pages`` are the runner's kernel options):

    ``prefill(ids [1, P], last_idx, interpret)`` -> ``(logits [1, V] of
    the real last position, k_stack, v_stack [attn_layers, P, H_kv, D],
    states — a tuple in the kinds' order, each [state layers, ...] at
    the REAL last position — or None, counts or None)``;

    ``decode(k_pool, v_pool, state_pools, ids [B, 1], positions [B],
    block_tables [B, pages], slots [B] or None, block_size, interpret,
    split_pages)`` -> ``(logits [B, V], k_pool, v_pool, state_pools,
    counts)``, ``state_pools`` the tuple of the kinds' pools (None
    without any). A decode step MOVES such state: a step whose tokens
    are discarded cannot be repeated on it (the engine re-prefills its
    rows, ``ServingEngine._reprefill_moved``).

    A family that generates by diffusion over blocks sets
    ``block_length`` (B) and ``mask_token_id``; its prefill returns
    ``None`` for the logits (it yields no token, and keeps the keys and
    values of the prompt's WHOLE blocks only: :meth:`prefill_keeps`),
    and in place of ``decode`` it has

    ``decode_block(k_pool, v_pool, ids [R, B], starts [R], block_tables
    [R, pages], live [R] bool, block_size, interpret, split_pages)`` ->
    ``(logits [R, B, V], k_pool, v_pool, counts)``: the block's B
    positions from ``starts`` on, seeing the cache before them and each
    other in both directions, their keys and values written in place.

    ``counts`` is the int32 array the program hands back with the
    tokens, or None: per expert layer of a routed model (``routed`` =
    (expert layers, experts a token)) the routing counts that
    ``count_names`` names and, behind them, the experts chosen for each
    row (``DroplessExperts.route_and_run``'s record)."""

    state_kinds: Optional[Dict[str, tuple]] = None
    kv_widths: Optional[Tuple[int, int]] = None
    unsupported: Tuple[str, ...] = ()
    count_names: Tuple[str, ...] = ()
    routed: Optional[Tuple[int, int]] = None
    block_length: Optional[int] = None
    mask_token_id: Optional[int] = None

    def __init__(self, model):
        self.model = model

    @property
    def row_positions(self) -> int:
        """Positions a decode row carries, and tokens it can yield."""
        return self.block_length or 1

    def prefill_keeps(self, n: int) -> int:
        """Of ``n`` tokens, how many leading ones a prefill computes and
        keeps keys and values of: all, or a block family's whole blocks
        (the rest opens its first block in flight)."""
        return n - n % self.row_positions

    def prefill(self, ids, last_idx, interpret):
        raise NotImplementedError

    def prefill_counts(self, padded: int) -> dict:
        """What the admission's ``prefill`` span says of the family's
        own work on a prompt padded to ``padded`` (nothing by default)."""
        return {}

    def decode_counts(self, positions) -> dict:
        """What ``decode.dispatch`` says of the family's own work on a
        step whose real rows stand at ``positions`` (int array; a row at
        position p sees p + 1 keys): nothing by default."""
        return {}

    def decode(self, k_pool, v_pool, state_pools, ids, positions,
               block_tables, slots, block_size, interpret, split_pages):
        raise NotImplementedError

    def kernel_page_counts(self, cache, tables, live_pages,
                           split_pages) -> dict:
        """What ``decode.dispatch`` says of the paged kernel's work on a
        step's ``tables`` (``[rows, pages]``, ``live_pages`` a row):
        ``kernel_pages_per_block``, the pages the family's kernel
        gathers per compute block in this page bucket's program, and
        ``coalesced_pages``, those of the live pages it fetches a run
        of consecutive pages at a time (a family adds what else the
        span should say of its step)."""
        from .paged_attention import (coalesced_pages, kernel_pages_per_block,
                                      kernel_pages_per_copy)
        # a block's positions ride the query tile as so many more heads
        shape = (tables.shape[1], cache.block_size,
                 self.num_heads * self.row_positions, self.head_dim,
                 cache.dtype, split_pages, self.num_kv_heads)
        return dict(
            kernel_pages_per_block=kernel_pages_per_block(*shape),
            coalesced_pages=coalesced_pages(
                tables[:len(live_pages)], live_pages,
                kernel_pages_per_copy(*shape, cache.num_blocks)))


class GPTFamily(ModelFamily):
    """``GPTForCausalLM``: every layer keeps keys and values, as many
    key/value heads as query heads, learned positions, no other
    state."""

    def __init__(self, model):
        super().__init__(model)
        cfg = model.cfg
        if getattr(cfg, "stacked_blocks", False):
            raise ValueError(
                "serving requires addressable blocks; rebuild with "
                "stacked_blocks=False (the decode program wires the "
                "paged append between qkv and attention per block)")
        self.attn_layers = cfg.num_layers
        self.num_heads = self.num_kv_heads = cfg.num_heads
        self.head_dim = cfg.head_dim
        self.max_positions = cfg.max_position_embeddings

    def prefill(self, ids, last_idx, interpret):
        import jax
        import jax.numpy as jnp
        from ..framework.tensor import Tensor
        model = self.model
        # the named scopes are the model's own plus head_ce / kv_write
        n_layers = model.cfg.num_layers
        hidden, caches = model.gpt.decode_step(
            Tensor(ids), [() for _ in range(n_layers)], 0)
        with jax.named_scope("head_ce"):
            h_last = jnp.take_along_axis(
                hidden._data, last_idx.reshape(1, 1, 1), axis=1)
            logits = model._head(Tensor(h_last))._data[:, -1]
        with jax.named_scope("kv_write"):
            k_stack = jnp.stack([c[0]._data[0] for c in caches])
            v_stack = jnp.stack([c[1]._data[0] for c in caches])
        return logits, k_stack, v_stack, None, None

    def decode(self, k_pool, v_pool, state_pools, ids, positions,
               block_tables, slots, block_size, interpret, split_pages):
        import jax
        import jax.numpy as jnp
        from ..framework.tensor import Tensor
        from .block_cache import PagedKVCache as _C
        model = self.model
        nh, hd = self.num_heads, self.head_dim
        B = ids.shape[0]
        phys = jnp.take_along_axis(
            block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
        slot = positions % block_size
        ctx = positions + 1
        scope = jax.named_scope     # GPTBlock.forward's names
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
                    ctx, interpret=interpret,
                    pages_per_split=split_pages, layer=li)
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
            logits = model._head(x)._data[:, -1]
        return logits, k_pool, v_pool, state_pools, None


def served_classes(config) -> tuple:
    """(causal-LM class, serving family class) of a config object: the
    ONE place that says which models the engine serves. The engine
    rebuilds an artifact's architecture with the first and the runner
    reads the model through the second."""
    from ..models.deepseek import DeepseekV2Config, DeepseekV2ForCausalLM
    from ..models.exaone_moe import ExaoneMoeConfig, ExaoneMoeForCausalLM
    from ..models.falcon_h1 import FalconH1Config, FalconH1ForCausalLM
    from ..models.gpt import GPTConfig, GPTForCausalLM
    from ..models.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
    from ..models.nemotron_h import NemotronHConfig, NemotronHForCausalLM
    from ..models.sdar import SdarMoeConfig, SdarMoeForCausalLM
    from .deepseek_family import DeepseekV2Family
    from .exaone_moe_family import ExaoneMoeFamily
    from .falcon_h1_family import FalconH1Family
    from .lfm2_family import Lfm2MoeFamily
    from .nemotron_h_family import NemotronHFamily
    from .sdar_family import SdarMoeFamily
    for config_class, classes in (
            (GPTConfig, (GPTForCausalLM, GPTFamily)),
            (Lfm2MoeConfig, (Lfm2MoeForCausalLM, Lfm2MoeFamily)),
            (SdarMoeConfig, (SdarMoeForCausalLM, SdarMoeFamily)),
            (DeepseekV2Config, (DeepseekV2ForCausalLM, DeepseekV2Family)),
            (FalconH1Config, (FalconH1ForCausalLM, FalconH1Family)),
            (NemotronHConfig, (NemotronHForCausalLM, NemotronHFamily)),
            (ExaoneMoeConfig, (ExaoneMoeForCausalLM, ExaoneMoeFamily))):
        if isinstance(config, config_class):
            return classes
    raise TypeError(
        f"no serving family for config {type(config).__name__}")


class PagedRunner:
    """Owns the compiled programs + the weight plumbing for one model,
    read through its :class:`ModelFamily` (:func:`served_classes`).
    Greedy (argmax) decoding — sampling belongs to a later PR; greedy
    is what the eviction-exactness guarantee is stated for."""

    def __init__(self, model, interpret: Optional[bool] = None,
                 split_pages: Optional[int] = None):
        from ..jit.functional import _collect_state
        self.family = served_classes(model.cfg)[1](model)
        self.model = model
        model.eval()
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

    @contextlib.contextmanager
    def bound(self, weight_arrays=None):
        """Bind the model's tensors to ``weight_arrays`` (default: the
        current weights), without gradients: inside it the family's
        step functions can be traced, or called eagerly."""
        import jax
        from ..framework import core
        from ..framework import random as fr
        if weight_arrays is None:
            weight_arrays = self._weights()
        with self._swapped(weight_arrays), core.no_grad(), \
                fr.scoped_rng(jax.random.PRNGKey(0)):
            yield

    @staticmethod
    def _sample(logits, counts):
        """Greedy tokens ``[B]`` (one zero where a family's prefill
        yields no token: ``logits`` None); a family's counts ride behind
        them in the SAME int32 array, so the one read-back brings both."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope("sample"):
            tok = jnp.zeros((1,), jnp.int32) if logits is None else \
                jax.lax.argmax(logits, logits.ndim - 1, jnp.int32)
        return PagedRunner._with_counts(tok, counts)

    @staticmethod
    def _with_counts(tok, counts):
        import jax.numpy as jnp
        if counts is None:
            return tok
        return jnp.concatenate([tok, counts.reshape(-1).astype(jnp.int32)])

    def split_counts(self, out, n_rows: int):
        """(tokens ``[n_rows]``, {count name: per-layer list}, experts
        chosen ``[rows routed, expert layers, k]``) of a program's
        int32 array, which is READ BACK here (the host waits for the
        step that made it); (tokens, None, None) for a family that
        routes nothing. A block family's rows are positions, B a
        sequence, and a token below zero is a position still masked."""
        out = np.asarray(out)
        if out.shape[0] == n_rows:
            return out, None, None
        layers, k = self.family.routed
        names = self.family.count_names
        rec = out[n_rows:].reshape(layers, -1)
        chosen = rec[:, len(names):].reshape(layers, -1, k)
        return (out[:n_rows],
                {name: rec[:, i].tolist() for i, name in enumerate(names)},
                chosen.transpose(1, 0, 2))

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
        return self.pad_len(n, self.family.max_positions)

    def _build_prefill(self, padded_len: int):
        import jax
        family = self.family

        # the function's name is the HLO module's (jit_p2t_prefill)
        def p2t_prefill(weight_arrays, ids, last_idx):
            # ids: [1, padded_len] int32; last_idx: int32 scalar index
            # of the real last token (causal masking makes the padded
            # tail invisible to every real row)
            with self.bound(weight_arrays):
                logits, k_stack, v_stack, state, counts = family.prefill(
                    ids, last_idx, self.interpret)
            out = (self._sample(logits, counts), k_stack, v_stack)
            return out if state is None else out + tuple(state)

        return jax.jit(p2t_prefill)

    def prefill_dispatch(self, token_ids: List[int]):
        """Pad one sequence's prompt, move it to the device and call
        its prefill program (built, inside a ``build`` span, on first
        use of the padded length). Returns (first token ``[1]`` still
        on the device — with the family's counts behind it, see
        :meth:`split_counts` —, k_stack, v_stack, and the family's
        states, one a kind, where it keeps any) with stacks ``[attn layers,
        padded_len, H_kv, D]`` — the caller scatters rows
        ``[:len(token_ids)]`` into blocks and reads the token back."""
        import jax.numpy as jnp
        n = len(token_ids)
        padded = self.prefill_padded_len(n)
        ids = np.zeros((1, padded), np.int32)
        ids[0, :n] = token_ids
        args = (self._weights(), jnp.asarray(ids),
                jnp.asarray(n - 1, jnp.int32))
        fn = self._prefill_programs.get(padded)
        if fn is not None:
            out = fn(*args)
        else:
            fn = self._prefill_programs[padded] = \
                self._build_prefill(padded)
            with _build_span("prefill", str(padded)) as b:
                out = fn(*args)
                with b.cost():
                    self._prefill_costs[padded] = self._cost_of(fn, args)
        _launch(PREFILL_MODULE)
        return out

    def prefill(self, token_ids: List[int]):
        """:meth:`prefill_dispatch` with the first token read back:
        (first_token:int, k_stack, v_stack[, state])."""
        out = self.prefill_dispatch(token_ids)
        return (int(out[0][0]),) + tuple(out[1:])

    # -- decode ----------------------------------------------------------
    def _build_block_decode(self, block_size: int):
        """The decode program of a block-diffusion family: ONE program
        serves rows in different phases (a denoise pass, the commit)."""
        import jax
        import jax.numpy as jnp
        from .blockdiff import unmask_low_confidence
        family = self.family
        B = family.block_length

        def p2t_decode(weight_arrays, k_pool, v_pool, held_ids, held_masked,
                       meta, block_tables):
            # a row is a sequence's block of B positions. meta [R, 4 +
            # 2B] int32, per row: where its block waits on the device (a
            # row of held_ids / held_masked [rows, B], the step before's,
            # which the host has not read) or -1: the block is the
            # host's, in the row's last 2B columns (ids, then masked:
            # a fresh block is all masked); how many positions this pass
            # fixes (0: a commit); the block's first position; is the row
            # a sequence at all. Pools [L, N, bs, H_kv*D], donated.
            with jax.named_scope("embed"):
                src, n_fix, starts = meta[:, 0], meta[:, 1], meta[:, 2]
                live = meta[:, 3] > 0
                own = (src < 0)[:, None]
                at = jnp.clip(src, 0, held_ids.shape[0] - 1)
                ids = jnp.where(own, meta[:, 4:4 + B], held_ids[at])
                masked = jnp.where(own, meta[:, 4 + B:] > 0, held_masked[at])
                fed = jnp.where(masked, family.mask_token_id, ids)
            with self.bound(weight_arrays):
                logits, k_pool, v_pool, counts = family.decode_block(
                    k_pool, v_pool, fed, starts, block_tables, live,
                    block_size, self.interpret, self.split_pages)
            with jax.named_scope("unmask"):
                ids, masked = unmask_low_confidence(logits, ids, masked,
                                                    n_fix)
                # one read-back: a position still masked reads -1
                tok = self._with_counts(
                    jnp.where(masked, -1, ids).reshape(-1), counts)
                pad = ((0, held_ids.shape[0] - ids.shape[0]), (0, 0))
                held = (jnp.pad(ids, pad), jnp.pad(masked, pad))
            return (tok,) + held + (k_pool, v_pool)

        return jax.jit(p2t_decode, donate_argnums=(1, 2))

    def _build_decode(self, batch: int, n_pages: int, block_size: int):
        import jax
        import jax.numpy as jnp
        family = self.family
        if family.block_length is not None:
            return self._build_block_decode(block_size)

        def p2t_decode(weight_arrays, k_pool, v_pool, fed, firsts, ids,
                       positions, block_tables, *state_args):
            # ids [B,1] int32; positions [B] int32 (0-based slot of the
            # NEW token); block_tables [B,P] int32. Pools
            # [L, N, bs, H_kv*D], donated. A family with per-sequence
            # state adds (a pool [Ls, slots+1, ...] a kind, donated,
            # then slots [B] int32). fed [R] int32: the tokens of the
            # step before,
            # firsts [R] int32: first tokens of prefills, neither read
            # by the host so far; an id below zero is a row of the two
            # end to end (-1 - row) and not a token.
            state_pools, slots = (state_args[:-1], state_args[-1]) \
                if state_args else (None, None)
            with jax.named_scope("embed"):
                held = jnp.concatenate([fed, firsts])
                taken = held[jnp.clip(-1 - ids, 0, held.shape[0] - 1)]
                ids = jnp.where(ids < 0, taken, ids)
            with self.bound(weight_arrays):
                logits, k_pool, v_pool, state_pools, counts = family.decode(
                    k_pool, v_pool, state_pools, ids, positions,
                    block_tables, slots, block_size, self.interpret,
                    self.split_pages)
            tok = self._sample(logits, counts)
            with jax.named_scope("sample"):
                # one width whatever the bucket, so that any program
                # can follow any other without a new signature
                fed = jnp.pad(tok[:batch], (0, fed.shape[0] - batch))
            out = (tok, fed, k_pool, v_pool)
            return out if state_pools is None else out + tuple(state_pools)

        # the kinds' pools ride behind the eight fixed arguments
        donate = (1, 2) + tuple(range(8, 8 + len(family.state_kinds or ())))
        return jax.jit(p2t_decode, donate_argnums=donate)

    def kernel_page_counts(self, cache, tables, live_pages) -> dict:
        """The paged kernel's counts on ``decode.dispatch`` for a step
        over ``tables`` (the family's: pages per compute block; a latent
        cache's also the pages that arrive a run at a time)."""
        return self.family.kernel_page_counts(cache, tables, live_pages,
                                              self.split_pages)

    def _decode_args(self, cache, *arrays):
        import jax.numpy as jnp
        if self.family.block_length is not None:
            meta, block_tables = arrays
            return (self._weights(), cache.k, cache.v, cache.block_ids,
                    cache.block_masked, jnp.asarray(meta, jnp.int32),
                    jnp.asarray(block_tables, jnp.int32))
        ids, positions, block_tables = arrays[:3]
        args = (self._weights(), cache.k, cache.v, cache.tokens,
                cache.firsts, jnp.asarray(ids, jnp.int32),
                jnp.asarray(positions, jnp.int32),
                jnp.asarray(block_tables, jnp.int32))
        if cache.states:
            args += tuple(cache.states.values()) \
                + (jnp.asarray(arrays[3], jnp.int32),)
        return args

    def decode(self, cache, *arrays):
        """One decode step over a bucketed batch: move it to the
        device and call its decode program (built, inside a ``build``
        span, on first use of the bucket). NOTHING is read back: the
        step is enqueued and the call returns. ``cache`` is the
        :class:`~.block_cache.PagedKVCache` whose pools are donated and
        replaced, and whose ``tokens`` (the step's tokens, kept on the
        device) feed the next step: an entry of ``ids`` below zero is
        ``-1 - row`` of the step BEFORE this one and not a token id — or,
        from ``len(cache.tokens)`` on, a row of ``cache.firsts``, where
        the prefills' first tokens wait that the host has not read.
        ``arrays`` are ``ids, positions, block_tables`` and, where the
        family keeps per-sequence state, the rows' state ``slots``; for
        a block-diffusion family ``meta, block_tables`` (the program's
        comment says what a row of ``meta`` holds), and what stays on
        the device is the block in flight of every row. Returns the
        program's int32 array, still on the device: next tokens ``[B]``
        (a block family's: the rows' blocks end to end), then the
        family's counts (:meth:`split_counts` reads it back)."""
        block_tables = arrays[1 if self.family.block_length is not None
                              else 2]
        B, n_pages = block_tables.shape
        key = (B, n_pages)
        args = self._decode_args(cache, *arrays)
        fn = self._decode_programs.get(key)
        if fn is not None:
            out = fn(*args)
        else:
            from ..observability.cost_model import abstractify, program_cost
            fn = self._decode_programs[key] = self._build_decode(
                B, n_pages, cache.block_size)
            shapes = abstractify(args)      # the call donates the pools
            with _build_span("decode", f"{B}x{n_pages}") as b:
                out = fn(*args)
                with b.cost():
                    self._decode_costs[key] = program_cost(fn, shapes)
        _launch(DECODE_MODULE)
        if self.family.block_length is not None:
            tok, cache.block_ids, cache.block_masked, cache.k, cache.v = out
        else:
            tok, cache.tokens, cache.k, cache.v = out[:4]
            if cache.states:
                cache.states = dict(zip(cache.states, out[4:]))
        return tok

    # -- deterministic cost accounting (PR 7 cost model) -----------------
    @staticmethod
    def _cost_of(fn, args) -> Optional[dict]:
        from ..observability.cost_model import abstractify, program_cost
        return program_cost(fn, abstractify(args))

    def decode_cost(self, bucket: Tuple[int, int]) -> Optional[dict]:
        return self._decode_costs.get(tuple(bucket))

    def prefill_cost(self, padded_len: int) -> Optional[dict]:
        return self._prefill_costs.get(int(padded_len))


# the name older callers know the runner by (the benchmark's
# broken-path test patches ``PagedGPTRunner.decode``)
PagedGPTRunner = PagedRunner
