"""ServingEngine: continuous batching + paged KV over a causal LM of a
family the runner knows (``model_runner.served_classes``: GPT, LFM2-MoE).

The production serving loop (ROADMAP item 2): requests come in via
``submit()``, the engine prefills them into paged KV blocks, and every
``decode_once()`` enqueues ONE bucketed compiled decode step over the
whole running batch, one call ahead of the host: it reads back the
step of the call before it AFTER enqueueing its own, and a prefill's
first token after the step that consumes it — admissions and
evictions happen between steps (iteration-level scheduling). A family
that generates by diffusion over blocks (``serving/blockdiff.py``) rides
the same loop: its step is a pass over a block of positions a sequence,
a sequence advances when its block is committed, and the block in
flight stays on the device as a token's does.
Construct it from a live model
(``GPTForCausalLM``, ``Lfm2MoeForCausalLM``) or from a ``jit.save``'d
artifact (the artifact's
weights are loaded into a rebuilt architecture — the exported forward
program itself has no KV surface to page).

Decode-step telemetry flows through the PR 7 metrics plane when it is
enabled: ``serving_*`` counters/gauges plus one step window per decode
step with EXPLICIT token counts (``step_end(tokens=...)``) — serving
never relies on the train-step token heuristic, whose int-id shape
sniffing must not see block tables or int8 KV payloads as token
batches. The modeled step cost (XLA cost model) rides in the step
record as ``modeled_step_s`` so ``perf_doctor diff`` can compare
serving streams deterministically.

Greedy decoding; time enters only through the caller-supplied ``now``
stamps (the serving bench passes a virtual cost-model clock — no wall
clocks in any gate).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence as Seq, Tuple

import numpy as np

from ..profiler import launched as _launched, span as _span
from .block_cache import (BlockAllocator, HostKVTier, PagedKVCache,
                          PrefixCache, blocks_for_tokens, GARBAGE_BLOCK,
                          SCATTER_MODULE)
from .blockdiff import STRATEGIES, BlockInFlight
from .model_runner import (DECODE_MODULE, PREFILL_MODULE, PagedRunner,
                           served_classes)
from .reliability import (EngineFailedError, PromptTooLongError,
                          ReliabilityConfig, RequestRejected,
                          flight_record as _flight_record)
from .scheduler import (ContinuousBatchingScheduler, Request, SchedulerConfig,
                        Sequence, SeqState)
from .spec import SpeculativeConfig, accept_drafts, ngram_draft

__all__ = ["EngineConfig", "ServingEngine"]


def _pow2_ladder(lo: int, hi: int) -> Tuple[int, ...]:
    out, v = [], lo
    while v < hi:
        out.append(v)
        v *= 2
    out.append(hi)
    return tuple(sorted(set(out)))


def _place(seq: Sequence) -> tuple:
    """Where a running sequence stands: it moves when the sequence is
    evicted, requeued or rebuilt, and a token in flight is then dropped
    (the re-prefill from the token log computes it again)."""
    return (seq.state is SeqState.RUNNING, seq.evictions, seq.recoveries)


class _First:
    """A prefill's first token from its enqueueing to its delivery:
    ``out``, the prefill program's int32 array, on the device until it
    is read back, of the ``n`` tokens prefilled; the token also waits in
    ``row`` of ``cache.firsts`` for the decode step that consumes it. A
    block-diffusion family's prefill yields no token (``row`` None):
    what waits in ``out`` is its routing record alone."""

    __slots__ = ("seq", "n", "out", "row", "_at")

    def __init__(self, seq, n, out, row):
        self.seq, self.n, self.out, self.row = seq, n, out, row
        self._at = _place(seq)

    def moved(self) -> bool:
        return _place(self.seq) != self._at


# most bytes of per-sequence state (``PagedKVCache.state_slot_bytes`` a
# prefill) that admissions may leave in flight with their first tokens
# un-read; past it the admission reads its token in place, which waits
# for every prefill before it (16.9 MB a prefill at Falcon-H1's sizes:
# 30 in flight; LFM2's 32 KB never reach it)
STATE_AHEAD_BYTES = 512 << 20


class _Step:
    """One decode step from its enqueueing to its delivery: who rode in
    it (``active``, in row order), what was sent (``arrays``, ``counts``
    for the span), and ``out``, the program's int32 array, on the
    device until the step is read back. ``kept[i]`` falls when sequence
    i is evicted or requeued while the step is in flight: its token is
    then dropped, not delivered. A row carries ``family.row_positions``
    positions: one, or a block-diffusion family's block. ``launch`` is
    the ordinal of the execution that computes it (``profiler.launch``),
    known once it is enqueued."""

    __slots__ = ("now", "active", "drafts", "bucket", "arrays", "counts",
                 "out", "launch", "kept", "_rows", "_places")

    def __init__(self, now, active, drafts, bucket, arrays, counts):
        self.now, self.active, self.drafts = now, active, drafts
        self.bucket, self.arrays, self.counts = bucket, arrays, counts
        self.out = self.launch = None
        self._rows, row = [], 0
        for s in active:
            self._rows.append(row)
            row += 1 + len(drafts.get(id(s), ()))
        self.kept = [True] * len(active)
        self._places = [_place(s) for s in active]

    def drop_moved(self) -> int:
        """Mark the rows whose sequence is no longer where the step
        left it (running, never evicted or rebuilt since); how many
        fell now."""
        fell = 0
        for i, s in enumerate(self.active):
            if self.kept[i] and _place(s) != self._places[i]:
                self.kept[i] = False
                fell += 1
        return fell

    def flying(self) -> Dict[int, int]:
        """{id(sequence): its row} of the rows still kept."""
        return {id(s): r for s, r, k in
                zip(self.active, self._rows, self.kept) if k}


@dataclass
class EngineConfig:
    block_size: int = 16
    num_blocks: int = 64
    max_batch: int = 8
    # None -> power-of-two ladders derived from max_batch /
    # max_model_len; the compiled decode program count is bounded by
    # len(batch_buckets) * len(page_buckets)
    batch_buckets: Optional[Tuple[int, ...]] = None
    page_buckets: Optional[Tuple[int, ...]] = None
    prefill_budget_tokens: int = 512
    weight_only_int8: bool = False
    # also quantize the lm_head / logits matmul (shared-embedding
    # aware: the fp embedding table keeps serving the lookup) through
    # quantization.quantize_lm_head — the same entry point the
    # training-time quantized_lm_head config calibrates against
    weight_only_lm_head: bool = False
    max_model_len: Optional[int] = None
    kv_dtype: str = "float32"
    interpret: Optional[bool] = None
    # admission control / load shedding (None = unbounded PR 9
    # behavior); see serving.reliability.ReliabilityConfig
    reliability: Optional[ReliabilityConfig] = None
    # copy-on-write prefix caching (ISSUE 14): shared system prompts
    # collapse to one refcounted KV copy; prefix_cache_blocks bounds
    # the cache (None = bounded only by LRU reclaim pressure)
    enable_prefix_cache: bool = False
    prefix_cache_blocks: Optional[int] = None
    # speculative decoding (None = off): see serving.spec
    spec: Optional[SpeculativeConfig] = None
    # split-K width for the paged-attention kernel (None = the
    # kernel's own VMEM-fit auto dispatch — PR 9 behavior at every
    # context PR 9 could serve)
    split_pages: Optional[int] = None
    # fleet-global KV tiering (ISSUE 16, needs enable_prefix_cache):
    # cold prefix blocks SPILL to a host-DRAM tier instead of being
    # discarded, and fetch back on hit — priced over the shared
    # offload host link (cost_model.DEFAULT_HOST_GBPS, the same
    # channel autotune's offload-remat policy models). With tiering on
    # the virtual clock also charges prefill for the UNCACHED tail
    # only (a cached prefix is KV that exists — the real system skips
    # its compute), which is what lets migration beat re-prefill.
    enable_kv_spill: bool = False
    # host-tier capacity in blocks (None = unbounded)
    host_tier_blocks: Optional[int] = None
    # host-link override in GB/s (None = env / shared default)
    host_link_gbps: Optional[float] = None
    # generation by diffusion over blocks (a family with a
    # ``block_length``): denoise passes a block (None = the block
    # length, a position a pass; must divide it) and how a pass chooses
    # the positions it fixes (serving.blockdiff.STRATEGIES)
    denoising_steps: Optional[int] = None
    unmask_strategy: str = "low_confidence_static"


class ServingEngine:
    """Continuous-batching serving engine over one causal LM.
    ``gpt_config`` (the keyword predates the second family) is the
    model's config object — ``GPTConfig`` or ``Lfm2MoeConfig`` — from
    which an artifact's architecture is rebuilt."""

    def __init__(self, model=None, *, artifact_path: Optional[str] = None,
                 artifact_params_path: Optional[str] = None,
                 gpt_config=None, config: Optional[EngineConfig] = None):
        self.config = config or EngineConfig()
        if model is None:
            if artifact_path is None:
                raise ValueError("pass model= or artifact_path=")
            model = self._load_artifact(artifact_path, gpt_config,
                                        artifact_params_path)
        self.runner = PagedRunner(model, interpret=self.config.interpret,
                                  split_pages=self.config.split_pages)
        family = self.runner.family
        refused = [f for f in family.unsupported
                   if getattr(self.config, f)]
        if refused:
            raise ValueError(
                f"{type(model).__name__} is not served with "
                f"{', '.join(refused)} yet")
        self.model = model
        model.eval()
        # a block-diffusion family: B positions a row, S denoise passes
        # and a commit a block (0: a token a step, as every other family)
        self._block = family.block_length or 0
        self._steps = self._block_steps(family)
        self.max_model_len = int(self.config.max_model_len
                                 or family.max_positions)
        if self.max_model_len > family.max_positions:
            # jnp gathers CLAMP out-of-range indices, so positions past
            # the wpe table would silently decode with the wrong
            # embedding instead of raising
            raise ValueError(
                f"max_model_len {self.max_model_len} exceeds the "
                f"model's max_position_embeddings "
                f"{family.max_positions}")
        if self.config.weight_only_int8:
            from ..quantization import weight_only_quantize
            # projection matmuls only: qkv/out_proj/up/down inside the
            # blocks — embeddings and the (tied) head stay fp unless
            # weight_only_lm_head opts the logits matmul in below
            # (the GPT family's blocks: the others refused it above)
            for block in model.gpt.h:
                weight_only_quantize(block)
        if self.config.weight_only_lm_head:
            from ..quantization import quantize_lm_head
            quantize_lm_head(model)
        max_pages = blocks_for_tokens(self.max_model_len,
                                      self.config.block_size)
        # a speculative verify round rides k extra rows per sequence
        # through the SAME decode program family — the batch-bucket
        # ladder must cover the widest verify batch so the program
        # census stays inside the bucket grid (the PR 9 gate)
        max_rows = self.config.max_batch
        if self.config.spec is not None:
            max_rows *= 1 + self.config.spec.num_draft_tokens
            if self.config.batch_buckets is not None and \
                    max(self.config.batch_buckets) < max_rows:
                # fail at construction, not mid-decode: the first full
                # verify round would otherwise hit batch_bucket() with
                # a row count the explicit ladder cannot cover
                raise ValueError(
                    f"batch_buckets {self.config.batch_buckets} cannot "
                    f"cover speculative verify rows (max_batch "
                    f"{self.config.max_batch} x (1 + "
                    f"{self.config.spec.num_draft_tokens} drafts) = "
                    f"{max_rows})")
        sched_cfg = SchedulerConfig(
            max_batch=self.config.max_batch,
            batch_buckets=(self.config.batch_buckets
                           or _pow2_ladder(1, max_rows)),
            page_buckets=(self.config.page_buckets
                          or _pow2_ladder(1, max_pages)),
            prefill_budget_tokens=self.config.prefill_budget_tokens,
            reliability=self.config.reliability,
            slots_per_step=family.row_positions)
        # two kinds of state, one manager: paged blocks for the layers
        # that keep keys and values, and (where the family has layers
        # with fixed-size states, of one kind or several) one slot per
        # running sequence, the same id in every kind's pool; the
        # last step's tokens stay on the device as wide as the widest
        # decode batch
        slots = self.config.max_batch if family.state_kinds else 0
        self.cache = PagedKVCache(
            family.attn_layers, self.config.num_blocks,
            self.config.block_size, family.num_kv_heads, family.head_dim,
            dtype=self.config.kv_dtype, state_kinds=family.state_kinds,
            state_slots=slots, token_rows=sched_cfg.batch_buckets[-1],
            block_length=family.block_length, kv_widths=family.kv_widths)
        self.allocator = BlockAllocator(self.config.num_blocks,
                                        self.config.block_size,
                                        state_slots=slots)
        self.scheduler = ContinuousBatchingScheduler(sched_cfg,
                                                     self.allocator)
        self.prefix_cache: Optional[PrefixCache] = None
        self.host_tier: Optional[HostKVTier] = None
        if self.config.enable_prefix_cache:
            if self.config.enable_kv_spill:
                self.host_tier = HostKVTier(self.config.host_tier_blocks)
            self.prefix_cache = PrefixCache(
                self.allocator, max_blocks=self.config.prefix_cache_blocks,
                host_tier=self.host_tier)
            if self.host_tier is not None:
                self.prefix_cache.set_spill_io(self._kv_gather_block,
                                               self._kv_scatter_block)
            self.scheduler.prefix_cache = self.prefix_cache
        # metric-counter snapshot for the KV-tier totals (spill/fetch
        # events fire deep inside the allocator's reclaim hook, so the
        # engine emits deltas rather than instrumenting the cache)
        self._kv_counts: Dict[str, int] = {}
        self.spec_accepted = 0
        self.spec_rejected = 0
        self._next_req_id = 0
        self._seqs: Dict[int, Sequence] = {}
        self.decode_steps = 0
        # the decode step enqueued and not read back yet (decode_once),
        # the prefills' first tokens likewise (in the order of their
        # rows in ``cache.firsts``; the next decode_once delivers them
        # all), and tokens in flight dropped since the last dispatch span
        self._ahead: Optional[_Step] = None
        self._firsts: List[_First] = []
        self._dropped_ahead = 0
        # rows re-prefilled because a discarded step had moved their
        # per-sequence state: since the last dispatch span, and in all
        self._state_reprefills = 0
        self.state_reprefills = 0
        # steps enqueued with the one before un-read, prefills left with
        # their first token un-read, and tokens in flight thrown away by
        # an eviction, a requeue or a failure
        self.ahead_steps = 0
        self.prefill_ahead = 0
        self.ahead_dropped = 0
        # failure plane: set by fail() (chaos kill_engine, an operator
        # kill, a poisoned device) — a failed engine refuses all work
        # and its in-flight sequences are harvested for failover
        self.engine_id = 0
        self.failed = False
        self.fail_reason: Optional[str] = None
        self.failed_t: Optional[float] = None

    def _block_steps(self, family) -> int:
        """Denoise passes a block, checked against what the family and
        the cache can do; 0 for a family that appends a token a step."""
        cfg, B = self.config, family.block_length
        if B is None:
            if cfg.denoising_steps is not None:
                raise ValueError(
                    f"{type(self.model).__name__} generates a token a "
                    f"step: denoising_steps does not apply")
            return 0
        steps = cfg.denoising_steps or B
        if steps < 1 or B % steps:
            raise ValueError(f"denoising_steps {steps} must divide the "
                             f"block length {B}")
        if cfg.unmask_strategy not in STRATEGIES:
            # a schedule whose pass count depends on the tokens needs
            # every pass read back before the next is selected
            raise ValueError(
                f"unmask_strategy {cfg.unmask_strategy!r} is not served "
                f"(have {', '.join(STRATEGIES)})")
        from .model_runner import PREFILL_PAD
        if cfg.block_size % B or PREFILL_PAD % B:
            # a block in flight lies in ONE page, and a shared (prefix)
            # page holds whole blocks only: its keys and values depend
            # on nothing behind it
            raise ValueError(
                f"block length {B} must divide the cache's block_size "
                f"{cfg.block_size} and the prefill padding {PREFILL_PAD}")
        return steps

    @property
    def engine_id(self) -> int:
        return self._engine_id

    @engine_id.setter
    def engine_id(self, value: int) -> None:
        # mirrored onto the scheduler so ITS flight/trace spans carry
        # the same lane id the engine's do (the router re-numbers
        # engines after construction — a copied id would go stale)
        self._engine_id = int(value)
        self.scheduler.engine_id = self._engine_id

    # -- construction helpers --------------------------------------------
    @staticmethod
    def _load_artifact(artifact_path: str, gpt_config,
                       params_path: Optional[str] = None):
        """Rebuild the architecture from ``gpt_config`` and load the
        ``jit.save``'d weights into it. ``params_path`` overrides the
        prefix-derived weights file — the same contract
        ``Config.set_model(prog_file, params_file)`` gives the
        Predictor path."""
        if gpt_config is None:
            raise ValueError(
                "artifact_path needs gpt_config= (the architecture is "
                "rebuilt; the serialized program has no pageable KV)")
        from ..jit.api import load as jit_load
        loaded = jit_load(artifact_path, params_path=params_path)
        model = served_classes(gpt_config)[0](gpt_config)
        # the values the architecture was created with are about to be
        # replaced: wait for them, or a loaded weight is allocated
        # while the value it replaces still waits for its producer
        # (twice the weights, where the host runs far enough ahead)
        import jax
        jax.block_until_ready([p._data for p in model.parameters()])
        state = loaded.state_dict()
        # the artifact's dtypes are the serving dtypes: a bf16
        # (amp O2) artifact is served in bf16, not widened back to the
        # rebuilt architecture's f32 defaults
        for name, t in model.state_dict().items():
            saved = state.get(name)
            if saved is not None and saved.dtype != t._data.dtype:
                t._replace_data(t._data.astype(saved.dtype))
        model.set_state_dict(state)
        return model

    # -- KV tier I/O (ISSUE 16) ------------------------------------------
    def _kv_gather_block(self, block: int):
        """One block's K/V bytes, device -> host arrays (the spill /
        peer-export path). Goes through ``self.cache`` at call time —
        the pools are reassigned after every donated program, so a
        captured pool reference would go stale."""
        return (np.asarray(self.cache.k[:, block]),
                np.asarray(self.cache.v[:, block]))

    def _kv_scatter_block(self, block: int, k_np, v_np) -> None:
        """Write fetched/migrated K/V bytes into ``block`` on device
        (the promotion path back into HBM)."""
        import jax.numpy as jnp
        self.cache.k = self.cache.k.at[:, block].set(
            jnp.asarray(k_np, self.cache.dtype))
        self.cache.v = self.cache.v.at[:, block].set(
            jnp.asarray(v_np, self.cache.dtype))

    @property
    def host_link_bps(self) -> float:
        """Host<->device offload-link rate the spill tier is priced
        at — the SAME shared channel the offload-remat policy models
        (one owner in ``cost_model``, no drift)."""
        from ..observability.cost_model import host_link_bps
        return host_link_bps(self.config.host_link_gbps)

    # -- request intake --------------------------------------------------
    def submit(self, prompt: Seq[int], max_new_tokens: int,
               arrival_t: float = 0.0, priority: Optional[int] = None,
               deadline_s: Optional[float] = None,
               trace_id: Optional[int] = None) -> int:
        """Submit one request. Typed rejections at submit time:
        :class:`~.reliability.PromptTooLongError` when the request can
        never fit the model's context,
        :class:`~.reliability.QueueFullError` when the bounded
        admission queue is full and the overload policy finds nothing
        lower-priority to shed. ``priority`` (higher = more important)
        and ``deadline_s`` (relative to ``arrival_t``) default from
        the engine's :class:`~.reliability.ReliabilityConfig`.
        ``trace_id`` is the stable id the request-tracing plane keys
        this request's span tree by (the failover router stamps its
        fleet-global id; default: this engine's request id)."""
        # `req` joins this span with the request's `prefill`: the time
        # to its first token, on the trace's clock, is from here to the
        # end of that prefill's `prefill.readback`
        with _span("submit") as sp:
            rid = self._submit(prompt, max_new_tokens, arrival_t,
                               priority, deadline_s, trace_id)
            sp.set_metadata(req=rid)
        return rid

    def _submit(self, prompt, max_new_tokens, arrival_t, priority,
                deadline_s, trace_id) -> int:
        self._check_alive()
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise RequestRejected("empty prompt")
        if max_new_tokens < 1:
            raise RequestRejected(
                "max_new_tokens must be >= 1 (prefill always produces "
                "the first token)")
        rows = self.runner.family.row_positions
        if -(-(len(prompt) + max_new_tokens) // rows) * rows \
                > self.max_model_len:
            # typed + at submit time: letting this through would only
            # surface later as a block-coverage stall or a clamped
            # position — far less legible than refusing the request
            raise PromptTooLongError(
                f"prompt({len(prompt)}) + max_new({max_new_tokens}) "
                f"exceeds max_model_len {self.max_model_len}"
                + (f" in whole blocks of {rows}" if rows > 1 else ""))
        rel = self.scheduler.reliability
        rid = self._next_req_id
        self._next_req_id += 1
        req = Request(rid, prompt, int(max_new_tokens), arrival_t,
                      priority=(rel.default_priority if priority is None
                                else int(priority)),
                      deadline_t=rel.deadline_for(arrival_t, deadline_s),
                      trace_id=(rid if trace_id is None else trace_id))
        seq = Sequence(req, self.allocator)
        self.scheduler.submit(seq)     # may shed, may raise QueueFull
        self._seqs[rid] = seq
        _flight_record(event="submit", req=rid, tid=req.trace_id,
                       t=arrival_t, engine=self.engine_id,
                       prompt_tokens=len(prompt),
                       max_new=int(max_new_tokens))
        self._gauge()
        return rid

    def sequence(self, req_id: int) -> Sequence:
        return self._seqs[req_id]

    def routed_experts(self, req_id: int) -> Optional[np.ndarray]:
        """The experts the served path chose for each token it was fed
        (prompt and generated, so one row fewer than the token log
        while the last token has not been fed): int32 ``[positions,
        expert layers, k]``; None for a family that routes nothing.
        Top-k routing is discontinuous, so a replay or a comparison
        against another implementation needs WHICH experts ran."""
        if self.runner.family.routed is None:
            return None
        pieces = self._seqs[req_id].routed
        layers, k = self.runner.family.routed
        return (np.concatenate(pieces) if pieces
                else np.zeros((0, layers, k), np.int32))

    def block_passes(self, req_id: int) -> list:
        """A block-diffusion family's record of a request, pass by pass
        as the served path ran them: (block start, the block's ids after
        the pass with -1 where still masked, the experts chosen for its
        B rows ``[B, expert layers, k]``, was it the commit)."""
        return list(self._seqs[req_id].passes)

    # -- failure plane ---------------------------------------------------
    def _check_alive(self) -> None:
        if self.failed:
            raise EngineFailedError(
                f"engine {self.engine_id} failed: {self.fail_reason}")

    def fail(self, reason: str, now: float = 0.0) -> None:
        """Mark this engine dead (idempotent). Device state — pools,
        compiled programs — is considered lost; host state (token
        logs, the scheduler ledger) survives for
        :meth:`recover_inflight`."""
        from ..observability import metrics
        if self.failed:
            return
        self._drop_ahead()          # the device's state is lost
        self.failed = True
        self.fail_reason = reason
        self.failed_t = now
        metrics.inc("serving_engine_failures_total")
        # the span carries every in-flight trace id: each of those
        # requests' failover_stall starts at THIS stamp (detection
        # latency is part of the stall), and the chaos fault that
        # killed the engine is attributable to specific requests
        tids = [s.trace_id for s in self.scheduler.running()
                + self.scheduler.waiting if s.trace_id is not None]
        _flight_record(event="engine_failed", engine=self.engine_id,
                       reason=reason, t=now, tids=tids or None)

    def recover_inflight(self) -> List[Sequence]:
        """Harvest every unfinished sequence of a FAILED engine for
        adoption elsewhere: running first (admission order — oldest
        work resumes first), then the waiting queue in order. Tables
        are dead with the engine; each sequence's accepted tokens live
        in its host-side token log, and re-prefilling that log
        reproduces the lost KV exactly (the eviction-exactness
        guarantee), so the continuation is token-for-token identical
        to a fault-free run."""
        if not self.failed:
            raise EngineFailedError(
                "recover_inflight is only valid on a failed engine "
                "(a healthy engine's sequences are still being served)")
        self._drop_ahead()
        running = list(self.scheduler._running)
        waiting = [s for s in self.scheduler.waiting
                   if s.state is SeqState.WAITING]
        self.scheduler._running = []
        self.scheduler.waiting = []
        for s in running:
            # only ever-ADMITTED work counts as a recovery: the
            # recoveries counter feeds _in_flight(), which exempts a
            # sequence from shedding/deadlines on the adopter — a
            # never-admitted waiting request must keep fresh-arrival
            # admission semantics there (its deadline still applies)
            s.state = SeqState.WAITING
            s.recoveries += 1
        return running + waiting

    def adopt(self, seq: Sequence, now: Optional[float] = None) -> int:
        """Adopt a sequence recovered from a dead engine: re-key it
        into this engine's request map and bind a fresh table on this
        engine's allocator (``trace_id`` survives the re-key — spans
        stay joined across the failover). Ever-ADMITTED work (tokens
        accepted) requeues at the FRONT, exempt from the admission
        bound — in-flight is honored. A never-admitted fresh arrival
        keeps fresh-arrival semantics: it goes through the normal
        bounded ``submit`` path, so the adopter's queue depth and shed
        policy still govern it (a refusal marks it SHED with the typed
        error, never silently over-fills the queue). ``now`` stamps
        the adoption span (the router passes its probe time)."""
        from ..observability import metrics
        from .reliability import QueueFullError
        self._check_alive()
        rid = self._next_req_id
        self._next_req_id += 1
        seq.request.req_id = rid
        seq.rebind(self.allocator)
        seq.ready_at = 0.0
        self._seqs[rid] = seq
        if self.scheduler._in_flight(seq):
            self.scheduler.requeue_front(seq, now=now, cause="adopt")
        else:
            try:
                self.scheduler.submit(seq)
            except QueueFullError as e:
                self.scheduler.mark_shed(seq, e, now=now)
        if seq.state is not SeqState.SHED:
            # an adoption the bounded queue refused is a shed (counted
            # by mark_shed), not a recovery
            metrics.inc("serving_recovered_seqs_total")
        _flight_record(event="adopt", engine=self.engine_id, req=rid,
                       tid=seq.trace_id, t=now, tokens=len(seq.tokens),
                       shed=seq.state is SeqState.SHED)
        self._gauge()
        return rid

    # -- weight hot-swap -------------------------------------------------
    def swap_weights(self, weights, now: float = 0.0,
                     source=None) -> List:
        """Swap new checkpoint weights into the running engine between
        decode steps. ``weights`` is a model (``GPTForCausalLM``) or a
        flat array list matching the runner state. Weights-as-args
        means the compiled programs are untouched — the swap can never
        grow the decode program census. Returns the previous weight
        arrays (the rollback payload).

        ``source`` (``CheckpointManager.swap_source()`` shape) stamps
        the producing checkpoint's restart generation onto the
        ``hot_swap`` span — and because that span carries ``t=`` and
        the in-flight ``tids=``, the generation rides into every
        affected request's trace."""
        from ..observability import metrics
        self._check_alive()
        # what is in flight ran with the old weights: its tokens are
        # delivered before the swap is stamped
        self._flush_ahead()
        arrays = weights
        if hasattr(weights, "state_dict"):       # a live model
            from ..jit.functional import _collect_state
            params, buffers = _collect_state([weights])
            arrays = [t._data for t in params + buffers]
        prev = self.runner.swap_weights(arrays)
        metrics.inc("serving_hot_swaps_total")
        # weights-as-args means the swap costs the running batch ZERO
        # pause (pause_s stays 0.0); the span still stamps WHICH
        # requests were in flight, so a future engine that must
        # quiesce can price its pause into their swap_stall component
        tids = [s.trace_id for s in self.scheduler.running()
                if s.trace_id is not None]
        src = source or {}
        _flight_record(event="hot_swap", engine=self.engine_id, t=now,
                       tids=tids or None, pause_s=0.0,
                       generation=src.get("generation"),
                       ckpt_step=src.get("step"),
                       session=src.get("session"))
        return prev

    # -- admission + prefill ---------------------------------------------
    def admit_and_prefill(self, now: float = 0.0,
                          ready_at_fn=None) -> List[dict]:
        """One admission round: FIFO-admit within the prefill budget,
        prefill each admitted sequence (ALL its tokens — first
        admission or post-eviction recompute), scatter K/V into its
        blocks, and sample its next token. Returns per-admission info
        dicts (seq, prompt_tokens, padded_len, cost) for the caller's
        clock; ``ready_at_fn(info) -> float`` (default: ``now``)
        stamps when each sequence may join the decode batch — the sim
        sets it to the prefill LANE's completion time, which is the
        whole point of disaggregation: decode never waits on it."""
        self._check_alive()
        with _span("admit"):
            with _span("admit.schedule"):
                admitted = self.scheduler.admit(now)
            out = [self._prefill_admitted(seq, now, ready_at_fn)
                   for seq in admitted]
            self._gauge()
        return out

    def _prefill_admitted(self, seq: Sequence, now: float,
                          ready_at_fn) -> dict:
        """Prefill one admitted sequence, scatter its K/V into its
        blocks and mark it running. Its first token stays on the device
        (``cache.firsts``) for the decode step that consumes it, and the
        next :meth:`decode_once` delivers it AFTER that step is
        enqueued: everything done here follows from lengths the host
        has. Where a step must be read back before the next is selected
        (:meth:`_reads_back_first`) the token is delivered here."""
        from ..observability import metrics
        family = self.runner.family
        # a block family prefills the whole blocks; the tokens left
        # over open the first block in flight, already fixed
        n = family.prefill_keeps(len(seq.tokens))
        left = len(seq.tokens) - n
        seq.block = None
        seq.passes = [p for p in seq.passes if p[0] < n]
        padded = self.runner.prefill_padded_len(n) if n else 0
        first_row = len(self._firsts) if not self._block else None
        # a prefill's outputs (its stacks, its per-sequence states) are
        # allocated when it is ENQUEUED and live until their writes have
        # run: admissions far ahead of the device (a warm-up of 128
        # prompts on cached programs) would hold that many states
        ahead = n > 0 and not self._reads_back_first() and (
            first_row is None or (
                first_row < self.cache.firsts.shape[0]
                and (first_row + 1) * self.cache.state_slot_bytes
                <= STATE_AHEAD_BYTES))
        with _span("prefill", req=seq.req_id, tokens=n, padded=padded,
                   ahead=int(ahead), **({"block_tokens": left}
                                        if self._block else {}),
                   **family.prefill_counts(padded)):
            out = None
            if n:
                # which execution this span enqueued: the ordinal the
                # runner's call site is about to take, and how many it
                # took (one), read off the counter on both sides
                first = _launched(PREFILL_MODULE)
                with _span("prefill.dispatch", program=PREFILL_MODULE,
                           launch=first) as sp:
                    out, k_stack, v_stack, *state = \
                        self.runner.prefill_dispatch(
                            seq.tokens[:n] if left else seq.tokens)
                    sp.set_metadata(
                        launches=_launched(PREFILL_MODULE) - first)
                    if ahead and first_row is not None:
                        self.cache.keep_first(first_row, out)
            row = np.asarray(seq.table.blocks, np.int64)
            # prefix-cache hit: the leading cached positions' KV is
            # ALREADY in the pool (and shared — rewriting it would
            # scribble on every sibling), so only the private tail is
            # scattered. The prefill still computed the full prompt:
            # the tail's hidden states need the prefix context, and
            # the first generated token comes from the last position.
            start = min(seq.prefix_cached_tokens, n)
            first = _launched(SCATTER_MODULE)
            with _span("prefill.scatter", program=SCATTER_MODULE,
                       launch=first) as sp:
                if n:
                    # (a latent cache: one pool, written a layer at a time)
                    self.cache.k = PagedKVCache.scatter_prefill(
                        self.cache.k, k_stack, row, n,
                        self.cache.block_size, start=start,
                        by_layer=v_stack is None)
                    if v_stack is not None:
                        self.cache.v = PagedKVCache.scatter_prefill(
                            self.cache.v, v_stack, row, n,
                            self.cache.block_size, start=start)
                    if state:
                        # the whole prompt was computed (a prefix hit
                        # too), so this is the state at its real last
                        # positions
                        self.cache.write_state(seq.table.state_slot,
                                               state)
                # a pool each (the latent cache has one); none where a
                # prefix hit covers the whole prompt
                sp.set_metadata(launches=_launched(SCATTER_MODULE) - first)
        seq.table.num_tokens = n
        cost = self.runner.prefill_cost(padded)
        info = {"seq": seq, "prompt_tokens": n, "padded_len": padded,
                "cost": cost}
        if self.host_tier is not None and cost and start > 0:
            # tiering charges the clock for the UNCACHED tail only
            # (linear token scaling of the full-prompt cost): the
            # cached prefix's KV already exists, and a real system
            # with paged-context prefill skips its compute. The
            # full prefill still RUNS (exactness — the tail's
            # hidden states need the prefix context); only the
            # modeled charge shrinks. Off-tier engines keep the
            # PR 13 full-charge behavior bitwise.
            # a FULL-prompt hit still computes the last position
            # (the first generated token's logits need it), so the
            # charge floors at one token — never the flopless
            # zero-dict that would trip the clock fallback
            frac = (n - min(start, n - 1)) / n
            info["charged_cost"] = {k: v * frac
                                    for k, v in cost.items()}
        ready = (ready_at_fn(info) if ready_at_fn is not None
                 else now)
        # tier-fetch stall: host-tier promotions pay the shared
        # offload link, peer fetches carry their modeled DCN
        # seconds from the registry's cost decision — both land
        # AFTER the prefill interval so the decomposition's
        # spill_fetch component never overlaps prefill_s
        host_blocks = getattr(seq, "kv_fetched_host", 0)
        peer_blocks = getattr(seq, "kv_fetched_peer", 0)
        fetch_s = (host_blocks * self.cache.block_bytes
                   / self.host_link_bps
                   + getattr(seq, "kv_peer_fetch_s", 0.0))
        seq.ready_at = ready + fetch_s
        if not self._block:
            # (a block family's first tokens exist at its first commit)
            self._stamp_first_token(seq, seq.ready_at)
        self.scheduler.mark_running(seq)
        # prefill span: admission -> first-token-ready on the
        # prefill lane (lane queueing included — the decode lane
        # never waits on it). `end` is the EXACT lane stamp so
        # a finish-at-prefill closes the sum bitwise.
        _flight_record(event="prefill", req=seq.req_id,
                       tid=seq.trace_id, t=now, end=ready,
                       engine=self.engine_id, tokens=n,
                       padded=padded)
        if fetch_s:
            _flight_record(event="spill_fetch", req=seq.req_id,
                           tid=seq.trace_id, t=ready,
                           end=seq.ready_at, engine=self.engine_id,
                           host_blocks=host_blocks or None,
                           peer_blocks=peer_blocks or None)
        metrics.inc("serving_prefill_tokens_total", n)
        if out is not None:
            self._firsts.append(_First(seq, n, out, first_row))
        if ahead:
            self.prefill_ahead += 1
        else:
            self._deliver_firsts()
        return info

    @staticmethod
    def _stamp_first_token(seq: Sequence, t: float) -> None:
        from ..observability import metrics
        if seq.first_token_t is None:
            seq.first_token_t = t
            metrics.observe("serving_ttft_s",
                            max(0.0, t - seq.request.arrival_t))

    def _deliver_firsts(self) -> None:
        """Read back every first token in flight (``prefill.readback``:
        the host waits for the device) into its sequence's log, each
        inside a span named ``prefill`` like the admission's, with
        ``req`` and the family's counts, which come in the token's
        array. One whose sequence moved meanwhile is dropped."""
        firsts, self._firsts = self._firsts, []
        for f in firsts:
            if f.moved():
                self._count_dropped(1)
                continue
            seq = f.seq
            with _span("prefill", req=seq.req_id) as sp:
                with _span("prefill.readback"):
                    tok, counts, chosen = self.runner.split_counts(f.out, 1)
                if counts:
                    sp.set_metadata(**self._count_stats(counts))
                    # the whole token log was routed anew (a re-prefill
                    # after an eviction too): its record replaces the old
                    seq.routed = [chosen[:f.n]]
            if f.row is None:
                continue            # a block family's prefill: no token
            seq.tokens.append(int(tok[0]))
            if seq.done:
                # its only token materializes when the prefill LANE
                # finishes — finishing at the admission instant would
                # stamp finish_t before first_token_t
                self.scheduler.finish(seq, seq.ready_at)

    @staticmethod
    def _count_stats(counts: Dict[str, list]) -> Dict[str, int]:
        """A program's per-layer counts as span stats: summed over the
        layers, but a ``*_max`` is the worst layer's. The rows routed,
        and those with an expert held here, also go to the metrics
        plane."""
        from ..observability import metrics
        stats = {k: int(max(v) if k.endswith("_max") else sum(v))
                 for k, v in counts.items()}
        for name in ("moe_rows", "moe_rows_routed_here"):
            if name in stats:
                metrics.inc(f"serving_{name}_total", stats[name])
        return stats

    # -- block-table integrity --------------------------------------------
    def _validate_tables(self, active: List[Sequence],
                         now: Optional[float] = None) -> List[Sequence]:
        """Integrity-check every RUNNING sequence's block table before
        the decode step consumes it: ids in the usable range, coverage
        for the cached tokens, and every block claimed no more often
        than its REFCOUNT covers. A repeat WITHIN one table is always
        corruption; a block claimed by several tables is legitimate
        copy-on-write sharing exactly when the claim count (plus the
        prefix cache's own hold) stays within the allocator's
        refcount — a scribble that aliases someone's block overshoots
        it. A violator (chaos ``corrupt_block_table``, a real
        scribble) is requeued for re-prefill from its token log and
        the allocator's free list AND refcounts are rebuilt from the
        SURVIVING claims — the corrupt ids cannot be trusted enough to
        free() (double-free risk); the prefix cache's held blocks are
        one more survivor claim list. Returns the still-running subset
        of ``active``."""
        from ..observability import metrics
        running = self.scheduler.running()
        n_blocks = self.config.num_blocks
        # every claim of every table in ONE pass of array operations (a
        # Python step per block id was thousands of steps a tick at long
        # contexts): the flat ids beside the index of their owner
        lens = [len(s.table.blocks) for s in running]
        flat = np.fromiter(itertools.chain.from_iterable(
            s.table.blocks for s in running), np.int64, sum(lens))
        owner = np.repeat(np.arange(len(running)), lens)
        in_range = (flat > 0) & (flat < n_blocks)
        ids, own = flat[in_range], owner[in_range]
        # a repeat WITHIN one table aliases two of the sequence's own
        # token pages onto one block: never legitimate
        pair, times = np.unique(own * n_blocks + ids, return_counts=True)
        # over-claimed: sharing must be covered by references (the
        # prefix cache's hold is one more claim). A cross-table alias
        # cannot say WHICH table was scribbled, so every claimant is
        # rebuilt: re-prefill is exact either way
        claims = np.bincount(ids, minlength=n_blocks)
        if self.prefix_cache is not None:
            claims[list(self.prefix_cache.held_blocks())] += 1
        over = claims > self.allocator.refcounts()
        wrong = np.zeros(len(running), bool)
        wrong[owner[~in_range]] = True
        wrong[pair[times > 1] // n_blocks] = True
        wrong[own[over[ids]]] = True
        bad = [s for s, w, n in zip(running, wrong, lens)
               if w or n < blocks_for_tokens(max(s.table.num_tokens, 1),
                                             self.config.block_size)]
        if not bad:
            return active
        for s in bad:
            metrics.inc("serving_table_corruptions_total")
            _flight_record(event="table_corrupt", engine=self.engine_id,
                           req=s.req_id, tid=s.trace_id, t=now,
                           blocks=list(s.table.blocks))
            self.scheduler.requeue_corrupt(s, now=now)
        survivors = [s.table.blocks for s in self.scheduler.running()]
        if self.prefix_cache is not None:
            survivors.append(self.prefix_cache.held_blocks())
        self.allocator.rebuild_free_list(
            survivors, [s.table.state_slot
                        for s in self.scheduler.running()
                        if s.table.state_slot is not None])
        return [s for s in active if s.state is SeqState.RUNNING]

    # -- one decode step -------------------------------------------------
    def decode_once(self, now: float = 0.0) -> Optional[dict]:
        """Enqueue ONE compiled decode step over every running sequence
        whose prefill has completed (``ready_at <= now``), THEN read
        back and deliver the step the call before this one enqueued:
        the device works on step n+1 while the host emits step n and
        selects step n+2. A row's input token is the one thing the host
        lacks for the next step (a sequence ends by length alone), and
        that is taken on the device from the step in flight — or, for a
        sequence just prefilled, from its first token, which the host
        has not read either. So a call delivers the tokens of the step
        before it and, after them, the first tokens of the prefills
        since the call before; the first call after an empty engine
        delivers no step, and :meth:`idle` counts what is in flight.

        Where the next step's inputs DO need the host to have seen the
        tokens (:meth:`_reads_back_first`) the step is read back in the
        call that enqueued it. Returns a step info dict (``bucket``,
        ``n_active``, ``cost`` of the step ``dispatched`` by this call,
        else of the one delivered; ``tokens`` delivered by steps;
        ``evictions``), or None when nothing was enqueued or delivered.
        Raises
        :class:`~.reliability.EngineFailedError` when the engine is (or
        chaos makes it) dead."""
        self._check_alive()
        # host spans of one tick (profiler.span; PERF.md lists them):
        # decode holds select -> build_batch -> dispatch (holding the
        # readback of the step BEFORE) -> emit; a tick with nothing
        # ready and nothing in flight ends inside select
        with _span("decode"):
            ahead = self._ahead
            sync = self._reads_back_first()
            if sync:
                # armed since the prefill: the next step's inputs are
                # to be in the logs
                self._deliver_firsts()
            picked = None
            if ahead is None or not sync:
                with _span("decode.select"):
                    picked = self._select_decode_rows(now, ahead)
            if picked is None and ahead is None and not self._firsts:
                return None
            return self._decode_rows(now, picked, ahead, sync)

    def _reads_back_first(self) -> bool:
        """Must a step be read back before the next is selected? Yes
        where the next step's inputs are a function of its tokens: a
        draft is drawn from the token log, and an armed chaos hook on
        the step discards it and repeats it (``drop_decode_step``) or
        kills the engine on a count of delivered steps."""
        from ..distributed.fault_tolerance import chaos
        if self.config.spec is not None:
            return True
        armed = chaos.active()
        return armed is not None and (armed.armed("drop_decode_step")
                                      or armed.armed("kill_engine"))

    def _drop_ahead(self) -> None:
        """Throw the step and the first tokens in flight away (the
        engine's device state is lost, or its sequences leave): no
        token of them reaches a log."""
        step, self._ahead = self._ahead, None
        firsts, self._firsts = self._firsts, []
        self._count_dropped(sum(step.kept if step is not None else ())
                            + len(firsts))

    def _count_dropped(self, n: int) -> None:
        self._dropped_ahead += n        # for the next dispatch span
        self.ahead_dropped += n

    def _flush_ahead(self) -> None:
        """Deliver what is in flight now, for a caller whose next act
        must not overtake it."""
        if self._ahead is not None or self._firsts:
            with _span("decode"):
                self._decode_rows(0.0, None, self._ahead, True)

    def _select_decode_rows(self, now: float, ahead: "Optional[_Step]"):
        """Who decodes next: the ready running sequences whose tables
        validate and whose next slots (drafts included) could be
        reserved. A row of the step in flight (``ahead``) and a
        sequence whose first token is in flight stand one token further
        than their logs say; one whose token in flight is its last is
        not selected. Returns (active, drafts, victims, {id(sequence):
        (where its input token waits on the device — a row of
        ``cache.tokens`` and ``cache.firsts`` end to end —, positions it
        stands past its table's count)}) or None."""
        from ..distributed.fault_tolerance import chaos
        from ..observability import metrics
        fed = {}
        if ahead is not None:
            self._count_dropped(ahead.drop_moved())
            fed = {sid: (row, 1) for sid, row in ahead.flying().items()}
        if self._block:
            # a pass in flight moves its sequence's table only when it
            # is the block's commit: the next block then opens behind it
            seqs = {id(s): s for s in ahead.active} if fed else {}
            fed = {sid: (row, self._block if seqs[sid].block.done
                         == len(seqs[sid].block.plan) else 0)
                   for sid, (row, _) in fed.items()}
        else:
            # the prefill wrote the table's count: a first token is AT it
            width = self.cache.tokens.shape[0]
            fed.update((id(f.seq), (width + f.row, 0))
                       for f in self._firsts)
        active = [s for s in self.scheduler.running()
                  if getattr(s, "ready_at", 0.0) <= now
                  and not (id(s) in fed and self._ends_in_flight(
                      s, fed[id(s)][1]))]
        if not active:
            return None
        # chaos scribbles land BEFORE validation — the validator must
        # catch them like any organic corruption (the active() guard
        # keeps the disarmed path free of the list allocation)
        if chaos.active() is not None:
            chaos.maybe_corrupt_block_table(
                [s.table.blocks for s in active])
            if self.host_tier is not None:
                chaos.maybe_corrupt_spill_block(self.host_tier)
        active = self._validate_tables(active, now=now)
        if not active:
            return None
        rows = self.runner.family.row_positions
        victims = self.scheduler.reserve_decode_slots(
            active, now=now,
            slots=[rows + fed.get(id(s), (0, 0))[1] for s in active])
        if victims:
            # counted HERE, not after the step: evicting every ready
            # sequence aborts the step below, and those evictions must
            # not vanish from the counter
            metrics.inc("serving_evictions_total", len(victims))
        active = [s for s in active if s.state is SeqState.RUNNING]
        if not active:
            return None
        if chaos.maybe_kill_engine(self.engine_id, self.decode_steps + 1):
            self.fail("chaos:kill_engine", now=now)
            raise EngineFailedError(
                f"engine {self.engine_id} killed by chaos at decode "
                f"step {self.decode_steps + 1}")
        # -- speculative drafts (host, deterministic): each sequence
        # may contribute 1 + k chunk rows to this round's verify batch.
        # spec=None degenerates to EXACTLY the PR 9 single-row step —
        # same buckets, same arrays, same program.
        spec = self.config.spec
        drafts: Dict[int, List[int]] = {}
        if spec is not None:
            for s in active:
                room = s.request.max_new_tokens - len(s.generated)
                k = min(spec.num_draft_tokens, room - 1)
                if k < 1:
                    continue
                d = (spec.draft_fn(s) if spec.draft_fn is not None
                     else ngram_draft(s.tokens, spec.ngram, k))
                d = [int(t) for t in d][:k]
                if d:
                    drafts[id(s)] = d
        if drafts:
            # verify rows need their slots reserved UP FRONT (the
            # program scatters the whole chunk's KV); rejected tails
            # roll back via truncate below
            slots = [1 + len(drafts.get(id(s), ())) for s in active]
            spec_victims = self.scheduler.reserve_decode_slots(
                active, now=now, slots=slots)
            if spec_victims:
                metrics.inc("serving_evictions_total",
                            len(spec_victims))
                victims += spec_victims
                active = [s for s in active
                          if s.state is SeqState.RUNNING]
                drafts = {k: v for k, v in drafts.items()
                          if k in {id(s) for s in active}}
            if not active:
                return None
        return active, drafts, victims, fed

    def _ends_in_flight(self, seq: Sequence, past: int) -> bool:
        """Does what is in flight for ``seq`` (``past`` positions beyond
        its table's count) bring its last token? A token a step: the
        one in flight; a block family: only a commit brings any."""
        room = seq.request.max_new_tokens - len(seq.generated)
        if not self._block:
            return room <= 1
        return past > 0 and seq.num_cached + past - len(seq.tokens) >= room

    def _build_step(self, now: float, active: List[Sequence],
                    drafts: Dict[int, List[int]], victims: list,
                    fed: Dict[int, Tuple[int, int]]) -> "_Step":
        """The next step's arrays. A sequence in ``fed`` (a row of the
        step in flight, a first token in flight) takes its input token
        on the device (id ``-1 - row`` of the tokens held there) and
        stands ``past`` positions beyond its table's count."""
        cfg = self.scheduler.config
        if self._block:
            return self._build_block_step(now, active, victims, fed)
        rows = []                      # (seq, token or -1 - row, position)
        ctx_tokens = 0
        for s in active:
            row, past = fed.get(id(s), (None, 0))
            p0 = s.num_cached + past
            ctx_tokens += p0
            rows.append((s, s.tokens[p0] if row is None else -1 - row, p0))
            for i, d in enumerate(drafts.get(id(s), ())):
                rows.append((s, d, p0 + 1 + i))
        # pages that hold a key of some row (a row at position p
        # attends over p + 1 keys): what the kernel moves, against
        # the rows x page_bucket table it is handed
        live_pages = [blocks_for_tokens(pos + 1, self.config.block_size)
                      for _, _, pos in rows]
        b_bucket = cfg.batch_bucket(len(rows))
        p_bucket = self.scheduler.decode_bucket(active)[1]
        ids = np.zeros((b_bucket, 1), np.int32)
        positions = np.zeros((b_bucket,), np.int32)
        tables = np.full((b_bucket, p_bucket), GARBAGE_BLOCK, np.int32)
        # state slots of the rows (0: the padded rows' garbage slot)
        slots = np.zeros((b_bucket,), np.int32) if self.cache.states \
            else None
        for i, (s, tok_in, pos) in enumerate(rows):
            ids[i, 0] = tok_in
            positions[i] = pos
            tables[i] = s.table.padded(p_bucket)
            if slots is not None:
                slots[i] = s.table.state_slot
        counts = self._step_counts(len(rows), tables, ctx_tokens,
                                   live_pages, victims)
        counts.update(self.runner.family.decode_counts(positions[:len(rows)]))
        return _Step(now, active, drafts, (b_bucket, p_bucket),
                     (ids, positions, tables)
                     + (() if slots is None else (slots,)), counts)

    def _step_counts(self, rows, tables, ctx_tokens, live_pages,
                     victims) -> dict:
        """What ``decode.dispatch`` says of the step it enqueues over
        ``tables`` (``live_pages``: of each row)."""
        counts = dict(
            rows=rows, row_bucket=tables.shape[0],
            page_bucket=tables.shape[1], ctx_tokens=ctx_tokens,
            live_pages=sum(live_pages),
            **self.runner.kernel_page_counts(self.cache, tables,
                                             live_pages),
            blocks_in_use=self.allocator.used_count,
            blocks_total=self.config.num_blocks, evicted=len(victims))
        if self.cache.states:
            # every real row reads and writes its slot of every kind
            counts["state_bytes"] = 2 * rows * self.cache.state_slot_bytes
        return counts

    def _build_block_step(self, now: float, active: List[Sequence],
                          victims: list,
                          fed: Dict[int, Tuple[int, int]]) -> "_Step":
        """The next step of a block-diffusion family: one row a
        sequence, its block's next pass. A sequence in ``fed`` has a
        pass in flight: its block waits on the device in that step's
        row — unless that pass is the commit, after which a fresh block,
        all masked, opens ``past`` positions on. Every count here follows
        from the plan (``blockdiff.fix_plan``): nothing is read back."""
        cfg, B = self.scheduler.config, self._block
        b_bucket = cfg.batch_bucket(len(active))
        p_bucket = self.scheduler.decode_bucket(active)[1]
        # per row: source row or -1, positions to fix, the block's first
        # position, live, then the host's ids and masked bits
        meta = np.zeros((b_bucket, 4 + 2 * B), np.int32)
        meta[:, 0] = -1
        tables = np.full((b_bucket, p_bucket), GARBAGE_BLOCK, np.int32)
        ctx_tokens = denoise = fresh = 0
        live_pages = []
        for i, s in enumerate(active):
            st = s.block
            if st is None:
                # first pass since the prefill: the tokens it left over
                # open the block, already fixed
                st = s.block = BlockInFlight(
                    s.num_cached, s.tokens[s.num_cached:], B, self._steps)
            row, past = fed.get(id(s), (None, 0))
            q = st.done if row is None else st.done + 1
            if past:
                start, q, n_fix = st.start + past, 0, B // self._steps
                meta[i, 4 + B:] = 1
            else:
                start, n_fix = st.start, st.fixes(q)
                if row is None:
                    meta[i, 4:4 + B] = st.ids
                    meta[i, 4 + B:] = st.masked
                else:
                    meta[i, 0] = row
            meta[i, 1:4] = n_fix, start, 1
            tables[i] = s.table.padded(p_bucket)
            ctx_tokens += start + B
            live_pages.append(blocks_for_tokens(start + B,
                                                self.config.block_size))
            denoise += n_fix > 0
            fresh += q == 0
        counts = self._step_counts(len(active), tables, ctx_tokens,
                                   live_pages, victims)
        counts.update(seqs=len(active), block_length=B,
                      denoise_rows=denoise,
                      commit_rows=len(active) - denoise, fresh_blocks=fresh)
        return _Step(now, active, {}, (b_bucket, p_bucket), (meta, tables),
                     counts)

    def _decode_rows(self, now: float, picked, ahead: "Optional[_Step]",
                     sync: bool) -> Optional[dict]:
        """Enqueue the step ``picked`` (if any), then read back and
        emit the step to deliver: the one in flight, else — where the
        next may not run ahead of it (``sync``) — the one just
        enqueued. Then the first tokens in flight: the step that
        consumes them is on the device by now."""
        from ..observability import metrics
        if ahead is not None:
            # evicted or requeued since it was enqueued (this call's
            # selection included): the token is dropped, the re-prefill
            # from the token log computes it again, exactly
            self._count_dropped(ahead.drop_moved())
        step = None
        victims = []
        if picked is not None:
            victims = picked[2]
            with _span("decode.build_batch"):
                step = self._build_step(now, *picked)
        due = ahead if ahead is not None else (step if sync else None)
        if step is None and due is None:
            # no step goes out and none comes back (nothing ready, a
            # request of one token): only first tokens are delivered
            self._deliver_firsts()
            return {"bucket": None, "n_active": 0, "tokens": 0,
                    "evictions": 0, "spec_accepted": 0, "spec_rejected": 0,
                    "dispatched": False, "cost": None}
        dropped, self._dropped_ahead = self._dropped_ahead, 0
        moved, self._state_reprefills = self._state_reprefills, 0
        # runner.decode is the one call that enqueues (H2D and the
        # program); decode.readback, in which the host waits for the
        # step BEFORE it, nests here; the routing counts that arrive
        # with it describe the step read: `read_launch` names that
        # execution, `launch` the one enqueued here
        if step is not None:
            step.launch = _launched(DECODE_MODULE)
            step.counts.update(program=DECODE_MODULE, launch=step.launch)
        with metrics.phase("compute"), \
                _span("decode.dispatch", **(step.counts if step else {}),
                      ahead=int(step is not None and ahead is not None),
                      dropped_ahead=dropped,
                      **({"state_reprefills": moved}
                         if self.cache.states else {})) as sp:
            if step is not None:
                step.out = self.runner.decode(self.cache, *step.arrays)
                if ahead is not None:
                    self.ahead_steps += 1
                    metrics.inc("serving_decode_ahead_total")
            self._ahead = None if step is due else step
            if due is not None:
                with _span("decode.readback"):
                    toks, counts, chosen = self.runner.split_counts(
                        due.out,
                        due.bucket[0] * self.runner.family.row_positions)
                sp.set_metadata(read_launch=due.launch)
                if counts:
                    sp.set_metadata(**self._count_stats(counts))
                if self._block:
                    sp.set_metadata(**self._read_block_rows(due, toks))
        about = step or due
        info = {"bucket": about.bucket, "n_active": len(about.active),
                "tokens": 0, "evictions": len(victims),
                "spec_accepted": 0, "spec_rejected": 0,
                "dispatched": step is not None,
                "cost": self.runner.decode_cost(about.bucket)}
        if due is not None:
            with _span("decode.emit"):
                info.update(self._emit_decoded(due, toks, chosen))
        self._deliver_firsts()
        return info

    def _emit_decoded(self, step: "_Step", toks, chosen=None) -> dict:
        """Append the step's tokens (and, for a routed family, the
        experts ``chosen`` for each row's input token) to the logs of
        the rows that kept their place, finish what is done, count.
        Stamps are the step's own: its tokens exist at ITS end."""
        from ..distributed.fault_tolerance import chaos
        from ..observability import metrics
        cfg = self.scheduler.config
        now, active, drafts = step.now, step.active, step.drafts
        b_bucket, p_bucket = step.bucket
        cost = self.runner.decode_cost(step.bucket)
        modeled_s = None
        if cost and "flops" in cost:
            from ..observability.cost_model import StepCost
            sc = StepCost(flops=cost.get("flops", 0.0),
                          hbm_bytes=cost.get("bytes accessed", 0.0))
            modeled_s = sc.step_time_modeled_s()
        # per-step span for the whole batch: each covered request's
        # decode_compute grows by the modeled step cost — the SAME
        # float the finish stamp below is built from, so the interval
        # end and a final-step finish quantize identically
        step_tids = [s.trace_id for s in active
                     if s.trace_id is not None]
        if chaos.maybe_drop_decode_step(self.engine_id):
            # transient step failure: the tokens are discarded and NO
            # token log advances, so the next step recomputes the same
            # positions (same inputs -> same tokens; the KV rewrite is
            # idempotent; the drafts are a pure function of the
            # unchanged token log) — retry costs one modeled step. A
            # family's per-sequence STATE has moved with the discarded
            # step, though: its rows are re-prefilled instead
            metrics.inc("serving_retries_total")
            if self.cache.states:
                self._reprefill_moved(step, now)
            _flight_record(event="decode_step_dropped",
                           engine=self.engine_id, t=now,
                           dur=modeled_s or 0.0,
                           tids=step_tids or None,
                           chaos="drop_decode_step",
                           step=self.decode_steps + 1)
            self.decode_steps += 1
            return {"tokens": 0, "dropped": True}
        # tokens exist at the step's END: finishing at `now` would cut
        # the final step's cost out of the virtual-clock makespan and
        # overstate the benched tokens/s
        done_at = now + (modeled_s or 0.0)
        self.decode_steps += 1
        _flight_record(event="decode_step", engine=self.engine_id,
                       t=now, dur=modeled_s or 0.0,
                       tids=step_tids or None,
                       step=self.decode_steps, batch=len(active),
                       rows=step.counts["rows"] if drafts else None,
                       bucket=[b_bucket, p_bucket])
        emitted_total = 0
        accepted_total = 0
        rejected_total = 0
        ri = 0
        W = self.runner.family.row_positions    # positions a row yields
        for i, s in enumerate(active):
            n_rows = 1 + len(drafts.get(id(s), ()))
            if not step.kept[i]:
                ri += n_rows            # evicted or requeued meanwhile
                continue
            if self._block:
                # the row's block, B positions; tokens exist at a commit
                emitted_total += self._emit_block_row(
                    s, toks[ri * W:ri * W + W],
                    None if chosen is None else chosen[ri * W:ri * W + W],
                    done_at)
                ri += 1
                continue
            outs = [int(toks[ri + j]) for j in range(n_rows)]
            if chosen is not None:
                s.routed.append(chosen[ri:ri + 1])
            ri += n_rows
            if n_rows == 1:
                emitted = [outs[0]]
            else:
                room = s.request.max_new_tokens - len(s.generated)
                accepted, bonus = accept_drafts(drafts[id(s)], outs,
                                                room)
                emitted = accepted + [bonus]
                accepted_total += len(accepted)
                rejected_total += len(drafts[id(s)]) - len(accepted)
            for tok in emitted:
                s.table.append_slot()
                s.tokens.append(tok)
            if n_rows > 1:
                # rejected tail: its KV writes sit past num_tokens and
                # are overwritten before any read; surplus blocks roll
                # back to the allocator here
                s.table.truncate()
            emitted_total += len(emitted)
            if s.done:
                self.scheduler.finish(s, done_at)
        if accepted_total:
            metrics.inc("serving_spec_accepted_total", accepted_total)
            self.spec_accepted += accepted_total
        if rejected_total:
            metrics.inc("serving_spec_rejected_total", rejected_total)
            self.spec_rejected += rejected_total
        metrics.inc("serving_decode_tokens_total", emitted_total)
        self._gauge()
        extra = {"serving": 1,
                 "batch_occupancy": len(active) / cfg.max_batch}
        if modeled_s is not None:
            extra["modeled_step_s"] = modeled_s
        metrics.step_end(tokens=emitted_total, **extra)
        return {"tokens": emitted_total, "spec_accepted": accepted_total,
                "spec_rejected": rejected_total}

    def _reprefill_moved(self, step: "_Step", now: float) -> None:
        """The rows of a discarded step go back to the queue's front, as
        after an eviction: their state slots hold the state AFTER the
        step, which a repeat would read as the state before it (a
        recurrence or a convolution window is not idempotent, a K/V
        rewrite is). The re-prefill recomputes the tokens so far and
        rewrites the state."""
        moved = [s for s, kept in zip(step.active, step.kept)
                 if kept and s.state is SeqState.RUNNING]
        for s in reversed(moved):           # the oldest ends up in front
            self.scheduler.requeue_moved(s, now=now)
        self._state_reprefills += len(moved)
        self.state_reprefills += len(moved)

    def _read_block_rows(self, step: "_Step", toks) -> Dict[str, int]:
        """What ``decode.dispatch`` says of a block family's step as it
        is read back: positions its denoise passes fixed and tokens its
        commits bring (of the rows that kept their place)."""
        B = self._block
        fixed = committed = 0
        for i, s in enumerate(step.active):
            if not step.kept[i]:
                continue
            st = s.block
            if st.done < len(st.plan):
                fixed += st.plan[st.done]
            else:
                committed += min(
                    st.start + B - len(s.tokens),
                    s.request.max_new_tokens - len(s.generated))
        return {"tokens_fixed": fixed, "tokens_committed": committed}

    def _emit_block_row(self, seq: Sequence, row, chosen, done_at) -> int:
        """One sequence's pass as read back: ``row`` is its block after
        the pass (-1: still masked). A denoise pass updates what the
        host knows of the block; the commit's keys and values stay, so
        the table grows by the block, its tokens join the log (those
        past ``max_new_tokens`` are computed and not delivered) and the
        next block opens. Returns the tokens delivered."""
        st, B = seq.block, self._block
        commit = st.done == len(st.plan)
        seq.passes.append((st.start, np.array(row), chosen, commit))
        if not commit:
            st.masked = row < 0
            st.ids = np.where(st.masked, 0, row).astype(np.int32)
            st.done += 1
            return 0
        limit = len(seq.request.prompt) + seq.request.max_new_tokens
        new = st.ids[len(seq.tokens) - st.start:limit - st.start].tolist()
        seq.tokens.extend(new)
        seq.table.num_tokens = st.start + B
        seq.block = BlockInFlight(st.start + B, (), B, self._steps)
        self._stamp_first_token(seq, done_at)
        if seq.done:
            self.scheduler.finish(seq, done_at)
        return len(new)

    def tick(self, now: float = 0.0) -> Optional[dict]:
        """Convenience round for live serving: admissions then one
        decode step, both stamped with ``now``."""
        self.admit_and_prefill(now)
        return self.decode_once(now)

    # -- reporting -------------------------------------------------------
    def _gauge(self) -> None:
        from ..observability import metrics
        metrics.set_gauge("serving_queue_depth",
                          self.scheduler.queue_depth)
        metrics.set_gauge("serving_batch_occupancy",
                          len(self.scheduler.running())
                          / self.scheduler.config.max_batch)
        metrics.set_gauge("serving_kv_blocks_in_use",
                          self.allocator.used_count)
        metrics.set_gauge("serving_kv_blocks_high_water",
                          self.allocator.high_water)
        metrics.set_gauge("serving_decode_programs",
                          self.runner.num_decode_programs)
        if self.prefix_cache is not None:
            metrics.set_gauge(
                "serving_shared_kv_bytes",
                self.prefix_cache.shared_bytes(self.cache.block_bytes))
            metrics.set_gauge("serving_prefix_cached_blocks",
                              len(self.prefix_cache))
            self._flush_kv_counters()
        if self.host_tier is not None:
            metrics.set_gauge("serving_kv_host_tier_blocks",
                              len(self.host_tier))
            metrics.set_gauge("serving_kv_host_tier_bytes",
                              len(self.host_tier)
                              * self.cache.block_bytes)

    def _flush_kv_counters(self) -> None:
        """Emit KV-tier counter DELTAS into the metrics plane. Spills
        and fetches fire deep inside the allocator's reclaim hook and
        the cache's lookup, so the engine reconciles the cache's
        monotonic totals here (every _gauge call) instead of threading
        the metrics plane through the block layer."""
        from ..observability import metrics
        pc = self.prefix_cache
        totals = (("serving_kv_spill_blocks_total", pc.spills),
                  ("serving_kv_fetch_host_blocks_total", pc.host_fetches),
                  ("serving_kv_fetch_peer_blocks_total", pc.peer_fetches))
        for name, total in totals:
            delta = total - self._kv_counts.get(name, 0)
            if delta:
                metrics.inc(name, delta)
                self._kv_counts[name] = total

    @property
    def num_decode_programs(self) -> int:
        return self.runner.num_decode_programs

    @property
    def program_budget(self) -> int:
        return self.scheduler.config.program_budget

    def kv_high_water_bytes(self) -> int:
        return self.cache.bytes_for_blocks(self.allocator.high_water)

    def contiguous_cache_bytes(self) -> int:
        """The comparator: a contiguous per-slot max-seq-len cache for
        the full decode batch."""
        return self.cache.contiguous_bytes(self.config.max_batch,
                                           self.max_model_len)

    def idle(self) -> bool:
        """Nothing queued, nothing running, nothing in flight."""
        return not self.scheduler.waiting \
            and not self.scheduler.running() and self._ahead is None \
            and not self._firsts
