"""Continuous-batching scheduler: admit/evict per decode step.

The Orca iteration-level scheduling model (Yu et al. OSDI'22): the
decode batch is re-formed at EVERY step — finished sequences leave
immediately, waiting requests join as soon as a batch slot and KV
blocks are free — instead of the static-batch regime where the whole
batch waits for its slowest member.

Three policies live here, all host-side and deterministic:

* **Admission** (FIFO + prefill budget): waiting requests are admitted
  oldest-first when (a) a decode slot is free, (b) the allocator can
  cover their prompt blocks, and (c) the per-round prefill token
  budget is not exhausted. The budget is the prefill/decode
  disaggregation knob: prefill compute runs on its own lane (a
  separate instance in a disaggregated deployment; between decode
  steps on one chip), and capping admitted prefill tokens per round
  bounds how long the decode batch can go without a step even on the
  single-chip fallback.
* **Preemption by eviction** (LIFO victim): when a running sequence
  needs a block and the free list is empty, the NEWEST running
  sequence is evicted — all its blocks freed, state back to WAITING at
  the FRONT of the queue (it re-prefills prompt+generated-so-far on
  re-admission, the vLLM recompute strategy). LIFO keeps the oldest
  requests making progress, so no request starves.
* **Bucketed shapes**: the decode batch is padded to a fixed set of
  (batch, pages) buckets so the compiled decode program is reused
  across compositions — the serving bench gates that the number of
  compiled decode programs never exceeds ``len(batch_buckets) x
  len(page_buckets)``.
* **Admission control & load shedding** (PR 11, opt-in via
  ``SchedulerConfig.reliability``): a bounded admission queue with
  per-request priorities and deadlines. When the queue is full, the
  overload policy sheds the LOWEST-priority waiting request (ties:
  youngest) to admit a strictly-higher-priority arrival — in-flight
  sequences are always honored (eviction requeues, shedding only ever
  removes WAITING work). Expired deadlines are shed at every
  admission boundary against the caller's virtual clock.

Scheduler decisions (admit / evict / requeue / shed) land in the
flight-recorder ring (one-attribute-load no-op when off) so
``flight_doctor`` can post-mortem a serving crash.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .block_cache import (BlockAllocator, BlockTable, OutOfBlocksError,
                          blocks_for_tokens)
from .reliability import (DeadlineExceeded, QueueFullError,
                          ReliabilityConfig, ServingError,
                          flight_record as _flight_record)

__all__ = ["Request", "Sequence", "SeqState", "SchedulerConfig",
           "ContinuousBatchingScheduler"]


@dataclass
class Request:
    """One generation request as submitted by a client.

    ``priority`` (higher = more important) and ``deadline_t``
    (ABSOLUTE virtual-clock stamp, None = none) drive the admission
    controller; both default to the PR 9 don't-care values.
    ``trace_id`` is the STABLE identity the request-tracing plane keys
    spans by: ``req_id`` is re-keyed when a failover adoption moves
    the sequence to another engine, ``trace_id`` never changes (the
    engine defaults it to the original ``req_id``; the router stamps
    its fleet-global id)."""
    req_id: int
    prompt: List[int]
    max_new_tokens: int
    arrival_t: float = 0.0
    priority: int = 0
    deadline_t: Optional[float] = None
    trace_id: Optional[int] = None


class SeqState(enum.Enum):
    WAITING = "waiting"
    RUNNING = "running"
    FINISHED = "finished"
    SHED = "shed"


class Sequence:
    """Scheduler-side state of one request."""

    def __init__(self, request: Request, allocator: BlockAllocator):
        self.request = request
        self.tokens: List[int] = list(request.prompt)
        self.table = BlockTable(allocator)
        self.state = SeqState.WAITING
        self.first_token_t: Optional[float] = None
        self.finish_t: Optional[float] = None
        self.evictions = 0
        self.recoveries = 0          # corruption / engine-failure rebuilds
        self.error: Optional[ServingError] = None   # set when SHED
        # leading tokens whose KV came from the prefix cache at the
        # LAST admission (the engine scatters only past this point)
        self.prefix_cached_tokens = 0
        # KV-tier attribution for the LAST admission (ISSUE 16): how
        # many of the cached blocks were promoted from the host tier /
        # a DCN peer, and the peer transfer's modeled seconds — the
        # engine prices the spill_fetch stall from these
        self.kv_fetched_host = 0
        self.kv_fetched_peer = 0
        self.kv_peer_fetch_s = 0.0
        # earliest stamp migrated KV is on-device (a failover
        # migration's DCN transfer completes here; admission waits)
        self.kv_ready_t = 0.0
        # a routed family's record: the experts chosen for each token
        # the model was fed, [positions, expert layers, k] in pieces (a
        # prefill's, then one a decode step); ``ServingEngine.
        # routed_experts`` joins them
        self.routed: List = []
        # a block-diffusion family's: the block in flight as the host
        # last read it (``blockdiff.BlockInFlight``; None until the
        # first pass after a prefill), and the record of every pass
        # read back: (block start, the block's ids after the pass with
        # -1 where still masked — a commit's are the final tokens —,
        # the experts chosen for its rows, was it the commit)
        self.block = None
        self.passes: List = []

    def check(self) -> "Sequence":
        """Raise the typed error a post-submission failure recorded
        (shed / deadline / engine death); returns self when healthy."""
        if self.error is not None:
            raise self.error
        return self

    def rebind(self, allocator: BlockAllocator) -> None:
        """Point the sequence at a FRESH empty table on ``allocator``
        WITHOUT releasing the old blocks — used when the old table is
        untrustworthy (corruption) or gone (its engine died). The
        token log is host state and survives; re-admission re-prefills
        it, which the eviction-exactness guarantee proves is
        token-for-token identical to never having lost the KV."""
        self.table = BlockTable(allocator)
        self.prefix_cached_tokens = 0     # re-resolved at re-admission

    @property
    def req_id(self) -> int:
        return self.request.req_id

    @property
    def trace_id(self) -> Optional[int]:
        return self.request.trace_id

    @property
    def priority(self) -> int:
        return self.request.priority

    @property
    def deadline_t(self) -> Optional[float]:
        return self.request.deadline_t

    @property
    def num_cached(self) -> int:
        return self.table.num_tokens

    @property
    def generated(self) -> List[int]:
        return self.tokens[len(self.request.prompt):]

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.request.max_new_tokens

    def __repr__(self):
        return (f"Sequence(req={self.req_id}, state={self.state.value}, "
                f"tokens={len(self.tokens)}, cached={self.num_cached})")


@dataclass
class SchedulerConfig:
    max_batch: int = 8
    # power-of-two-ish ladders; padded shapes key the compiled decode
    # programs, so these two lists BOUND the program count
    batch_buckets: Tuple[int, ...] = (1, 2, 4, 8)
    page_buckets: Tuple[int, ...] = (2, 4, 8, 16)
    # prefill/decode disaggregation: max prompt tokens admitted per
    # scheduling round (0 = unlimited)
    prefill_budget_tokens: int = 512
    # admission control / load shedding (None = PR 9 behavior:
    # unbounded queue, no deadlines)
    reliability: Optional[ReliabilityConfig] = None
    # cache slots a sequence's step writes: 1 (a token a step), or the
    # block length of a family whose step carries a block of positions
    slots_per_step: int = 1

    def __post_init__(self):
        self.batch_buckets = tuple(sorted(set(self.batch_buckets)))
        self.page_buckets = tuple(sorted(set(self.page_buckets)))
        if self.batch_buckets[-1] < self.max_batch:
            raise ValueError("largest batch bucket must cover max_batch")

    @property
    def program_budget(self) -> int:
        return len(self.batch_buckets) * len(self.page_buckets)

    def batch_bucket(self, n: int) -> int:
        for b in self.batch_buckets:
            if n <= b:
                return b
        raise ValueError(f"batch {n} exceeds largest bucket "
                         f"{self.batch_buckets[-1]}")

    def page_bucket(self, n: int) -> int:
        for p in self.page_buckets:
            if n <= p:
                return p
        raise ValueError(f"{n} pages exceed largest bucket "
                         f"{self.page_buckets[-1]}")


class ContinuousBatchingScheduler:
    """Pure-host scheduling core; the engine owns the actual compute.

    The engine drives it as::

        admitted = sched.admit()            # -> seqs to prefill
        ...prefill each, mark running...
        batch = sched.running()             # current decode batch
        victims = sched.reserve_decode_slots()   # may evict
        ...run one decode step over sched.running()...
    """

    def __init__(self, config: SchedulerConfig, allocator: BlockAllocator):
        self.config = config
        self.allocator = allocator
        self.reliability = config.reliability or ReliabilityConfig()
        # CoW prefix cache (engine-installed; None = PR 9 behavior):
        # admission consults it so a hit's shared blocks don't count
        # against the free list
        self.prefix_cache = None
        self.engine_id = 0          # mirrored by the owning engine
        self.waiting: List[Sequence] = []
        self._running: List[Sequence] = []      # admission order
        self.finished: List[Sequence] = []
        self.shed: List[Sequence] = []
        self.total_evictions = 0
        self.total_shed = 0
        # SLO ledger (reliability.slo opt-in): good/bad request counts
        # driving the burn-rate gauge
        self.slo_good = 0
        self.slo_bad = 0

    # -- introspection ---------------------------------------------------
    def running(self) -> List[Sequence]:
        return list(self._running)

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    @staticmethod
    def _in_flight(seq: Sequence) -> bool:
        """True once a sequence has ever been admitted: an evicted or
        recovered sequence waiting to resume is IN-FLIGHT work (tokens
        already accepted), not a fresh arrival — it is never a shed
        candidate and its deadline no longer applies (deadlines gate
        ADMISSION; admitted work runs to completion)."""
        return seq.evictions > 0 or seq.recoveries > 0

    # -- submission ------------------------------------------------------
    def submit(self, seq: Sequence) -> None:
        """Enqueue a new request. With a bounded admission queue
        (``reliability.max_queue_depth``), a full queue either sheds
        the lowest-priority waiting request (only if STRICTLY lower
        priority than the arrival — ties reject the arrival, FIFO
        fairness) or raises :class:`QueueFullError`. In-flight
        sequences are never candidates: eviction requeues bypass this
        bound via :meth:`requeue_front`, and an evicted/recovered
        sequence back in the queue is exempt from victim selection."""
        depth = self.reliability.max_queue_depth
        if depth is not None and len(self.waiting) >= depth:
            victim = None
            shippable = [s for s in self.waiting
                         if not self._in_flight(s)]
            if self.reliability.shed_on_full and shippable:
                # lowest priority first; ties broken by YOUNGEST
                # (latest queue position) so older work keeps its place
                victim = min(reversed(shippable),
                             key=lambda s: s.priority)
            if victim is None or victim.priority >= seq.priority:
                raise QueueFullError(
                    f"admission queue full ({len(self.waiting)} >= "
                    f"{depth}) and no waiting request has priority < "
                    f"{seq.priority}")
            self._shed(victim, QueueFullError(
                f"shed (priority {victim.priority}) for arrival "
                f"req {seq.req_id} (priority {seq.priority})"),
                now=seq.request.arrival_t)
        self.waiting.append(seq)

    def requeue_front(self, seq: Sequence, now: Optional[float] = None,
                      cause: str = "evict") -> None:
        """Put a previously-admitted sequence back at the FRONT of the
        queue (eviction, corruption recovery, engine-failover
        adoption): preempted work resumes before new arrivals and is
        exempt from the admission bound — in-flight is honored."""
        seq.state = SeqState.WAITING
        self.waiting.insert(0, seq)
        _flight_record(event="requeue", req=seq.req_id,
                       tid=seq.trace_id, t=now, cause=cause,
                       engine=self.engine_id, tokens=len(seq.tokens))

    # -- load shedding ---------------------------------------------------
    def _shed(self, seq: Sequence, err: ServingError,
              now: Optional[float] = None) -> None:
        self.waiting.remove(seq)
        self.mark_shed(seq, err, now=now)

    def mark_shed(self, seq: Sequence, err: ServingError,
                  now: Optional[float] = None) -> None:
        """Shed bookkeeping for a sequence NOT in the waiting queue —
        e.g. a recovered fresh arrival the adopting engine's bounded
        queue refuses at failover time."""
        from ..observability import metrics
        seq.state = SeqState.SHED
        seq.error = err
        self.shed.append(seq)
        self.total_shed += 1
        reason = ("deadline" if isinstance(err, DeadlineExceeded)
                  else "overload")
        metrics.inc("serving_shed_total", reason=reason)
        if reason == "deadline":
            metrics.inc("serving_deadline_exceeded_total")
        if self.reliability.slo is not None:
            # a shed request consumed error budget without an answer
            self._note_slo_verdict(False)
        _flight_record(event="shed", req=seq.req_id, tid=seq.trace_id,
                       t=now, reason=reason, engine=self.engine_id,
                       priority=seq.priority)

    def expire_deadlines(self, now: float) -> List[Sequence]:
        """Shed every never-admitted WAITING sequence whose deadline
        has passed — called at each admission boundary. In-flight work
        is honored to completion: RUNNING sequences are untouched, and
        an evicted/recovered sequence back in the queue already has
        accepted tokens, so its (admission) deadline no longer
        applies."""
        expired = [s for s in self.waiting
                   if s.deadline_t is not None and s.deadline_t < now
                   and not self._in_flight(s)]
        for s in expired:
            self._shed(s, DeadlineExceeded(
                f"req {s.req_id} deadline {s.deadline_t:.6f} < now "
                f"{now:.6f} before admission"), now=now)
        return expired

    # -- admission -------------------------------------------------------
    def admit(self, now: float = 0.0) -> List[Sequence]:
        """Pick waiting sequences to prefill this round: FIFO, bounded
        by free decode slots, allocator coverage for the WHOLE current
        token list (prompt + any pre-eviction generation), and the
        prefill token budget. Admitted sequences get their blocks
        allocated here; the engine must prefill and mark them RUNNING.
        A request whose blocks cannot be covered blocks the queue
        (FIFO — skipping it would starve long prompts forever).
        Expired deadlines are shed first, against ``now``."""
        self.expire_deadlines(now)
        admitted: List[Sequence] = []
        budget = self.config.prefill_budget_tokens or float("inf")
        spent = 0
        while self.waiting:
            seq = self.waiting[0]
            if len(self._running) + len(admitted) >= self.config.max_batch:
                break
            if seq.kv_ready_t > now:
                # migrated KV still on the wire (DCN transfer from a
                # dead engine's host tier): admitting before it lands
                # would prefill positions the migration covers —
                # head-of-line until the modeled transfer completes
                break
            need_tokens = len(seq.tokens)
            cached: List[int] = []
            if self.prefix_cache is not None and not seq.table.blocks:
                # peek first (no refcount bump): the hit only commits
                # once admission is certain, so a blocked head-of-line
                # request never leaks shared references
                cached, _ = self.prefix_cache.lookup(seq.tokens,
                                                     share=False)
            step = self.config.slots_per_step
            need_blocks = blocks_for_tokens(
                need_tokens + step, self.allocator.block_size) - len(cached)
            if spent and spent + need_tokens > budget:
                break                      # budget spent: next round
            if not self.allocator.can_admit(need_blocks):
                break       # head-of-line until blocks (or a slot) free
            self.waiting.pop(0)
            seq.prefix_cached_tokens = 0
            seq.kv_fetched_host = 0
            seq.kv_fetched_peer = 0
            seq.kv_peer_fetch_s = 0.0
            shared: List[int] = []
            spills_before = (self.prefix_cache.spills
                             if self.prefix_cache is not None else 0)
            if self.prefix_cache is not None:
                from ..observability import metrics
                shared, n_cached = self.prefix_cache.lookup(seq.tokens)
                if shared:
                    seq.table.attach_shared(shared)
                    seq.prefix_cached_tokens = n_cached
                    # tier attribution: the engine charges the
                    # spill_fetch stall for promoted blocks
                    seq.kv_fetched_host = \
                        self.prefix_cache.last_host_fetched
                    seq.kv_fetched_peer = \
                        self.prefix_cache.last_peer_fetched
                    seq.kv_peer_fetch_s = \
                        self.prefix_cache.last_peer_fetch_s
                    metrics.inc("serving_prefix_hits_total")
                    metrics.inc("serving_prefix_hit_blocks_total",
                                len(shared))
                else:
                    metrics.inc("serving_prefix_misses_total")
            try:
                seq.table.ensure_capacity(need_tokens + step)
            except OutOfBlocksError:
                # the can_allocate check above counted reclaimable
                # cached blocks as headroom — but THIS request's own
                # cached prefix may be exactly that headroom, and the
                # commit share just pinned it (refcount 2 = no longer
                # reclaimable). Undo the hit and put the request back
                # at the head: it stays head-of-line until real blocks
                # free up, nothing is lost or leaked.
                if shared:
                    self.allocator.free(shared)
                seq.table.blocks = []
                seq.prefix_cached_tokens = 0
                self.waiting.insert(0, seq)
                break
            if self.prefix_cache is not None:
                # publish the prompt's full blocks NOW, not after
                # prefill: a same-round sibling with the same system
                # prompt can then share them — every admitted
                # sequence's prefill scatters before any decode reads,
                # so the registered blocks' KV exists by first use
                self.prefix_cache.insert(seq.request.prompt,
                                         seq.table.blocks,
                                         len(seq.request.prompt))
                spilled = self.prefix_cache.spills - spills_before
                if spilled:
                    # this admission's allocations forced cold cached
                    # blocks down to the host tier — join key is the
                    # request whose admission applied the pressure
                    _flight_record(event="kv_spill", req=seq.req_id,
                                   tid=seq.trace_id, t=now,
                                   engine=self.engine_id,
                                   blocks=spilled)
            spent += need_tokens
            admitted.append(seq)
            _flight_record(event="admit", req=seq.req_id,
                           tid=seq.trace_id, t=now, tokens=need_tokens,
                           engine=self.engine_id,
                           blocks=len(seq.table.blocks),
                           shared_blocks=(len(seq.table.blocks)
                                          - need_blocks) or None)
        return admitted

    def mark_running(self, seq: Sequence) -> None:
        seq.state = SeqState.RUNNING
        self._running.append(seq)

    # -- decode-step block reservation ----------------------------------
    def reserve_decode_slots(self, seqs: Optional[List[Sequence]] = None,
                             now: Optional[float] = None,
                             slots: Optional[List[int]] = None
                             ) -> List[Sequence]:
        """Make sure every sequence in ``seqs`` (default: all running)
        has block slots for the token(s) the next decode step appends
        — ``slots[i]`` per sequence (default 1; a speculative verify
        round reserves ``1 + len(drafts)``, a block-diffusion pass its
        whole block) — evicting LIFO on
        exhaustion. Returns the evicted sequences (already requeued).
        ``now`` stamps the eviction spans."""
        victims: List[Sequence] = []
        todo = list(self._running) if seqs is None else list(seqs)
        want = [1] * len(todo) if slots is None else \
            [max(1, int(s)) for s in slots]
        if len(want) != len(todo):
            raise ValueError("slots must parallel seqs")
        i = 0
        while i < len(todo):
            seq = todo[i]
            if seq.state is not SeqState.RUNNING:
                i += 1      # evicted while reserving an earlier seq
                continue
            try:
                seq.table.ensure_capacity(seq.num_cached + want[i])
                i += 1
            except OutOfBlocksError:
                victim = self._running[-1]
                self._evict(victim, now=now)
                victims.append(victim)
                if victim is seq:
                    continue    # re-check the same index (list shrank)
        return victims

    def _evict(self, seq: Sequence, now: Optional[float] = None) -> None:
        self._running.remove(seq)
        seq.table.release()
        seq.evictions += 1
        self.total_evictions += 1
        _flight_record(event="evict", req=seq.req_id, tid=seq.trace_id,
                       t=now, engine=self.engine_id,
                       evictions=seq.evictions)
        # front of the queue: preempted work resumes before new arrivals
        self.requeue_front(seq, now=now, cause="evict")

    def requeue_corrupt(self, seq: Sequence,
                        now: Optional[float] = None) -> None:
        """Pull a RUNNING sequence whose block table can no longer be
        trusted (chaos ``corrupt_block_table``, a real scribble): the
        table is REBOUND to a fresh empty one instead of released —
        freeing corrupted ids could double-free a live block. The
        caller must rebuild the allocator's free list from the
        surviving tables (``BlockAllocator.rebuild_free_list``)."""
        self._running.remove(seq)
        seq.rebind(self.allocator)
        seq.recoveries += 1
        self.requeue_front(seq, now=now, cause="corrupt")

    def requeue_moved(self, seq: Sequence,
                      now: Optional[float] = None) -> None:
        """Pull a RUNNING sequence whose per-sequence state on the
        device no longer matches its token log (a decode step moved it
        and its tokens were discarded): blocks and state slot are
        released as at an eviction, and the re-prefill from the token
        log rewrites both. Counted as a recovery, not an eviction."""
        self._running.remove(seq)
        seq.table.release()
        seq.recoveries += 1
        self.requeue_front(seq, now=now, cause="state_moved")

    # -- completion ------------------------------------------------------
    def finish(self, seq: Sequence, now: float = 0.0) -> None:
        self._running.remove(seq)
        seq.table.release()
        seq.state = SeqState.FINISHED
        seq.finish_t = now
        self.finished.append(seq)
        self._note_slo(seq, now)
        _flight_record(event="finish", req=seq.req_id, tid=seq.trace_id,
                       t=now, engine=self.engine_id,
                       tokens=len(seq.generated))

    # -- SLO accounting --------------------------------------------------
    def _note_slo(self, seq: Sequence, now: float) -> None:
        """Evaluate the engine's SLO targets against one FINISHED
        request (reliability.slo opt-in): TTFT, TPOT, e2e — all on the
        caller's clock, so the verdicts are as deterministic as the
        clock. Per-dimension verdicts and the good/bad totals flow
        through the metrics plane; the burn-rate gauge follows."""
        slo = self.reliability.slo
        if slo is None:
            return
        from ..observability import metrics
        arrival = seq.request.arrival_t
        first = seq.first_token_t if seq.first_token_t is not None else now
        gen = len(seq.generated)
        dims = {
            "ttft": (slo.ttft_target_s, first - arrival),
            "tpot": (slo.tpot_target_s,
                     (now - first) / (gen - 1) if gen > 1 else 0.0),
            "e2e": (slo.e2e_target_s, now - arrival),
        }
        good = True
        for name, (target, value) in dims.items():
            if target is None:
                continue
            ok = value <= target
            good = good and ok
            metrics.inc("serving_slo_checks_total", slo=name,
                        verdict="good" if ok else "bad")
        self._note_slo_verdict(good)

    def _note_slo_verdict(self, good: bool) -> None:
        from ..observability import metrics
        slo = self.reliability.slo
        if good:
            self.slo_good += 1
            metrics.inc("serving_slo_good_total")
        else:
            self.slo_bad += 1
            metrics.inc("serving_slo_bad_total")
        total = self.slo_good + self.slo_bad
        bad_frac = self.slo_bad / total if total else 0.0
        metrics.set_gauge("serving_slo_burn_rate",
                          bad_frac / slo.error_budget)

    # -- bucket shape of the current batch -------------------------------
    def decode_bucket(self, seqs: Optional[List[Sequence]] = None
                      ) -> Tuple[int, int]:
        """(batch_bucket, page_bucket) for the NEXT decode step over
        ``seqs`` (default: all running) — the compiled-program cache
        key; the engine passes the ready subset."""
        seqs = self._running if seqs is None else seqs
        n = len(seqs)
        pages = max((len(s.table.blocks) for s in seqs), default=1)
        return (self.config.batch_bucket(max(n, 1)),
                self.config.page_bucket(max(pages, 1)))
