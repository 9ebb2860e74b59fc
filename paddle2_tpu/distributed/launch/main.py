"""Distributed launcher: python -m paddle2_tpu.distributed.launch
(reference python/paddle/distributed/launch/main.py:23 + controller/).

TPU-native model: one PROCESS per HOST drives all local chips (PJRT), so
--nproc_per_node defaults to 1 and multi-host scaling is coordinated via
jax.distributed (coordinator = --master host:port; the reference's
TCPStore rendezvous analog). The launcher:

  * wires rank env vars (PADDLE_TRAINER_ID/.., JAX coordinator vars),
  * spawns + babysits worker processes, streaming logs per rank,
  * on a worker failure kills the gang (comm-watchdog parity,
    SURVEY §5.3) and, with --max_restarts > 0, relaunches the remaining
    gang — the elastic manager's restart loop (fleet/elastic/manager.py),
  * with --rdzv_master (+ --rdzv_serve on node 0) joins the HTTP
    rendezvous job (launch/master.py — the reference's
    controllers/master.py pod/job membership): every membership change
    rescales every node's gang, giving multi-node elastic scale-IN
    (dead-pod sweep) and scale-UP (node rejoin).
"""

from __future__ import annotations

import argparse
import glob
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import List, Optional, Tuple


def _parse(argv: Optional[List[str]] = None):
    p = argparse.ArgumentParser(
        prog="paddle2_tpu.distributed.launch",
        description="TPU distributed launcher")
    p.add_argument("--master", default=None,
                   help="coordinator host:port (multi-host rendezvous)")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", "--rank", type=int, dest="node_rank",
                   default=int(os.environ.get("PADDLE_NODE_RANK", 0)))
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per host (1 = SPMD over local chips)")
    p.add_argument("--devices", "--gpus", dest="devices", default=None,
                   help="visible accelerator ids (comma list)")
    p.add_argument("--log_dir", default=None)
    p.add_argument("--max_restarts", type=int, default=0,
                   help="elastic restart budget after worker failure")
    p.add_argument("--elastic_rescale", action="store_true",
                   help="on worker failure relaunch at the SURVIVING "
                        "world size (scale-in; reference ElasticManager "
                        "scale semantics) instead of same-size restart")
    p.add_argument("--job_id", default="default")
    p.add_argument("--rdzv_master", default=None,
                   help="rendezvous master endpoint (host:port). Enables "
                        "the multi-node elastic agent: pods join/leave, "
                        "a version bump rescales every node's gang "
                        "(reference launch/controllers/master.py)")
    p.add_argument("--rdzv_serve", action="store_true",
                   help="host the rendezvous master in THIS launcher "
                        "(typically node_rank 0)")
    p.add_argument("--rdzv_beat", type=float, default=5.0,
                   help="agent heartbeat / version-poll interval (s)")
    p.add_argument("--rdzv_dead", type=float, default=30.0,
                   help="pod heartbeat timeout before the master sweeps "
                        "it (s)")
    p.add_argument("--preflight", action="store_true",
                   help="run the device self-test + loopback echo "
                        "(fault_tolerance/health.py) BEFORE gang "
                        "formation; a failing host is written to the "
                        "quarantine store (PADDLE_QUARANTINE_DIR) and "
                        "the launcher refuses to start")
    p.add_argument("--preempt_grace", type=float, default=30.0,
                   help="seconds workers get to checkpoint-then-exit "
                        "after the launcher receives SIGTERM (TPU "
                        "preemption notice); extended while a worker's "
                        "save-in-flight marker exists")
    p.add_argument("--mttr_budget", type=float, default=0.0,
                   help="mean-time-to-recovery budget (seconds) for a "
                        "restart: the launcher times failure-detection "
                        "-> respawn, records it in the elastic event "
                        "stream, and warns when the budget is blown "
                        "(0 = record only). Forwarded to workers as "
                        "PADDLE_MTTR_BUDGET so the instrumented train "
                        "step can account its compile+first-step time "
                        "against the same budget. bench.py --elastic "
                        "gates the full kill->first-step MTTR on top")
    p.add_argument("--compile_cache_dir", default=None,
                   help="persistent XLA compilation cache directory "
                        "forwarded to workers (PADDLE2_TPU_CACHE_DIR); "
                        "yields to JAX_COMPILATION_CACHE_DIR. Default: "
                        "the workers' own fixed <checkout>/.jax_cache, "
                        "which is on — compile+first-step is pure MTTR "
                        "on every respawn/rescale, and a warm cache "
                        "turns the recovery recompile into a cache "
                        "read ('none' disables)")
    p.add_argument("--metrics_dir", default=None,
                   help="always-on metrics plane directory forwarded "
                        "to workers as PADDLE_METRICS_DIR: every rank "
                        "streams metrics_rank_N.jsonl (step-time "
                        "breakdown, tokens/s, reliability counters) "
                        "that `python -m paddle2_tpu.tools."
                        "perf_doctor` reads; an existing "
                        "PADDLE_METRICS_DIR in the operator env wins")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


# a launcher that refuses to run because this host (or every local
# slot) sits in the quarantine store exits with this code — distinct
# from worker failures so orchestration can reschedule elsewhere
QUARANTINED_EXIT_CODE = 113


def _node_for_slot(slot: int) -> str:
    """Quarantine identity of one worker slot: the host, suffixed by
    the SPAWN slot (stable across rescales — a renumbered rank keeps
    its original slot id, so a verdict follows the physical position,
    not the shifting rank). One process per host (the TPU-native
    default) makes this effectively per-host; several slots on one
    host get per-chip granularity."""
    import socket
    return f"{socket.gethostname()}/s{slot}"


def _quarantine_store():
    """The persistent quarantine store, or None when the operator has
    not opted in (no PADDLE_QUARANTINE_DIR)."""
    try:
        from ..fault_tolerance.health import get_store
        store = get_store()
        return store if store.enabled else None
    except Exception:
        return None


def _filter_quarantined_slots(slots: List[int]) -> Tuple[List[int],
                                                         List[int]]:
    """Split ``slots`` into (live, excluded) against the quarantine
    store: a slot is excluded when its slot identity OR the whole host
    is quarantined. Consulted on EVERY (re-)formation — the store is
    how a fingerprint-vote verdict from the previous incarnation
    reaches the next rendezvous."""
    store = _quarantine_store()
    if store is None:
        return list(slots), []
    import socket
    host = socket.gethostname()
    host_bad = store.is_quarantined(host)
    live, excluded = [], []
    for s in slots:
        if host_bad or store.is_quarantined(_node_for_slot(s)):
            excluded.append(s)
        else:
            live.append(s)
    return live, excluded


def _announce_quarantine(excluded: List[int], generation: int) -> None:
    store = _quarantine_store()
    for s in excluded:
        verdict = (store.entry(_node_for_slot(s)) if store else None) \
            or {}
        print(f"[launch] slot {s} ({_node_for_slot(s)}) is QUARANTINED"
              f" ({verdict.get('reason', 'unknown')}) — excluded from "
              f"this formation", file=sys.stderr)
        _elastic_event("quarantine", host=_node_for_slot(s), slot=s,
                       reason=verdict.get("reason"),
                       evidence=str(verdict.get("evidence"))[:300],
                       generation=generation)


def _run_preflight() -> bool:
    """--preflight: device self-test + loopback echo before any gang
    forms. Returns False (and quarantines this host) on failure."""
    try:
        from ..fault_tolerance.health import preflight
    except Exception as e:
        print(f"[launch] preflight unavailable: {e}", file=sys.stderr)
        return True
    report = preflight()
    if report.ok:
        print(f"[launch] preflight ok: {report.probe} digest="
              f"{report.digest} ({report.device})", file=sys.stderr)
        return True
    print(f"[launch] PREFLIGHT FAILED: {report.reason} — host "
          f"quarantined; refusing to form a gang", file=sys.stderr)
    return False


def _marker_prefix() -> str:
    """Shared path prefix for preemption save-in-flight markers: each
    worker's PreemptionGuard touches ``<prefix>.<rank>`` while its final
    checkpoint is being written; the launcher extends its SIGTERM grace
    period while any such marker exists."""
    return os.path.join(tempfile.gettempdir(),
                        f"p2t_preempt_{os.getpid()}")


def _launch_session() -> str:
    """Unique id of THIS launcher incarnation. Workers get it as
    PADDLE_LAUNCH_SESSION: checkpoint generation fencing compares
    restart generations only within one session, so a fresh launch of
    the same job is never fenced by a stale generation file."""
    import socket
    return f"{socket.gethostname()}-{os.getpid()}-{int(time.time())}"


_SESSION = None


def _worker_env(args, local_rank: int, generation: int = 0) -> dict:
    global _SESSION
    if _SESSION is None:
        _SESSION = _launch_session()
    env = dict(os.environ)
    world = args.nnodes * args.nproc_per_node
    rank = args.node_rank * args.nproc_per_node + local_rank
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_NNODES": str(args.nnodes),
        "PADDLE_JOB_ID": args.job_id,
        "PADDLE_PREEMPT_MARKER": f"{_marker_prefix()}.{rank}",
        # gang restart generation: flight-recorder dump headers carry it
        # and CheckpointManager fences latest-pointer commits on it, so
        # a zombie pre-restart rank cannot clobber the new lineage. It
        # bumps on EVERY re-formation, deliberate scale events included
        "PADDLE_RESTART_GENERATION": str(generation),
        "PADDLE_LAUNCH_SESSION": _SESSION,
    })
    if args.mttr_budget:
        # the worker half of the MTTR ledger: the instrumented train
        # step accounts compile+first-step against the same budget the
        # launcher's detect->respawn span is charged to
        env["PADDLE_MTTR_BUDGET"] = str(args.mttr_budget)
    cache = _compile_cache_dir(args)
    # precedence: JAX_COMPILATION_CACHE_DIR (JAX reads it; nothing else
    # is set), then the operator's exported choice, then the option
    if cache is not None and not any(
            k in os.environ for k in (
                "JAX_COMPILATION_CACHE_DIR", "PADDLE2_TPU_CACHE_DIR",
                "FLAGS_compilation_cache_dir")):
        env["PADDLE2_TPU_CACHE_DIR"] = cache
    if args.metrics_dir and "PADDLE_METRICS_DIR" not in os.environ:
        # workers auto-enable on import (PADDLE_TRAINER_ID guard);
        # an operator-exported PADDLE_METRICS_DIR wins, same
        # precedence as the compile cache above
        env["PADDLE_METRICS_DIR"] = args.metrics_dir
    if args.master:
        env.update({
            "PADDLE_MASTER": args.master,
            # jax.distributed.initialize() reads these
            "JAX_COORDINATOR_ADDRESS": args.master,
            "JAX_NUM_PROCESSES": str(world),
            "JAX_PROCESS_ID": str(rank),
        })
    if args.devices is not None:
        env["CUDA_VISIBLE_DEVICES"] = args.devices
        env["TPU_VISIBLE_DEVICES"] = args.devices
    return env


def _compile_cache_dir(args) -> Optional[str]:
    """The ``PADDLE2_TPU_CACHE_DIR`` workers inherit: the explicit
    ``--compile_cache_dir`` ('none' = '' = off), else ``None`` — the
    workers' own default is the one fixed path in the checkout
    (``flags.DEFAULT_COMPILE_CACHE_DIR``), ON, so a respawned worker's
    recompile is a cache read without the launcher inventing a
    directory (a path built from a temporary directory or a job id
    never hits on a fresh machine)."""
    if args.compile_cache_dir is None:
        return None
    if str(args.compile_cache_dir).lower() in ("none", "off", ""):
        return ""
    return args.compile_cache_dir


def _spawn(args, generation: int = 0,
           slots: Optional[List[int]] = None) -> List[subprocess.Popen]:
    procs = []
    slots = list(range(args.nproc_per_node)) if slots is None else slots
    for lr, slot in enumerate(slots):
        cmd = [sys.executable, args.training_script] \
            + args.training_script_args
        stdout = stderr = None
        log_path = None
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            rank = args.node_rank * args.nproc_per_node + lr
            log_path = os.path.join(args.log_dir, f"workerlog.{rank}")
            f = open(log_path, "ab")
            stdout = stderr = f
        env = _worker_env(args, lr, generation)
        # quarantine identity: ranks renumber across rescales, the
        # SPAWN SLOT does not — a fingerprint-vote verdict written by
        # this worker's peers names a stable physical position
        env["PADDLE_NODE_ID"] = _node_for_slot(slot)
        p = subprocess.Popen(cmd, env=env, stdout=stdout, stderr=stderr)
        p.log_path = log_path
        procs.append(p)
    return procs


def _surface_failure_logs(procs, n_tail: int = 30) -> None:
    """Reference launch/watcher.py behavior: on gang failure, surface the
    tail of each failed worker's log so the operator sees WHY without
    digging through per-rank files."""
    from ..fleet.elastic import ELASTIC_EXIT_CODE
    for i, p in enumerate(procs):
        rc = p.poll()
        # only workers that died on their OWN with a real error: skip
        # survivors our teardown signalled (_torn_down, set by _watch)
        # and deliberate scale-event exits — their tails would bury the
        # actual cause. A worker killed by an EXTERNAL signal (SIGSEGV,
        # OOM SIGKILL → negative rc) IS the original failure and must
        # surface its tail.
        if rc is None or rc == 0 or rc == ELASTIC_EXIT_CODE \
                or getattr(p, "_torn_down", False) \
                or not getattr(p, "log_path", None):
            continue
        try:
            with open(p.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - 8192))
                tail = f.read().decode("utf-8", "replace")
            lines = tail.splitlines()[-n_tail:]
            print(f"[launch] ---- worker {i} (rc={rc}) log tail "
                  f"({p.log_path}) ----", file=sys.stderr)
            for ln in lines:
                print(f"[launch] | {ln}", file=sys.stderr)
        except OSError:
            pass


def _surface_flight_dumps() -> None:
    """Collect surviving flight-recorder dumps when the gang dies: each
    worker dumps its event ring to PADDLE_FLIGHT_DIR on its own terminal
    fault (exception, timeout, SIGTERM); the launcher's job is to point
    the operator at whatever evidence survived — including dumps from
    ranks that were reaped without writing one themselves (their
    absence is itself a clue the doctor reports)."""
    flight_dir = os.environ.get("PADDLE_FLIGHT_DIR")
    if not flight_dir:
        return
    try:
        from ..fault_tolerance.flight_recorder import list_dumps
        dumps = [os.path.basename(p) for p in list_dumps(flight_dir)]
    except Exception:
        dumps = []
    if dumps:
        print(f"[launch] flight-recorder dumps collected in "
              f"{flight_dir}: {', '.join(dumps)}", file=sys.stderr)
        print(f"[launch] diagnose with: python -m "
              f"paddle2_tpu.tools.flight_doctor {flight_dir}",
              file=sys.stderr)
    else:
        print(f"[launch] no flight-recorder dumps found in "
              f"{flight_dir} (workers died before dumping?)",
              file=sys.stderr)


def _prune_gossip(live_world: int) -> None:
    """Elastic scale-in: drop step-time gossip of ranks that left the
    gang so straggler attribution stops accusing dead ranks."""
    if not os.environ.get("PADDLE_STEP_GOSSIP_DIR"):
        return
    try:
        from ..watchdog import prune_gossip
        pruned = prune_gossip(live_world)
        if pruned:
            print(f"[launch] pruned step gossip of departed ranks "
                  f"{pruned}", file=sys.stderr)
    except Exception:
        pass


def _prune_departed(live_world: int, job_id: Optional[str] = None) -> None:
    """Scale-event hygiene, all three stores at once: step-time gossip
    (straggler attribution), flight-recorder dumps (post-mortem
    evidence of the live lineage only), and buddy-replica slots (a
    departed rank's stale snapshot must never be restored).
    ``job_id`` pins the default replica store to the workers' job (the
    launcher injects PADDLE_JOB_ID into THEIR env, not its own)."""
    _prune_gossip(live_world)
    try:
        from ..fault_tolerance.flight_recorder import prune_ranks
        pruned = prune_ranks(live_world)
        if pruned:
            print(f"[launch] pruned flight-recorder dumps of departed "
                  f"ranks {pruned}", file=sys.stderr)
    except Exception:
        pass
    try:
        # covers the default /dev/shm store too (PADDLE_REPLICA_DIR is
        # optional for workers); prune_store no-ops on a missing dir
        from ..fault_tolerance.replica import prune_store
        removed = prune_store(live_world, job=job_id)
        if removed:
            print(f"[launch] pruned buddy replicas of departed "
                  f"ranks: {', '.join(removed)}", file=sys.stderr)
    except Exception:
        pass


def _elastic_event(kind: str, **fields) -> None:
    """Launcher-side ``elastic.*`` event: appended to the flight dir's
    ``elastic_events.jsonl`` (no-op without PADDLE_FLIGHT_DIR)."""
    try:
        from ..fault_tolerance.flight_recorder import append_elastic_event
        append_elastic_event(kind, **fields)
    except Exception:
        pass


class _PreemptForwarder:
    """Launcher-side half of preemption safety: on SIGTERM, forward the
    signal to every live worker (whose PreemptionGuard turns it into
    checkpoint-then-exit at the next step boundary) and grant a grace
    period before SIGKILL. The deadline EXTENDS while any worker's
    save-in-flight marker (``<_marker_prefix()>.<rank>``) exists — a
    final checkpoint write is never truncated by the kill — bounded by a
    10x hard cap so a leaked marker can't wedge the launcher."""

    def __init__(self, grace: float):
        self.grace = max(0.1, grace)
        self.procs: List[subprocess.Popen] = []
        self.fired = threading.Event()
        self._prev = None

    def install(self) -> "_PreemptForwarder":
        try:
            self._prev = signal.signal(signal.SIGTERM, self._handle)
        except ValueError:        # non-main thread (embedded): poll-only
            self._prev = None
        return self

    def uninstall(self) -> None:
        if self._prev is not None:
            try:
                signal.signal(signal.SIGTERM, self._prev)
            except ValueError:
                pass
            self._prev = None

    def _handle(self, signum, frame):
        self.fired.set()
        for p in self.procs:
            if p.poll() is None:
                p._torn_down = True   # our forward, not its own failure
                try:
                    p.send_signal(signal.SIGTERM)
                except OSError:
                    pass

    @staticmethod
    def save_in_flight() -> bool:
        return bool(glob.glob(_marker_prefix() + ".*"))

    def drain(self) -> None:
        """Wait for the gang's checkpoint-then-exit, then reap. Forwards
        SIGTERM (again) first: the signal may have fired between gangs —
        e.g. while the elastic agent was re-joining — in which case the
        CURRENT procs never saw the original forward."""
        self._handle(signal.SIGTERM, None)
        start = time.time()
        deadline = start + self.grace
        hard = start + self.grace * 10
        while any(p.poll() is None for p in self.procs):
            now = time.time()
            if self.save_in_flight():
                deadline = min(max(deadline, now + self.grace), hard)
            if now > deadline:
                break
            time.sleep(0.1)
        for p in self.procs:
            if p.poll() is None:
                p.kill()


def _watch(procs: List[subprocess.Popen],
           forwarder: Optional[_PreemptForwarder] = None
           ) -> Tuple[int, List[int], bool]:
    """Babysit the local gang: first non-zero exit kills everyone
    (failure-detection parity — a dead rank must not hang the ring).
    Returns (rc, failed_local_ranks, preempted): WHICH workers died on
    their OWN (not from our teardown) — --elastic_rescale retires
    exactly those workers' slots — and whether a forwarded SIGTERM
    (preemption) ended the gang instead."""
    from ..fleet.elastic import ELASTIC_EXIT_CODE
    if forwarder is not None:
        forwarder.procs = procs
    while True:
        if forwarder is not None and forwarder.fired.is_set():
            forwarder.drain()
            return 0, [], True
        alive = False
        failed: List[int] = []
        rc_out = 0
        for i, p in enumerate(procs):
            rc = p.poll()
            if rc is None:
                alive = True
            elif rc != 0:
                failed.append(i)
                # a real crash outranks a deliberate scale-event exit
                # (ELASTIC_EXIT_CODE): simultaneous mixed exits must
                # consume the restart budget, not bypass it
                if rc_out in (0, ELASTIC_EXIT_CODE):
                    rc_out = rc
        if failed:
            for q in procs:
                if q.poll() is None:
                    q._torn_down = True   # our teardown, not its failure
                    q.send_signal(signal.SIGTERM)
            time.sleep(2)
            for q in procs:
                if q.poll() is None:
                    q.kill()
            return rc_out, failed, False
        if not alive:
            return 0, [], False
        time.sleep(0.5)


def _spawn_layout(args, layout: dict, me: dict, generation: int,
                  attempt: int,
                  slots: Optional[List[int]] = None
                  ) -> List[subprocess.Popen]:
    """Spawn the local gang for one rendezvous layout: global ranks are
    the master-assigned offset + local rank, world is the layout's.
    ``generation`` bumps on every re-formation (not just failures) —
    the checkpoint-fencing / flight-dump stamp; ``attempt`` counts only
    budget-consuming FAILURES and is what workers see as
    ``PADDLE_ELASTIC_RESTART_COUNT`` (same semantics as the
    single-node loop — a deliberate rescale must not read as a
    failure)."""
    procs = []
    slots = list(range(args.nproc_per_node)) if slots is None else slots
    for lr, slot in enumerate(slots):
        # one shared env builder (_worker_env: devices, master, job id),
        # then override the rank/world vars with the MASTER-ASSIGNED
        # layout instead of the static nnodes*nproc derivation
        env = _worker_env(args, lr, generation)
        env["PADDLE_NODE_ID"] = _node_for_slot(slot)
        rank = me["rank_offset"] + lr
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(layout["world"]),
            "PADDLE_NNODES": str(layout["nnodes"]),
            "PADDLE_NODE_RANK": str(me["node_rank"]),
            "PADDLE_JOB_VERSION": str(layout["version"]),
            "PADDLE_ELASTIC_RESTART_COUNT": str(attempt),
            "PADDLE_PREEMPT_MARKER": f"{_marker_prefix()}.{rank}",
        })
        if args.master:
            env.update({
                "JAX_NUM_PROCESSES": str(layout["world"]),
                "JAX_PROCESS_ID": str(rank),
            })
        cmd = [sys.executable, args.training_script] \
            + args.training_script_args
        stdout = stderr = None
        log_path = None
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            log_path = os.path.join(args.log_dir, f"workerlog.{rank}")
            f = open(log_path, "ab")
            stdout = stderr = f
        p = subprocess.Popen(cmd, env=env, stdout=stdout, stderr=stderr)
        p.log_path = log_path
        procs.append(p)
    return procs


def _teardown(procs):
    for q in procs:
        if q.poll() is None:
            q._torn_down = True
            q.send_signal(signal.SIGTERM)
    deadline = time.time() + 3
    while time.time() < deadline and any(q.poll() is None for q in procs):
        time.sleep(0.1)
    for q in procs:
        if q.poll() is None:
            q.kill()


def _watch_with_master(procs, client, node_id: str, version: int,
                       beat: float,
                       forwarder: Optional[_PreemptForwarder] = None):
    """Babysit the local gang AND the job version: a version bump means
    the membership changed — tear down and respawn at the new layout."""
    from .master import UnknownPodError
    from ..fleet.elastic import ELASTIC_EXIT_CODE
    if forwarder is not None:
        forwarder.procs = procs
    last_beat = 0.0
    while True:
        if forwarder is not None and forwarder.fired.is_set():
            forwarder.drain()
            return "preempted", 0, 0
        alive = False
        failed = 0
        rc_out = 0
        for p in procs:
            rc = p.poll()
            if rc is None:
                alive = True
            elif rc != 0:
                failed += 1
                if rc_out in (0, ELASTIC_EXIT_CODE):
                    rc_out = rc
        if failed:
            _teardown(procs)
            return "failed", rc_out, failed
        if not alive:
            return "done", 0, 0
        if time.time() - last_beat >= beat:
            last_beat = time.time()
            try:
                r = client.beat(node_id)
                if int(r.get("version", version)) != version:
                    _teardown(procs)
                    return "rescale", 0, 0
            except UnknownPodError:
                _teardown(procs)          # master swept us: re-join
                return "rescale", 0, 0
            except ConnectionError:
                pass                      # master briefly unreachable
        time.sleep(min(0.2, beat / 4))


def _elastic_agent(args) -> int:
    """Multi-node elastic launcher: join the rendezvous job, spawn the
    local gang at the agreed layout, respawn on every membership change
    — scale-IN when the master sweeps a dead pod, scale-UP when a node
    (re)joins (reference ElasticManager + master watch loop)."""
    import socket
    from .master import MasterClient, RendezvousMaster
    master = None
    if args.rdzv_serve:
        port = int(str(args.rdzv_master).rsplit(":", 1)[1])
        master = RendezvousMaster(port, job=args.job_id,
                                  dead_after=args.rdzv_dead).start()
        print(f"[launch] rendezvous master serving on :{port}",
              file=sys.stderr)
    client = MasterClient(args.rdzv_master)
    node_id = f"node-{args.node_rank}"
    host = socket.gethostname()
    attempt = 0        # budget-consuming failures
    generation = 0     # bumps on EVERY re-formation (fencing stamp)
    t_detect = None    # set when a gang ends; cleared at the respawn
    forwarder = _PreemptForwarder(args.preempt_grace).install()
    beat_thread_stop = threading.Event()

    def _beat_during_settle():
        # keep the pod alive while (re)joining/settling
        while not beat_thread_stop.is_set():
            try:
                client.beat(node_id)
            except Exception:
                pass
            beat_thread_stop.wait(args.rdzv_beat)

    slots = list(range(args.nproc_per_node))
    try:
        while True:
            # quarantine fence before EVERY rendezvous join: a pod
            # whose slots were all convicted leaves the job for good
            # (the other pods rescale around the hole), a partially
            # convicted pod re-joins smaller
            live, excluded = _filter_quarantined_slots(slots)
            if excluded:
                _announce_quarantine(excluded, generation)
                if not live:
                    print("[launch] every local slot is quarantined — "
                          "leaving the rendezvous job", file=sys.stderr)
                    try:
                        client.leave(node_id)
                    except Exception:
                        pass
                    return QUARANTINED_EXIT_CODE
                slots = live
                args.nproc_per_node = len(slots)
            layout = client.join(node_id, host, args.nproc_per_node)
            # settle: let concurrent joins land, then read the final
            # layout all agents will agree on
            beat_thread_stop.clear()
            settler = threading.Thread(target=_beat_during_settle,
                                       daemon=True)
            settler.start()
            time.sleep(max(0.2, args.rdzv_beat))
            layout = client.layout()
            beat_thread_stop.set()
            me = next((nd for nd in layout["nodes"]
                       if nd["node_id"] == node_id), None)
            if me is None:
                continue                      # swept mid-settle: re-join
            version = int(layout["version"])
            print(f"[launch] job v{version}: world={layout['world']} "
                  f"nnodes={layout['nnodes']} node_rank="
                  f"{me['node_rank']}", file=sys.stderr)
            _elastic_event("rendezvous", version=version,
                           world=int(layout["world"]),
                           nnodes=int(layout["nnodes"]),
                           node_rank=int(me["node_rank"]),
                           generation=generation, restart=attempt)
            _prune_departed(int(layout["world"]), args.job_id)
            procs = _spawn_layout(args, layout, me, generation, attempt,
                                  slots)
            if t_detect is not None:
                # the re-formation this span budgets is now COMPLETE:
                # teardown + rendezvous + settle + prune + spawn
                _mttr_check(args, t_detect, generation)
                t_detect = None
            state, rc, _n = _watch_with_master(procs, client, node_id,
                                               version, args.rdzv_beat,
                                               forwarder)
            t_detect = time.time()
            generation += 1            # any outcome below re-forms
            if state in ("done", "preempted"):
                if state == "preempted":
                    print("[launch] preemption: gang checkpointed and "
                          "exited", file=sys.stderr)
                try:
                    client.leave(node_id)
                except Exception:
                    pass
                return 0
            if state == "rescale":
                print("[launch] membership changed — rescaling",
                      file=sys.stderr)
                _elastic_event("rescale", version=version,
                               generation=generation)
                continue
            # local failure
            _surface_failure_logs(procs)
            _surface_flight_dumps()
            from ..fleet.elastic import ELASTIC_EXIT_CODE
            if rc != ELASTIC_EXIT_CODE:
                attempt += 1
                if attempt > args.max_restarts:
                    print(f"[launch] gang failed (rc={rc}) after "
                          f"{attempt - 1} restarts; leaving job",
                          file=sys.stderr)
                    _elastic_event("give_up", rc=rc,
                                   restarts=attempt - 1,
                                   generation=generation)
                    try:
                        client.leave(node_id)
                    except Exception:
                        pass
                    return rc
            else:
                _elastic_event("scale_request", rc=rc,
                               generation=generation)
            # leave+rejoin bumps the version twice so OTHER nodes
            # rescale around our restart instead of hanging on dead
            # collectives
            try:
                client.leave(node_id)
            except Exception:
                pass
            print(f"[launch] worker failed (rc={rc}); elastic restart "
                  f"{attempt}/{args.max_restarts}", file=sys.stderr)
    finally:
        beat_thread_stop.set()
        forwarder.uninstall()
        if master is not None:
            master.shutdown()


def _reject_shared_chips(args) -> None:
    """On a TPU host one process drives every local chip (a mesh over
    ``jax.devices()``), and a chip belongs to one process at a time:
    several local workers would all claim all chips and fail or hang
    against each other. Refuse that layout instead of sharing
    silently."""
    from ...flags import _env_platform
    if args.nproc_per_node > 1 \
            and _env_platform(os.environ).startswith("tpu"):
        raise SystemExit(
            f"--nproc_per_node {args.nproc_per_node} on a TPU host: "
            "every local process would claim the same chips. Use "
            "--nproc_per_node 1 (one process, a mesh over the host's "
            "chips); JAX_PLATFORMS=cpu runs a multi-process CPU gang.")


def launch(argv: Optional[List[str]] = None) -> int:
    args = _parse(argv)
    _reject_shared_chips(args)
    if args.preflight and not _run_preflight():
        return QUARANTINED_EXIT_CODE
    if args.rdzv_master:
        return _elastic_agent(args)
    attempt = 0
    forwarder = _PreemptForwarder(args.preempt_grace).install()
    try:
        return _launch_loop(args, forwarder, attempt)
    finally:
        forwarder.uninstall()


def _launch_loop(args, forwarder: _PreemptForwarder, attempt: int) -> int:
    # `attempt` counts budget-consuming failures; `generation` bumps on
    # EVERY respawn (failures AND deliberate scale events) — it is the
    # checkpoint-fencing stamp, and a zombie from before a scale event
    # must be fenced just like one from before a crash
    generation = attempt
    t_detect = None
    # spawn slots: the stable per-position identities behind
    # PADDLE_NODE_ID; quarantine exclusion and failure scale-in both
    # shrink this list, never renumber it
    slots = list(range(args.nproc_per_node))
    while True:
        # quarantine fence, consulted on EVERY formation: a slot whose
        # node was convicted since the last spawn (fingerprint vote,
        # failed probe) is excluded before the gang re-forms
        live, excluded = _filter_quarantined_slots(slots)
        if excluded:
            _announce_quarantine(excluded, generation)
            if not live:
                print("[launch] every local slot is quarantined — "
                      "refusing to form a gang", file=sys.stderr)
                return QUARANTINED_EXIT_CODE
            if args.nnodes > 1:
                # static multi-node rank/world math cannot absorb a
                # one-node shrink (same constraint as the failure
                # rescale below); forming a gang that INCLUDES a
                # convicted chip would silently poison it instead —
                # refuse, and point at the elastic agent
                print("[launch] quarantined slot on a static "
                      "multi-node launch: cannot rescale without a "
                      "rendezvous master (--rdzv_master, --rdzv_serve "
                      "on node 0) — refusing to form a gang with a "
                      "convicted chip", file=sys.stderr)
                return QUARANTINED_EXIT_CODE
            print(f"[launch] quarantine scale-in: world "
                  f"{len(slots)} -> {len(live)}", file=sys.stderr)
            slots = live
            args.nproc_per_node = len(slots)
            _prune_departed(len(slots), args.job_id)
        procs = _spawn(args, generation, slots)
        _elastic_event("respawn", generation=generation,
                       world=args.nnodes * args.nproc_per_node,
                       restart=attempt)
        if t_detect is not None:
            # measured AFTER the respawn it budgets: the span covers
            # teardown, log surfacing, pruning, and the spawn itself
            _mttr_check(args, t_detect, generation)
            t_detect = None
        rc, failed_idx, preempted = _watch(procs, forwarder)
        t_detect = time.time()
        if preempted:
            print("[launch] preemption: gang checkpointed and exited",
                  file=sys.stderr)
            return 0
        if rc == 0:
            return 0
        _surface_failure_logs(procs)
        _surface_flight_dumps()
        # reference ELASTIC_EXIT_CODE (manager.py:33): a worker exiting
        # 101 announces a deliberate scale event — restart does not
        # consume the failure budget
        from ..fleet.elastic import ELASTIC_EXIT_CODE
        if rc != ELASTIC_EXIT_CODE:
            attempt += 1
            if attempt > args.max_restarts:
                print(f"[launch] gang failed (rc={rc}) after "
                      f"{attempt - 1} restarts; giving up",
                      file=sys.stderr)
                _elastic_event("give_up", rc=rc, restarts=attempt - 1,
                               generation=generation)
                return rc
        else:
            _elastic_event("scale_request", rc=rc,
                           generation=generation)
        generation += 1
        if args.elastic_rescale and args.nnodes > 1:
            print("[launch] --elastic_rescale without a rendezvous "
                  "master only rescales the local gang; for multi-node "
                  "membership run with --rdzv_master host:port "
                  "(--rdzv_serve on node 0) — restarting at full size",
                  file=sys.stderr)
        if args.elastic_rescale and args.nnodes == 1:
            new_world = max(1, args.nproc_per_node
                            - max(1, len(failed_idx)))
            if new_world != args.nproc_per_node:
                print(f"[launch] scale-in: world "
                      f"{args.nproc_per_node} -> {new_world}",
                      file=sys.stderr)
                _elastic_event("scale_in",
                               world_from=args.nproc_per_node,
                               world_to=new_world, rc=rc,
                               generation=generation)
                args.nproc_per_node = new_world
                # retire the FAILED workers' slots — the verdict (and
                # any later quarantine) follows the physical position,
                # so the marginal chip's slot must be the one dropped,
                # never a healthy tail slot
                keep = [s for i, s in enumerate(slots)
                        if i not in set(failed_idx)]
                slots = (keep + [s for s in slots
                                 if s not in keep])[:new_world]
                _prune_departed(new_world, args.job_id)
        os.environ["PADDLE_ELASTIC_RESTART_COUNT"] = str(attempt)
        print(f"[launch] worker failed (rc={rc}); elastic restart "
              f"{attempt}/{args.max_restarts} at world "
              f"{args.nnodes * args.nproc_per_node}", file=sys.stderr)


def _mttr_check(args, t_detect: float, generation: int) -> None:
    """Record how long the launcher took from failure detection to the
    COMPLETED respawn (callers invoke this right after the new gang is
    spawned — the span covers teardown, rendezvous, pruning, and the
    spawn), and warn when an --mttr_budget is blown. The
    worker-observed MTTR (kill -> first post-recovery step) is gated by
    ``bench.py --elastic``; this is the launcher's share of it."""
    detect_to_respawn = time.time() - t_detect
    _elastic_event("restart_latency",
                   detect_to_respawn_s=round(detect_to_respawn, 4),
                   budget_s=args.mttr_budget, generation=generation)
    if args.mttr_budget > 0 and detect_to_respawn > args.mttr_budget:
        print(f"[launch] MTTR budget blown: failure-to-respawn took "
              f"{detect_to_respawn:.2f}s against a budget of "
              f"{args.mttr_budget:.2f}s", file=sys.stderr)


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()
