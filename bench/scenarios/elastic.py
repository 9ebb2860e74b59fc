"""Scenario: the ``--elastic`` node-loss MTTR lane (artifact
``ELASTIC_r01.json``). The verdict is the result's top-level ``ok``
key; the MTTR in it is a host-clock reading.
"""

import json
import os
import sys

from . import registry

# the spawned trainer needs the REPO root on PYTHONPATH, three levels
# up from bench/scenarios/elastic.py
_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

def build(scenario):
    """``--elastic`` MTTR gate: spawn a 2-rank launcher gang on CPU,
    SIGKILL rank 1 mid-run (node-loss injection — the dying rank stamps
    the kill wall-clock first), and measure **MTTR = injected kill ->
    first post-recovery optimizer step** on the respawned smaller gang.
    GATES on three things at once: the gang recovers at world 1, the
    respawned worker restores from the buddy's in-memory replica with
    ZERO checkpoint-directory reads (the disk chain is instrumented),
    and MTTR lands under the budget (env BENCH_MTTR_BUDGET_S, default
    60 s — dominated by interpreter+jax import on CPU CI; on a pod the
    same path is seconds). Prints one JSON line like the other
    benches."""
    import subprocess
    import tempfile

    budget_s = float(os.environ.get("BENCH_MTTR_BUDGET_S", "60"))
    repo = _REPO
    with tempfile.TemporaryDirectory() as td:
        replica = os.path.join(td, "shm")
        flight = os.path.join(td, "flight")
        ckpt = os.path.join(td, "ckpt")
        out = os.path.join(td, "result.json")
        t_kill_file = os.path.join(td, "t_kill")
        t_rec_file = os.path.join(td, "t_recover")
        script = os.path.join(td, "train.py")
        with open(script, "w") as f:
            f.write(f"""
import json, os, signal, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle2_tpu as paddle
import paddle2_tpu.nn as nn
import paddle2_tpu.optimizer as opt
from paddle2_tpu.distributed import fault_tolerance as ft

rank = int(os.environ.get("PADDLE_TRAINER_ID", 0))
world = int(os.environ.get("PADDLE_TRAINERS_NUM", 1))
restart = int(os.environ.get("PADDLE_ELASTIC_RESTART_COUNT", 0))

paddle.seed(0)
m = nn.Linear(4, 1)
o = opt.SGD(learning_rate=0.05, parameters=m.parameters())
rep = ft.BuddyReplicator(store_dir={replica!r})
mgr = ft.CheckpointManager({ckpt!r})
disk_reads = []
_real = mgr.restore
mgr.restore = lambda s: (disk_reads.append(1) or _real(s))

state = {{"w": m.weight, "b": m.bias, "step": 0}}
start, source = ft.elastic_restore(state, rep, mgr)
start = 0 if start is None else start + 1

rs = np.random.RandomState(0)
W = np.array([[1.0], [2.0], [-1.0], [0.5]], np.float32)
loss_fn = nn.MSELoss()
losses = []
for step in range(start, 12):
    if world > 1:
        time.sleep(0.25)
    if rank == 1 and restart == 0 and step == 4:
        with open({t_kill_file!r}, "w") as f:
            f.write(repr(time.time()))
        os.kill(os.getpid(), signal.SIGKILL)   # injected node loss
    x = paddle.to_tensor(rs.randn(16, 4).astype(np.float32))
    y = paddle.to_tensor(np.asarray(x._data) @ W)
    loss = loss_fn(m(x), y)
    loss.backward()
    o.step()
    o.clear_grad()
    losses.append(float(np.asarray(loss._data)))
    if restart > 0 and not losses[1:]:
        with open({t_rec_file!r}, "w") as f:       # first recovered step
            f.write(repr(time.time()))
    state["step"] = step
    rep.put(state, step)
if rank == 0:
    json.dump({{"world": world, "restart": restart, "source": source,
               "start": start, "disk_reads": len(disk_reads),
               "losses": losses}}, open({out!r}, "w"))
""")
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "PADDLE_", "FLAGS_"))}
        env["PYTHONPATH"] = repo
        env["JAX_PLATFORMS"] = "cpu"
        env["PADDLE_REPLICA_DIR"] = replica
        env["PADDLE_FLIGHT_DIR"] = flight
        proc = subprocess.run(
            [sys.executable, "-m", "paddle2_tpu.distributed.launch",
             "--nproc_per_node", "2", "--max_restarts", "2",
             "--elastic_rescale", "--mttr_budget", str(budget_s),
             script],
            env=env, capture_output=True, text=True, timeout=600)
        launch_ok = proc.returncode == 0
        res = {}
        mttr = float("inf")
        try:
            res = json.load(open(out))
            mttr = (float(open(t_rec_file).read())
                    - float(open(t_kill_file).read()))
        except (OSError, ValueError):
            launch_ok = False
        detect_to_respawn = None
        try:
            for ln in open(os.path.join(flight,
                                        "elastic_events.jsonl")):
                ev = json.loads(ln)
                if ev.get("kind") == "elastic.restart_latency":
                    detect_to_respawn = ev.get("detect_to_respawn_s")
        except OSError:
            pass

    recovered_smaller = res.get("world") == 1 and res.get("restart", 0) >= 1
    ram_only = res.get("source") == "replica" and res.get("disk_reads") == 0
    ok = bool(launch_ok and recovered_smaller and ram_only
              and mttr <= budget_s)
    if not launch_ok:
        sys.stderr.write(proc.stderr[-2000:] + "\n")
    return {
        "metric": "elastic_mttr",
        "value": round(mttr, 3) if mttr != float("inf") else None,
        "unit": "s from injected SIGKILL to first post-recovery step "
                "(gated)",
        "budget_s": budget_s,
        "recovered_world": res.get("world"),
        "restore_source": res.get("source"),
        "ckpt_dir_reads": res.get("disk_reads"),
        "launcher_detect_to_respawn_s": detect_to_respawn,
        "resumed_at_step": res.get("start"),
        "stack": "2-rank launcher gang, --elastic_rescale; buddy "
                 "replica over shm; SIGKILL rank 1 at step 4; "
                 "CheckpointManager disk chain instrumented (must "
                 "stay cold)",
        "ok": ok,
    }


SCENARIO = registry.register(registry.Scenario(
    name="elastic",
    artifact="ELASTIC_r01.json",
    build=build,
    description="elastic node-loss MTTR: SIGKILL a rank mid-gang, "
                "buddy-replica restore with a cold checkpoint chain",
    model={"net": "Linear(4,1)", "optimizer": "SGD"},
    parallelism={"ranks": 2, "max_restarts": 2},
    trace={"kill": "SIGKILL rank 1 at step 4"},
    gates=("ok",),
    streams={},
    deterministic=False,
))
