"""Scenario: the ``--reliable-step`` instrumented-train-step lane
(artifact ``RELIABLE_STEP_r01.json``). The verdict is the result's
top-level ``ok`` key; the warm-cache drill records real compile
seconds, so two runs' artifacts differ by design.
"""

import json
import os
import sys

import numpy as np

from . import registry

# the repo root: the warm-cache restart subprocess imports paddle2_tpu
_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def build(scenario):
    """Gates the INSTRUMENTED compiled train step
    (jit.train_step(..., reliability=...)) on deterministic invariants —
    no wall-clock A/B (unreliable on this shared host):

    * in-program sentinel+fingerprint overhead < 2% of step FLOPs,
      measured as ops-added x count via XLA cost_analysis of the
      lowered executables (instrumented vs plain program of the SAME
      train_fn);
    * the clean path performs ZERO extra host syncs (the sentinel is
      folded into the loss; the packed aux is never read), and the SDC
      mode exactly ONE packed readback per step;
    * instrumentation changes NOTHING: clean-path losses and final
      params are bitwise identical to the plain program;
    * recovery: an injected NaN step rewinds+replays to the bitwise
      clean-run state;
    * warm-cache restart: two worker incarnations sharing a persistent
      compilation cache record ``elastic.compile_cache`` events, the
      second with ``hit: true`` and a cheaper compile+first-step (the
      MTTR accounting the elastic restart path reads).
    """
    import json as _json
    import subprocess
    import tempfile
    import paddle2_tpu as paddle
    import paddle2_tpu.nn as nn
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.distributed.fault_tolerance import (
        ReliabilityConfig, SDCGuard, chaos, numerics)

    def build(reliability, seed=0):
        paddle.seed(seed)
        model = nn.Sequential(nn.Linear(128, 256), nn.ReLU(),
                              nn.Linear(256, 128))
        o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
        step = paddle.jit.train_step(
            lambda x, y: ((model(x) - y) ** 2).mean(), o,
            layers=[model], reliability=reliability)
        return model, o, step

    # batch chosen for a REALISTIC compute/param ratio: the sentinel +
    # fingerprint are O(params) while the step is O(params x batch), so
    # a toy batch would overstate the overhead a real workload never
    # sees (GPT batches are thousands of tokens per step)
    rs = np.random.RandomState(0)
    batches = [(paddle.to_tensor(rs.randn(256, 128).astype(np.float32)),
                paddle.to_tensor(rs.randn(256, 128).astype(np.float32)))
               for _ in range(8)]
    steps = 16
    chaos.disarm()

    # -- deterministic overhead accounting (flops, not wall clock) ----
    _, _, plain = build(None)
    plain.collect_cost = True
    plain(*batches[0])
    m_ref, _, inst = build(True, seed=0)
    inst.program.collect_cost = True
    for i in range(steps):
        inst(*batches[i % len(batches)])
    inst.finalize()
    plain_flops = plain.last_cost_flops
    inst_flops = inst.program.last_cost_flops
    overhead_pct = (None if not plain_flops or not inst_flops
                    else (inst_flops - plain_flops) / plain_flops * 100.0)

    # -- host-sync + bitwise-transparency invariants ------------------
    m_plain, _, plain2 = build(None)
    plain_losses = [float(plain2(*batches[i % len(batches)]))
                    for i in range(steps)]
    m_inst, _, inst2 = build(True)
    s0 = numerics.host_sync_count()
    inst_losses = [float(inst2(*batches[i % len(batches)]))
                   for i in range(steps)]
    inst2.finalize()
    clean_syncs = (numerics.host_sync_count() - s0) / steps
    bitwise_clean = (plain_losses == inst_losses and np.array_equal(
        np.asarray(m_plain.state_dict()["0.weight"]._data),
        np.asarray(m_inst.state_dict()["0.weight"]._data)))

    with tempfile.TemporaryDirectory() as sdc_dir:
        guard = SDCGuard(optimizer=None, store_dir=sdc_dir, rank=0,
                         world=1, evict=False)
        _, _, sdc_step = build(ReliabilityConfig(sdc=guard))
        s0 = numerics.host_sync_count()
        for i in range(steps):
            sdc_step(*batches[i % len(batches)])
        sdc_step.finalize()
        sdc_syncs = (numerics.host_sync_count() - s0) / steps

    # -- recovery: injected NaN -> rewind+replay to the clean state ---
    ref_w = np.asarray(m_inst.state_dict()["0.weight"]._data)
    chaos.arm("poison_loss:5")
    m_rec, _, rec = build(True)
    for i in range(steps):
        rec(*batches[i % len(batches)])
    rec.finalize()
    chaos.disarm()
    recovered_bitwise = np.array_equal(
        np.asarray(m_rec.state_dict()["0.weight"]._data), ref_w)

    # -- warm-cache restart: compile time is MTTR ---------------------
    script = (
        "import os, numpy as np\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import paddle2_tpu as paddle\n"
        "import paddle2_tpu.optimizer as opt\n"
        "from paddle2_tpu import nn\n"
        "paddle.seed(0)\n"
        "m = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),"
        " nn.Linear(128, 64))\n"
        "o = opt.AdamW(learning_rate=1e-3,"
        " parameters=m.parameters())\n"
        "step = paddle.jit.train_step("
        "lambda x, y: ((m(x) - y) ** 2).mean(), o, layers=[m],"
        " reliability=True)\n"
        "rs = np.random.RandomState(0)\n"
        "x = paddle.to_tensor(rs.randn(32, 64).astype(np.float32))\n"
        "y = paddle.to_tensor(rs.randn(32, 64).astype(np.float32))\n"
        "step(x, y); step.finalize()\n")
    with tempfile.TemporaryDirectory() as td:
        wpath = os.path.join(td, "w.py")
        with open(wpath, "w") as f:
            f.write(script)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "PADDLE_", "FLAGS_"))}
        env.update({
            "PYTHONPATH": _REPO_ROOT,
            "JAX_PLATFORMS": "cpu",
            "PADDLE2_TPU_CACHE_DIR": os.path.join(td, "cache"),
            "PADDLE2_TPU_CACHE_MIN_COMPILE_S": "0",
            "PADDLE_FLIGHT_DIR": os.path.join(td, "flight"),
        })
        for gen in ("0", "1"):
            env["PADDLE_RESTART_GENERATION"] = gen
            subprocess.run([sys.executable, wpath], env=env, check=True,
                           capture_output=True, timeout=240)
        events = [_json.loads(ln) for ln in
                  open(os.path.join(td, "flight", "elastic_events.jsonl"))]
        cc = [e for e in events if e["kind"] == "elastic.compile_cache"]
    warm = (len(cc) >= 2 and cc[0]["hit"] is False
            and cc[-1]["hit"] is True
            and cc[-1]["compile_s"] < cc[0]["compile_s"])

    ok = (overhead_pct is not None and overhead_pct < 2.0
          and clean_syncs == 0.0 and sdc_syncs <= 1.0
          and bitwise_clean and recovered_bitwise and warm
          and rec.stats["retries"] == 1)
    return {
        "metric": "reliable_step",
        "value": round(overhead_pct, 4) if overhead_pct is not None
        else None,
        "unit": "% step FLOPs added by in-program sentinel+fingerprint "
                "(XLA cost_analysis, deterministic)",
        "plain_flops": plain_flops,
        "instrumented_flops": inst_flops,
        "clean_host_syncs_per_step": clean_syncs,
        "sdc_host_syncs_per_step": round(sdc_syncs, 3),
        "clean_path_bitwise_transparent": bool(bitwise_clean),
        "nan_recovery_bitwise": bool(recovered_bitwise),
        "recovery_retries": rec.stats["retries"],
        "compile_cache": [{"gen": e.get("generation"),
                           "hit": e.get("hit"),
                           "compile_s": e.get("compile_s")}
                          for e in cc],
        "note": "GATES: overhead<2% via deterministic op accounting, "
                "0 extra clean-path syncs, <=1 packed sync with SDC, "
                "bitwise transparency + bitwise NaN recovery, and a "
                "warm-cache restart recording compile_cache_hit",
        "ok": bool(ok),
    }


SCENARIO = registry.register(registry.Scenario(
    name="reliable-step",
    artifact="RELIABLE_STEP_r01.json",
    build=build,
    description="instrumented compiled train step: sentinel+"
                "fingerprint FLOP overhead, host-sync counts, bitwise "
                "transparency, NaN rewind+replay, warm-cache restart",
    model={"net": "Linear(128,256)+ReLU+Linear(256,128)",
           "optimizer": "AdamW"},
    parallelism={"replicas": 1},
    trace={"chaos": "poison_loss:5", "steps": 16},
    gates=("clean_path_bitwise_transparent", "nan_recovery_bitwise",
           "ok"),
    streams={},
    deterministic=False,
))
