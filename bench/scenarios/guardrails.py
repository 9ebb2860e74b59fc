"""Scenario: the ``--guardrails`` numerical-guardrail smoke. Keeps no
artifact; the verdict is the top-level ``ok`` key of the stdout JSON
line.
"""

import time

import numpy as np

from . import registry


def build(scenario):
    """``--guardrails`` smoke: measures the clean-path cost of the full
    numerical-guardrail stack — GradScaler's fused non-finite sentinel
    (rank-consistent found_inf), FLAGS_check_loss_finite, and a
    ReliableStep wrapper — against a bare fp32 loop, chaos disarmed,
    interleaved A/B trials with medians (REPORT-ONLY, same rationale as
    --inject-fault). GATES on the host-sync invariant: the sentinel
    must read back exactly ONE scalar per step (the skip decision the
    reference AMP path already pays), independent of parameter count —
    never a per-parameter any()/bool() chain."""
    import paddle2_tpu as paddle
    import paddle2_tpu.nn as nn
    import paddle2_tpu.nn.functional as F
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.amp import GradScaler
    from paddle2_tpu.distributed.fault_tolerance import (ReliableStep,
                                                         chaos, numerics)

    def build(mode):
        """mode: 'bare' fp32 loop; 'sentinel' adds the loss sentinel
        consumers (ReliableStep deferred check + check_loss_finite) —
        the no-extra-sync claim under test; 'amp' adds GradScaler's
        fused grad sentinel on top (whose ONE readback per step is the
        skip decision AMP inherently pays)."""
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                              nn.Linear(128, 64))
        o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
        if mode == "amp":
            scaler = GradScaler(init_loss_scaling=2.0 ** 10)

            def inner(x, y):
                loss = F.mse_loss(model(x), y)
                scaler.scale(loss).backward()
                scaler.step(o)
                scaler.update()
                o.clear_grad()
                return loss
        else:
            def inner(x, y):
                loss = F.mse_loss(model(x), y)
                loss.backward()
                o.step()
                o.clear_grad()
                return loss
        if mode == "bare":
            return inner, None
        reliable = ReliableStep(model, o, snapshot_every=20)

        def step(x, y):
            return reliable.run(inner, x, y)
        return step, reliable

    rs_data = np.random.RandomState(0)
    batches = [(paddle.to_tensor(rs_data.randn(32, 64).astype(np.float32)),
                paddle.to_tensor(rs_data.randn(32, 64).astype(np.float32)))
               for _ in range(8)]
    steps, warm, trials = 30, 10, 5

    def timed_loop(run_one):
        t0 = time.perf_counter()
        for i in range(steps):
            run_one(*batches[i % len(batches)])
        return (time.perf_counter() - t0) / steps

    chaos.disarm()
    paddle.set_flags({"FLAGS_check_loss_finite": True})
    bare_step, _ = build("bare")
    sent_step, sent_rel = build("sentinel")
    amp_step, amp_rel = build("amp")
    for i in range(warm):
        bare_step(*batches[i % len(batches)])
        sent_step(*batches[i % len(batches)])
        amp_step(*batches[i % len(batches)])

    def syncs_over(run_one):
        s0 = numerics.host_sync_count()
        for i in range(steps):
            run_one(*batches[i % len(batches)])
        return (numerics.host_sync_count() - s0) / steps

    # host-sync invariants: the loss sentinel adds ZERO readbacks (the
    # loss was already on host); the grad sentinel adds exactly ONE per
    # step (the skip decision), regardless of parameter count
    sent_syncs = syncs_over(sent_step)
    amp_syncs = syncs_over(amp_step)
    bare_t, sent_t, amp_t = [], [], []
    for _ in range(trials):
        bare_t.append(timed_loop(bare_step))
        sent_t.append(timed_loop(sent_step))
        amp_t.append(timed_loop(amp_step))
    sent_rel.finalize()
    amp_rel.finalize()
    paddle.set_flags({"FLAGS_check_loss_finite": False})
    bare = float(np.median(bare_t))
    sent = float(np.median(sent_t))
    amp = float(np.median(amp_t))
    sentinel_overhead_pct = (sent - bare) / bare * 100.0
    ok = (sent_syncs == 0.0 and amp_syncs <= 1.0
          and sent_rel.stats["retries"] == 0
          and amp_rel.stats["retries"] == 0)

    return {
        "metric": "guardrails_smoke",
        "value": round(sentinel_overhead_pct, 2),
        "unit": "% clean-path overhead of the loss sentinel",
        "bare_step_ms": round(bare * 1e3, 3),
        "sentinel_step_ms": round(sent * 1e3, 3),
        "amp_guarded_step_ms": round(amp * 1e3, 3),
        "sentinel_host_syncs_per_step": round(sent_syncs, 3),
        "amp_host_syncs_per_step": round(amp_syncs, 3),
        "spurious_retries": sent_rel.stats["retries"]
        + amp_rel.stats["retries"],
        "stack": "ReliableStep deferred check + check_loss_finite "
                 "(sentinel) | + GradScaler fused rank-consistent "
                 "found_inf (amp)",
        "note": "REPORT-ONLY timing (shared-host noise); GATES on zero "
                "extra loss-sentinel syncs, <=1 amp sync per step, and "
                "zero spurious retries",
        "ok": bool(ok),
    }


SCENARIO = registry.register(registry.Scenario(
    name="guardrails",
    artifact="",
    build=build,
    description="clean-path cost of the loss + grad sentinels "
                "(report-only); gates the host-sync invariant",
    model={"net": "Linear(64,128)-ReLU-Linear(128,64)",
           "optimizer": "AdamW"},
    parallelism={},
    trace={"steps": 30, "trials": 5},
    gates=("ok",),
    streams={},
    deterministic=False,
))
