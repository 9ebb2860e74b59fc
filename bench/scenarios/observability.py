"""Scenario: the ``--observability`` metrics/cost-model triage lane
(artifact ``OBSERVABILITY_r01.json``). The verdict is the result's
top-level ``ok`` key.
"""

import os

import numpy as np

from ..artifact import log
from . import registry


def build(scenario):
    """``--observability``: gates the always-on metrics plane + the
    deterministic cost model + the perf_doctor triage path, all without
    wall-clock A/B (unreliable on this shared host):

    * metrics overhead < 1% of step FLOPs by DETERMINISTIC record
      accounting: events recorded per step x a pessimistic per-event
      host-op cost (``metrics.EVENT_COST_OPS``) against the step's XLA
      cost_analysis FLOPs;
    * the clean path performs ZERO extra host syncs with the plane on
      (telemetry reads host-known values only — never the device);
    * every step record's four breakdown components (input-wait /
      compute / collective / host) sum to the recorded step total
      exactly (host is the residual by construction; the gate proves
      the plumbing doesn't double-count);
    * the cost model's FLOPs equal XLA ``cost_analysis`` of the same
      lowered program EXACTLY (three independent readers of one
      deterministic source);
    * ``perf_doctor diff`` names an injected slowdown — chaos
      ``stall_collective`` held inside a deadline-watched all_reduce —
      as the top regressed component, and exits nonzero (the CI gate).
    """
    import contextlib
    import io
    import json as _json
    import tempfile
    import paddle2_tpu as paddle
    import paddle2_tpu.nn as nn
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.distributed import collective as C
    from paddle2_tpu.distributed.fault_tolerance import chaos, numerics
    from paddle2_tpu.observability import cost_model, metrics
    from paddle2_tpu.tools import perf_doctor

    def build(seed=0):
        paddle.seed(seed)
        model = nn.Sequential(nn.Linear(128, 256), nn.ReLU(),
                              nn.Linear(256, 128))
        o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
        step = paddle.jit.train_step(
            lambda x, y: ((model(x) - y) ** 2).mean(), o,
            layers=[model])
        return model, o, step

    rs = np.random.RandomState(0)
    batches = [(paddle.to_tensor(rs.randn(256, 128).astype(np.float32)),
                paddle.to_tensor(rs.randn(256, 128).astype(np.float32)))
               for _ in range(8)]
    steps = 16
    chaos.disarm()
    metrics.disable()

    with tempfile.TemporaryDirectory() as td:
        # ---- overhead + sync + breakdown + cost-model legs ----------
        mdir = os.path.join(td, "metrics")
        pl = metrics.enable(mdir, rank=0)
        _, _, prog = build()
        prog.collect_cost = True
        s0 = numerics.host_sync_count()
        ev0 = pl.events_recorded
        for i in range(steps):
            prog(*batches[i % len(batches)])
        clean_syncs = (numerics.host_sync_count() - s0) / steps
        events_per_step = (pl.events_recorded - ev0) / steps
        step_flops = prog.last_cost_flops
        overhead_pct = (None if not step_flops else
                        events_per_step * metrics.EVENT_COST_OPS
                        / step_flops * 100.0)
        metrics.flush()
        recs = [_json.loads(ln) for ln in open(pl.stream_path)]
        srecs = [r for r in recs if r["type"] == "step"]
        sums_ok = bool(srecs) and all(
            abs(r["total_s"] - (r["input_wait_s"] + r["compute_s"]
                                + r["collective_s"] + r["host_s"]))
            <= 1e-9 for r in srecs)
        host_ok = all(r["host_s"] >= -1e-9 for r in srecs)
        # three independent readers of the SAME lowered program must
        # agree bit-for-bit: the program's own collect_cost pass, the
        # cost model's StepCost, and a direct cost_analysis here
        direct = cost_model.cost_analysis_of(
            prog.last_entry.lower(*prog.last_abstract_args)).get("flops")
        sc = cost_model.step_cost_of_program(prog)
        cost_exact = (direct is not None and sc is not None
                      and direct == sc.flops == step_flops)
        metrics.disable()

        # ---- perf_doctor diff leg: injected collective slowdown -----
        def run_stream(sub, spec):
            d = os.path.join(td, sub)
            metrics.enable(d, rank=0)
            _, _, sp = build()
            t = paddle.to_tensor(np.ones((1, 64), np.float32))
            try:
                if spec:
                    chaos.arm(spec)
                for i in range(12):
                    sp(*batches[i % len(batches)])
                    # deadline-watched: the stall blocks the caller
                    # inside the collective span (not just a waiter
                    # thread), exactly like a real slow ring
                    C.all_reduce(t, timeout=120.0)
            finally:
                chaos.disarm()
                metrics.flush()
                metrics.disable()
            return d

        # 2s one-shot stall ≈ +180ms/step mean over the counted steps —
        # far above this sandbox's load-spike noise floor, so the diff
        # verdict stays deterministic even though the stall is wall time
        base_dir = run_stream("a", None)
        slow_dir = run_stream("b", "stall_collective:6:2.0")
        rep_a = perf_doctor.summarize(perf_doctor.load_streams(base_dir))
        rep_b = perf_doctor.summarize(perf_doctor.load_streams(slow_dir))
        d = perf_doctor.diff(rep_a, rep_b, threshold_pct=10.0)
        with contextlib.redirect_stdout(io.StringIO()) as cli_out:
            cli_rc = perf_doctor.main(["diff", base_dir, slow_dir,
                                       "--threshold", "10"])
        diff_ok = (d["top_regressed"] == "collective" and d["regressed"]
                   and cli_rc == perf_doctor.REGRESSION_EXIT)
        log(cli_out.getvalue().strip())

    ok = (overhead_pct is not None and overhead_pct < 1.0
          and clean_syncs == 0.0 and sums_ok and host_ok
          and cost_exact and diff_ok)
    return {
        "metric": "observability",
        "value": round(overhead_pct, 5) if overhead_pct is not None
        else None,
        "unit": "% of step FLOPs charged by metric events "
                "(deterministic events-per-step x EVENT_COST_OPS, no "
                "wall clock)",
        "events_per_step": events_per_step,
        "step_flops": step_flops,
        "clean_host_syncs_per_step": clean_syncs,
        "breakdown_sums_exact": bool(sums_ok),
        "host_residual_nonnegative": bool(host_ok),
        "cost_model_flops_exact": bool(cost_exact),
        "perf_doctor_top_regressed": d["top_regressed"],
        "perf_doctor_cli_exit": cli_rc,
        "note": "GATES: overhead<1% by deterministic record "
                "accounting, 0 extra clean-path syncs, components sum "
                "to step total, cost-model==cost_analysis, and "
                "perf_doctor diff names an injected stall_collective "
                "as the regressed component with a nonzero exit",
        "ok": bool(ok),
    }


SCENARIO = registry.register(registry.Scenario(
    name="observability",
    artifact="OBSERVABILITY_r01.json",
    build=build,
    description="always-on metrics plane + deterministic cost model + "
                "perf_doctor triage: overhead/sync/breakdown/"
                "cost-exactness gates and an injected collective "
                "stall the diff must name",
    model={"net": "Linear(128,256)+ReLU+Linear(256,128)",
           "optimizer": "AdamW"},
    parallelism={},
    trace={"chaos": "stall_collective:6:2.0"},
    gates=("breakdown_sums_exact", "host_residual_nonnegative",
           "cost_model_flops_exact", "ok"),
    streams={},
))
