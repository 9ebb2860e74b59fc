"""Scenario: the ``--tracing`` request-lifecycle attribution lane
(artifact ``TRACING_r01.json``); its two stream scratch dirs come
through ``scenario.streams``.
"""

import os

import numpy as np

from ..artifact import bench_scratch, log
from . import registry


def build(scenario):
    """``--tracing``: request-lifecycle tracing + exact tail-latency
    attribution (ISSUE 13) — all deterministic (virtual clock x seeded
    traces x integer-picosecond decomposition; run twice, the
    TRACING_r01.json artifact is byte-identical).

    Gates:
      1. **Transparency** — the PR 11 kill drill produces a
         token-for-token identical stream with tracing ON vs OFF
         (tracing is pure recording, it must never perturb the DES).
      2. **Exact decomposition** — every finished request of all four
         PR 11 chaos drills (kill / transient / overload / hot-swap)
         decomposes into queue_wait + prefill + decode_compute +
         eviction_stall + failover_stall + swap_stall + host summing
         EXACTLY (integer-ps, bitwise-stable) to its e2e latency.
      3. **Fault attribution** — serve_doctor names the injected
         overload as the ``queue-wait`` owner of the p99-p50 gap, and
         a drop_decode_step chaos diff names ``decode-compute`` as the
         top regressed component with the dropped steps attributed to
         specific trace ids.
      4. **Overhead** — trace events x EVENT_COST_OPS < 1% of the
         drills' executed modeled FLOPs (deterministic accounting, no
         wall-clock A/B). The disabled path is one attribute load
         (gated by tests/test_tracing.py).
      5. **SLO plane** — the overload drill's SLOConfig ledger closes
         (good == completed, bad == shed), the burn-rate gauge rides
         the metrics snapshot, and perf_doctor reconstructs TTFT
         p50/p99 from the histogram bucket counts.
    """
    import io
    import shutil
    import zlib
    from contextlib import redirect_stdout

    import paddle2_tpu as paddle
    from paddle2_tpu.distributed.fault_tolerance import chaos
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    from paddle2_tpu.observability import metrics, tracing
    from paddle2_tpu.serving import (
        EngineConfig, EngineFailoverRouter, HotSwapController,
        ReliabilityConfig, SLOConfig, ServingEngine, poisson_trace,
        simulate_router, simulate_serving)
    from paddle2_tpu.serving.simulate import cost_seconds
    from paddle2_tpu.tools import perf_doctor, serve_doctor

    trace_root = bench_scratch("tracing",
                               env_var=scenario.streams["traces"])
    metrics_dir = bench_scratch("tracing_metrics",
                                env_var=scenario.streams["metrics"])
    for d in (trace_root, metrics_dir):
        shutil.rmtree(d, ignore_errors=True)   # streams append

    paddle.seed(0)
    cfg = gpt_tiny(use_scan=False, max_position_embeddings=128)
    model = GPTForCausalLM(cfg)
    prompt_lens, gen_tokens = [16, 24], [12, 24]
    mean_gen = float(np.mean(gen_tokens))

    def make_engine(reliability=None):
        return ServingEngine(model, config=EngineConfig(
            block_size=16, num_blocks=40, max_batch=8,
            prefill_budget_tokens=64, max_model_len=128,
            reliability=reliability))

    def make_trace(n, seed, rate, priorities=False, gen=None):
        t = poisson_trace(n, rate_per_s=rate, prompt_lens=prompt_lens,
                          gen_tokens=gen or gen_tokens,
                          vocab=cfg.vocab_size, seed=seed)
        if priorities:
            for i, r in enumerate(t):
                r["priority"] = 1 if i % 3 == 0 else 0
        return t

    def crc(router, rep):
        payload = b"".join(
            np.asarray(router.sequence(r).generated, np.int64).tobytes()
            for r in rep.rids)
        return zlib.crc32(payload) & 0xFFFFFFFF

    # -- phase 0: probe the cost model (compiles prefill + b1 decode)
    probe = make_engine()
    simulate_serving(probe, make_trace(2, seed=1, rate=100.0))
    b1_key = min(probe.runner._decode_costs)
    decode_s = cost_seconds(probe.runner.decode_cost(b1_key))
    prefill_s = max(cost_seconds(c)
                    for c in probe.runner._prefill_costs.values())
    base_capacity = 1.0 / decode_s
    probe_interval_s = 2.0 * decode_s
    log(f"tracing probe: decode_s={decode_s*1e6:.1f}us "
        f"prefill_s={prefill_s*1e6:.1f}us")

    drill_stats = {}   # name -> {events, flops, completed, exact, ...}

    def run_drill(name, n_engines, rel=None, arm=None, n=16, seed=101,
                  rate=None, priorities=False, gen=None, on_round=None,
                  traced=True):
        rate = rate if rate is not None else 2.0 * base_capacity / mean_gen
        tdir = os.path.join(trace_root, name)
        if traced:
            shutil.rmtree(tdir, ignore_errors=True)
            tracing.enable(tdir, rank=0)
        if arm:
            chaos.arm(arm)
        router = EngineFailoverRouter(
            [make_engine(rel) for _ in range(n_engines)],
            probe_interval_s=probe_interval_s)
        rep = simulate_router(
            router, [dict(r) for r in
                     make_trace(n, seed, rate, priorities, gen)],
            on_round=on_round)
        chaos.disarm()
        events = 0
        if traced:
            events = tracing.active().events_recorded
            tracing.flush()
            tracing.disable()
        return router, rep, tdir, events

    gates = {}
    total_events = 0
    total_flops = 0.0
    exact_by_drill = {}

    def audit(name, tdir, rep, events):
        """Decompose one drill's traces; returns (gate_ok, decomps)."""
        nonlocal total_events, total_flops
        dec = tracing.decompose(tracing.load_trace_dir(tdir))
        fin = {t: c for t, c in dec.items() if c["finished"]}
        exact_by_drill[name] = {
            "finished": len(fin),
            "completed": rep.completed,
            "exact": sum(1 for c in fin.values() if c["exact"]),
            "events": events,
        }
        total_events += events
        total_flops += rep.modeled_flops
        ok = (len(fin) == rep.completed
              and all(c["exact"] for c in fin.values()))
        return ok, dec

    # -- drill 1: engine kill -> failover (traced vs untraced twin)
    r_off, rep_off, _, _ = run_drill("kill_off", 2,
                                     arm="kill_engine:4:1",
                                     traced=False)
    r_kill, rep_kill, d_kill, ev_kill = run_drill(
        "kill", 2, arm="kill_engine:4:1")
    kill_crc = crc(r_kill, rep_kill)
    gates["tracing_transparent_token_for_token"] = (
        kill_crc == crc(r_off, rep_off)
        and rep_kill.completed == rep_off.completed)
    gates["decomposition_exact_kill"], _ = audit("kill", d_kill,
                                                 rep_kill, ev_kill)

    # -- drill 2: transient faults (drop + corrupt), single engine
    _, rep_tr, d_tr, ev_tr = run_drill(
        "transient", 1, arm="drop_decode_step:3,corrupt_block_table:5:1")
    gates["decomposition_exact_transient"], _ = audit(
        "transient", d_tr, rep_tr, ev_tr)

    # -- drill 3: overload burst + SLO plane (+ metrics join)
    metrics.enable(metrics_dir, rank=0, flush_steps=1)
    ttft_bound = 10.0 * (prefill_s + decode_s)
    slo = SLOConfig(ttft_target_s=ttft_bound,
                    availability_target=0.99)
    # uniform generation length: every request costs the same decode
    # work, so the ONLY source of tail spread is the injected overload
    # itself — what queue_wait should (and must) be blamed for
    r_over, rep_over, d_over, ev_over = run_drill(
        "overload", 1,
        rel=ReliabilityConfig(max_queue_depth=6, slo=slo),
        n=40, seed=202, rate=20.0 * base_capacity / 16.0,
        priorities=True, gen=[16])
    metrics.flush()
    metrics.export_prometheus()
    metrics.disable()
    gates["decomposition_exact_overload"], _ = audit(
        "overload", d_over, rep_over, ev_over)
    over_report = serve_doctor.summarize(
        serve_doctor._load(d_over), metrics_dir=metrics_dir)
    tail = over_report["tail"]
    gates["overload_tail_owned_by_queue_wait"] = (
        tail["owner"] == "queue_wait_s" and tail["owner_gap_s"] > 0)
    eng_over = r_over.engines[0]
    slo_led = over_report["slo"]
    gates["slo_ledger_closes"] = (
        slo_led["good"] == rep_over.completed
        and slo_led["bad"] == rep_over.shed
        and slo_led["bad"] > 0
        and slo_led["burn_rate"] is not None
        and eng_over.scheduler.slo_good + eng_over.scheduler.slo_bad
        == rep_over.completed + rep_over.shed)
    # histogram satellite: perf_doctor reconstructs TTFT percentiles
    # from the cumulative bucket counts the snapshot now carries
    pd_report = perf_doctor.summarize(
        perf_doctor.load_streams(metrics_dir), warmup=0)
    hist = pd_report.get("histograms") or {}
    ttft_lane = next((v for k, v in hist.items()
                      if k.startswith("serving_ttft_s")), None)
    gates["perf_doctor_histogram_ttft_lane"] = (
        ttft_lane is not None and ttft_lane["count"] > 0
        and ttft_lane["p99"] is not None and ttft_lane["p99"] > 0)
    slo_counters_seen = pd_report.get("counters") or {}
    gates["perf_doctor_slo_counters"] = (
        slo_counters_seen.get("serving_slo_good_total", 0) > 0
        and slo_counters_seen.get("serving_slo_bad_total", 0) > 0)

    # -- drill 4: staged hot-swap rollout + rollback mid-traffic
    swap_state = {}

    def on_round(rt, clock, idx):
        ctl = swap_state.get("ctl")
        if ctl is None:
            new_w = [w * 1.001 if "float" in str(getattr(w, "dtype", ""))
                     else w for w in rt.engines[0].runner._weights()]
            ctl = swap_state["ctl"] = HotSwapController(
                rt.engines, new_w)
        if idx in (6, 9):
            ctl.stage_next(now=clock)
        elif idx == 14 and ctl.state == "committed":
            ctl.rollback(now=clock)

    _, rep_swap, d_swap, ev_swap = run_drill(
        "swap", 2, n=16, seed=303, on_round=on_round)
    gates["decomposition_exact_swap"], swap_dec = audit(
        "swap", d_swap, rep_swap, ev_swap)
    gates["swap_spans_cover_requests"] = any(
        c["swaps"] > 0 for c in swap_dec.values())

    # -- drill 5: drop-chaos diff pair (BASE clean vs CAND dropped)
    _, rep_db, d_drop_base, ev_db = run_drill(
        "drop_base", 1, n=8, seed=404)

    def rearm(rt, clock, idx):
        if idx in (4, 6, 8, 10):
            chaos.arm("drop_decode_step:1")

    _, rep_dc, d_drop_cand, ev_dc = run_drill(
        "drop", 1, n=8, seed=404, on_round=rearm)
    base_rep = serve_doctor.summarize(serve_doctor._load(d_drop_base))
    cand_rep = serve_doctor.summarize(serve_doctor._load(d_drop_cand))
    drop_diff = serve_doctor.diff(base_rep, cand_rep)
    drop_tids = (cand_rep.get("chaos") or {}).get("drop_decode_step",
                                                  [])
    gates["drop_diff_names_decode_compute"] = (
        drop_diff["top_regressed"] == "decode-compute"
        and drop_diff["components"]["decode-compute"]["delta_s"] > 0)
    gates["drop_chaos_attributed_to_tids"] = (
        len(drop_tids) > 0
        and drop_diff["counter_deltas"].get("retries", {}).get("new", 0)
        > 0)

    # -- overhead: deterministic event-cost accounting vs step FLOPs
    overhead_pct = (100.0 * total_events * metrics.EVENT_COST_OPS
                    / max(total_flops, 1.0))
    gates["tracing_overhead_under_1pct_of_flops"] = overhead_pct < 1.0

    # -- serve_doctor CLI round-trips (quiet: bench stdout is one line)
    sink = io.StringIO()
    with redirect_stdout(sink):
        rc_summary = serve_doctor.main(
            [d_over, "--metrics-dir", metrics_dir])
        rc_diff_same = serve_doctor.main(["diff", d_kill, d_kill])
    gates["serve_doctor_cli_exit_codes"] = (
        rc_summary == 0 and rc_diff_same == 0)

    log(f"tracing: events={total_events} flops={total_flops:.3e} "
        f"overhead={overhead_pct:.4f}% tail_owner="
        f"{tail['owner_label']} drop_top="
        f"{drop_diff['top_regressed']} slo good/bad="
        f"{slo_led['good']:g}/{slo_led['bad']:g} "
        f"burn={slo_led['burn_rate']:.2f}x")

    result = {
        "metric": "request_tracing",
        "value": round(overhead_pct, 6),
        "unit": "overhead_pct_of_step_flops",
        "drills": exact_by_drill,
        "kill_tokens_crc": kill_crc,
        "tail": {
            "owner": tail["owner_label"],
            "gap_us": round(tail["gap_s"] * 1e6, 3),
            "owner_gap_us": round(tail["owner_gap_s"] * 1e6, 3),
        },
        "drop_diff": {
            "top_regressed": drop_diff["top_regressed"],
            "decode_delta_us": round(
                drop_diff["components"]["decode-compute"]["delta_s"]
                * 1e6, 3),
            "retries": drop_diff["counter_deltas"].get(
                "retries", {}).get("new", 0),
            "chaos_tids": drop_tids,
        },
        "slo": {
            "good": slo_led["good"], "bad": slo_led["bad"],
            "attainment": round(slo_led["attainment"], 4),
            "burn_rate": round(slo_led["burn_rate"], 4),
            "ttft_target_us": round(ttft_bound * 1e6, 3),
        },
        "histogram_ttft": {
            "count": ttft_lane["count"] if ttft_lane else 0,
            "p50_us": round(ttft_lane["p50"] * 1e6, 3)
            if ttft_lane and ttft_lane["p50"] is not None else None,
            "p99_us": round(ttft_lane["p99"] * 1e6, 3)
            if ttft_lane and ttft_lane["p99"] is not None else None,
        },
        "events": total_events,
        "event_cost_ops": metrics.EVENT_COST_OPS,
        "modeled_flops": total_flops,
        "gates": gates,
    }
    return result


SCENARIO = registry.register(registry.Scenario(
    name="tracing",
    artifact="TRACING_r01.json",
    build=build,
    description="request-lifecycle tracing + exact tail-latency "
                "attribution: integer-ps decomposition over the four "
                "serving chaos drills, serve_doctor fault naming, "
                "deterministic overhead accounting, SLO ledger",
    model={"net": "gpt_tiny", "max_position_embeddings": 128},
    parallelism={"engines": 2},
    trace={"chaos": ("kill_engine / drop_decode_step / "
                     "corrupt_block_table / overload / hot-swap")},
    gates=("tracing_transparent_token_for_token",
           "decomposition_exact_kill",
           "decomposition_exact_transient",
           "decomposition_exact_overload",
           "overload_tail_owned_by_queue_wait",
           "slo_ledger_closes",
           "perf_doctor_histogram_ttft_lane",
           "perf_doctor_slo_counters",
           "decomposition_exact_swap",
           "swap_spans_cover_requests",
           "drop_diff_names_decode_compute",
           "drop_chaos_attributed_to_tids",
           "tracing_overhead_under_1pct_of_flops",
           "serve_doctor_cli_exit_codes"),
    streams={"traces": "BENCH_TRACING_DIR",
             "metrics": "BENCH_TRACING_METRICS_DIR"},
))
