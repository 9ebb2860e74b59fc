"""Scenario: the ``--serving`` continuous-batching gate (artifact
``SERVING_r01.json``).
"""

import numpy as np

from ..artifact import bench_scratch, log

from . import registry


def build(scenario):
    """Production serving gate: continuous batching + paged KV vs the
    one-request-at-a-time Predictor loop, fully deterministic (XLA
    cost model x seeded Poisson trace — ZERO wall-clock anywhere).

    Gates (ISSUE 9 acceptance):
      1. aggregate tokens/s >= 3x the Predictor baseline under the
         same modeled load,
      2. p99 TTFT under the load bound (10x the per-request floor of
         prefill + one decode step — a stable-queue bound: offered
         load is pinned at 5x baseline capacity, well under the
         batch-8 engine's capacity),
      3. KV high-water mark <= 55% of the contiguous max-seq-len
         cache a non-paged engine reserves for the same batch,
      4. compiled decode program count <= the fixed bucket budget
         (no per-composition recompiles).
    Writes the serving metrics stream (step records carry EXPLICIT
    tokens + modeled_step_s) for perf_doctor, and SERVING_r01.json.
    """
    import paddle2_tpu as paddle
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    from paddle2_tpu.observability import metrics
    from paddle2_tpu.serving import (EngineConfig, ServingEngine,
                                     poisson_trace, simulate_serving,
                                     simulate_predictor_baseline)
    from paddle2_tpu.serving.simulate import cost_seconds

    metrics_dir = bench_scratch("serving_metrics",
                                env_var="BENCH_SERVING_METRICS_DIR")
    paddle.seed(0)
    # WIDTH CALIBRATION (PR 21). The gates below state what continuous
    # batching buys when a decode step is dominated by bytes that do
    # not grow with the batch — on a chip, the weight stream. At
    # gpt_tiny's hidden 64 the weights are 0.5 MB and the cost model
    # (XLA cost analysis of the UNOPTIMIZED program) prices the per-row
    # elementwise work — mostly the unfused erf-GELU chain — at ~0.55
    # MB per row, so a batch-8 step costs 4.1x a batch-1 step and no
    # load can show 3x. Until PR 21 the lane passed at hidden 64 only
    # because the decode program copied a layer out of the KV pool
    # every step — 2.4 MB of row-independent bytes that stood in for a
    # weight stream; the chip-shaped kernel reads the pool in place.
    # Hidden 512 (25 MB of weights, a batch-8 step 2.0x a batch-1 step)
    # is in the regime the gates are about; 8 heads x 64 is the served
    # head geometry (two heads per 128-lane page).
    # max_position_embeddings must cover max_model_len=128 — the
    # engine validates it (clamped wpe gathers would silently corrupt)
    cfg = gpt_tiny(use_scan=False, hidden_size=512, num_heads=8,
                   max_position_embeddings=128)
    model = GPTForCausalLM(cfg)

    def make_engine():
        return ServingEngine(model, config=EngineConfig(
            block_size=16, num_blocks=40, max_batch=8,
            prefill_budget_tokens=64, max_model_len=128))

    prompt_lens, gen_tokens = [16, 24], [12, 24]
    mean_gen = float(np.mean(gen_tokens))

    # -- phase 1: probe the cost model (compiles prefill + b1 decode),
    #    then derive the OFFERED LOAD from the baseline's own modeled
    #    capacity: 5x over it saturates one-at-a-time serving while
    #    staying under the batch-8 engine's ~8x headroom
    probe = make_engine()
    probe_trace = poisson_trace(2, rate_per_s=100.0,
                                prompt_lens=prompt_lens,
                                gen_tokens=gen_tokens,
                                vocab=cfg.vocab_size, seed=1)
    simulate_serving(probe, probe_trace)
    b1_key = min(probe.runner._decode_costs)
    decode_s = cost_seconds(probe.runner.decode_cost(b1_key))
    prefill_s = max(cost_seconds(c)
                    for c in probe.runner._prefill_costs.values())
    base_token_capacity = 1.0 / decode_s
    offered_tokens_per_s = 5.0 * base_token_capacity
    rate_req = offered_tokens_per_s / mean_gen
    log(f"serving probe: decode_s={decode_s*1e6:.1f}us "
        f"prefill_s={prefill_s*1e6:.1f}us "
        f"offered={offered_tokens_per_s:,.0f} tok/s "
        f"({rate_req:,.1f} req/s)")

    # -- phase 2: the measured run, metrics plane on
    metrics.enable(metrics_dir, rank=0, flush_steps=1)
    engine = make_engine()
    trace = poisson_trace(40, rate_per_s=rate_req,
                          prompt_lens=prompt_lens, gen_tokens=gen_tokens,
                          vocab=cfg.vocab_size, seed=7)
    rep = simulate_serving(engine, trace)
    base = simulate_predictor_baseline(engine, trace)
    metrics.flush()
    metrics.export_prometheus()
    metrics.disable()

    ratio = rep.tokens_per_s / max(base.tokens_per_s, 1e-12)
    ttft_bound = 10.0 * (prefill_s + decode_s)
    gates = {
        "tokens_per_s_3x_baseline": ratio >= 3.0,
        "p99_ttft_under_bound": rep.p99_ttft_s <= ttft_bound,
        "kv_high_water_le_55pct": rep.kv_ratio <= 0.55,
        "decode_programs_bounded":
            rep.decode_programs <= rep.program_budget,
    }
    log(f"serving: CB {rep.tokens_per_s:,.0f} tok/s vs baseline "
        f"{base.tokens_per_s:,.0f} (ratio {ratio:.2f}, gate >= 3)")
    log(f"serving: p99 TTFT {rep.p99_ttft_s*1e3:.3f}ms "
        f"(bound {ttft_bound*1e3:.3f}ms)  mean occupancy "
        f"{rep.mean_batch_occupancy:.2f}  evictions {rep.evictions}")
    log(f"serving: KV high water {rep.kv_high_water_bytes:,}B = "
        f"{100*rep.kv_ratio:.1f}% of contiguous "
        f"{rep.contiguous_cache_bytes:,}B (gate <= 55%)")
    log(f"serving: decode programs {rep.decode_programs} <= budget "
        f"{rep.program_budget}")
    result = {
        "metric": "serving_tokens_per_s_vs_predictor",
        "value": round(ratio, 3), "unit": "x",
        "tokens_per_s": round(rep.tokens_per_s, 1),
        "baseline_tokens_per_s": round(base.tokens_per_s, 1),
        "p99_ttft_ms": round(rep.p99_ttft_s * 1e3, 4),
        "ttft_bound_ms": round(ttft_bound * 1e3, 4),
        "mean_ttft_ms": round(rep.mean_ttft_s * 1e3, 4),
        "kv_high_water_ratio": round(rep.kv_ratio, 4),
        "decode_programs": rep.decode_programs,
        "program_budget": rep.program_budget,
        "mean_batch_occupancy": round(rep.mean_batch_occupancy, 3),
        "evictions": rep.evictions,
        "decode_steps": rep.decode_steps,
        "offered_tokens_per_s": round(offered_tokens_per_s, 1),
        "gates": gates,
    }
    return result


SCENARIO = registry.register(registry.Scenario(
    name="serving",
    artifact="SERVING_r01.json",
    build=build,
    description="continuous batching + paged KV vs the one-request-"
                "at-a-time Predictor loop, cost x rate",
    model={"net": "gpt_tiny", "hidden_size": 512, "num_heads": 8,
           "max_position_embeddings": 128},
    parallelism={"engines": 1},
    trace={"kind": "poisson", "requests": 40, "seed": 7,
           "prompt_lens": [16, 24], "gen_tokens": [12, 24]},
    gates=("tokens_per_s_3x_baseline", "p99_ttft_under_bound",
           "kv_high_water_le_55pct", "decode_programs_bounded",),
    streams={"metrics": "BENCH_SERVING_METRICS_DIR"},
))
