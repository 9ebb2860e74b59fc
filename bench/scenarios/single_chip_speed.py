"""Scenario: the ``--single-chip-speed`` raw-speed lane (artifact
``SPEED_r01.json``), modeled under pinned v5e rates.
"""

import json
import os
import tempfile

import numpy as np_

from ..artifact import log
from . import registry


def build(scenario):
    """The raw-speed gate for ROADMAP item 3 (close the last third to
    sustained matmul), fully deterministic — cost x rate accounting
    plus executed bitwise/bound parity, ZERO wall-clock A/B
    (unreliable in this sandbox).

    Evidence layers (ISSUE 10 acceptance):

    1. **Remat policy search fits the declared budget** — the
       cost-model searcher resolves the BENCH_r05 GPT geometry against
       the v5e 16 GB HBM budget; the chosen policy's total footprint
       (params + grads + optimizer state + saved activations) must fit
       by the searcher's own accounting.
    2. **Modeled step cost improves >= 10% vs PR 9 HEAD** — one
       symmetric phase model (matmul fwd+bwd / remat recompute /
       optimizer update, each its own roofline under pinned v5e
       rates) prices the PR 9 configuration (remat "dots", fp head
       matmul, generic XLA optimizer chain with its staging copies)
       and the candidate (searched remat, int8 weight-only lm_head
       fwd+dgrad at the 2x int8 MXU rate, one-pass fused optimizer).
       Both sides flow through the SAME formulas — the only deltas are
       the fast paths under test.
    3. **Executed parity** (small geometry, runs on CPU):
       remat-searched grads bitwise vs the same policy passed
       explicitly; int8 matmul within its analytic per-channel error
       bound AND the bound proven non-vacuous (a payload quantized
       with half the claimed resolution must VIOLATE it); fused
       optimizer step bitwise vs the eager AdamW chain on f32 state
       (params AND moments, through jit.train_step).
    4. **perf_doctor lane** — the modeled records (modeled_step_s +
       the MFU/roofline triple) round-trip through perf_doctor:
       summarize shows the MFU lane, identical streams diff at exactly
       0%, and the baseline->candidate diff reports the improvement on
       the modeled verdict.
    """
    import jax
    import jax.numpy as jnp
    import paddle2_tpu as paddle
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.incubate import autotune
    from paddle2_tpu.kernels import pallas_matmul as pm
    from paddle2_tpu.models import GPTForCausalLM
    from paddle2_tpu.models.gpt import gpt_tiny
    from paddle2_tpu.observability.cost_model import (PhasedStepCost,
                                                      StepCost)
    from paddle2_tpu.tools import perf_doctor

    gates = {}

    # ---- BENCH_r05 geometry under pinned v5e rates (deterministic on
    # every host — no device probing in the model)
    H, L, NH, T, B, V = 1024, 24, 16, 1024, 8, 32768
    FFN = 4 * H
    tokens = B * T
    PEAK, HBMBW = 197e12, 819e9
    HBM_BUDGET = 16.0e9
    n_params = V * H + T * H + 12 * L * H * H
    f32_bytes = n_params * 4.0
    bf16_bytes = n_params * 2.0

    # ---- 1. remat policy search + budget fit --------------------------
    fixed = n_params * (2.0 + 2.0 + 3 * 4.0)   # bf16 p+g, f32 master+m+v
    plan = autotune.search_remat_policy(
        hidden=H, num_layers=L, num_heads=NH, seq=T, batch=B, ffn=FFN,
        budget_bytes=HBM_BUDGET, fixed_bytes=fixed,
        peak_flops=PEAK, hbm_bps=HBMBW)
    gates["remat_policy_fits_budget"] = (
        plan.fits and plan.total_bytes <= HBM_BUDGET)
    log(f"remat search: {plan.policy} (granularity="
        f"{plan.granularity}), {plan.total_bytes/1e9:.2f} GB of "
        f"{HBM_BUDGET/1e9:.0f} GB budget, modeled recompute overhead "
        f"{plan.overhead_s*1e3:.2f} ms/step")

    # ---- 2. modeled step cost: PR 9 HEAD vs candidate -----------------
    row_of = {r["policy"]: r for r in plan.table}

    def step_phases(remat_policy, int8_head, fused_opt):
        """The symmetric three-phase model. Accounting:
        * matmul — the repo's own FLOPs convention:
          tokens x (6 n_params + 12 L T H); HBM = 3 weight passes
          (fwd/dgrad/wgrad) + the activation census written forward and
          re-read backward. int8_head runs the lm_head logits matmul
          (fwd + dgrad — wgrad needs the fp activations either way) at
          the 2x int8 MXU rate: charged as half its fp FLOP-time.
        * remat — the searcher's own per-policy recompute row.
        * optimizer — HBM-bound serial tail after the last grad:
          reads bf16 grads + f32 (master, m, v), writes those three +
          the bf16 param. The generic XLA chain additionally
          materializes the f32 grad staging copy (one write + one
          re-read) the one-pass fused kernel eliminates.
        """
        ph = PhasedStepCost()
        mm_flops = tokens * (6.0 * n_params + 12.0 * L * T * H)
        head_mm = 2.0 * tokens * H * V          # logits matmul, fwd
        if int8_head:
            mm_flops -= (head_mm + head_mm) / 2.0   # fwd + dgrad at 2x
        act_census = L * tokens * (10.0 * H + 2.0 * FFN) * 2.0
        mm_bytes = 3.0 * bf16_bytes + 2.0 * act_census
        if int8_head:
            # int8 head weight: half the bytes on its fwd+dgrad reads
            mm_bytes -= 2.0 * (V * H * 1.0)
        ph.add("matmul", StepCost(mm_flops, mm_bytes,
                                  peak_flops=PEAK, hbm_bps=HBMBW))
        row = row_of[remat_policy]
        ph.add("remat", StepCost(row["recompute_flops"],
                                 row["recompute_bytes"],
                                 peak_flops=PEAK, hbm_bps=HBMBW))
        opt_bytes = (bf16_bytes              # grad read (bf16)
                     + 3.0 * f32_bytes       # master, m, v read
                     + 3.0 * f32_bytes       # master, m, v write
                     + bf16_bytes)           # bf16 param write
        if not fused_opt:
            opt_bytes += 2.0 * f32_bytes     # f32 grad staging copy
        ph.add("optimizer", StepCost(12.0 * n_params, opt_bytes,
                                     peak_flops=PEAK, hbm_bps=HBMBW))
        return ph

    base = step_phases("save_dots", int8_head=False, fused_opt=False)
    cand = step_phases(plan.policy, int8_head=True, fused_opt=True)
    t_base = base.step_time_modeled_s()
    t_cand = cand.step_time_modeled_s()
    improvement = 1.0 - t_cand / t_base
    gates["modeled_step_cost_improves_ge_10pct"] = improvement >= 0.10
    log(f"modeled step: {t_base*1e3:.1f} ms (PR 9 HEAD: dots remat, fp "
        f"head, generic optimizer) -> {t_cand*1e3:.1f} ms "
        f"({plan.policy} + int8 lm_head + fused optimizer): "
        f"{improvement*100:.1f}% better, MFU {base.mfu_modeled():.3f} "
        f"-> {cand.mfu_modeled():.3f}")

    # ---- 3a. remat search bitwise vs explicit policy ------------------
    def train_tiny(gran, budget_gb=None, seed=0, steps=3):
        paddle.seed(seed)
        cfg = gpt_tiny(use_recompute=gran is not None,
                       recompute_granularity=gran or "full",
                       remat_budget_gb=budget_gb, use_scan=True)
        m = GPTForCausalLM(cfg)
        o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
        step = paddle.jit.train_step(
            lambda ids, lab: m(ids, labels=lab)[1], o, layers=[m])
        rs = np_.random.RandomState(7)
        for _ in range(steps):
            ids = paddle.to_tensor(
                rs.randint(0, 128, (2, 16)).astype(np_.int32))
            step(ids, ids)
        return m, step

    # a probe plan (through the model's own resolution, so the fixed
    # params/optimizer bytes match) tells us which budget forces which
    # policy on the tiny geometry — the bitwise check must exercise a
    # REAL checkpoint policy, not just the save-all fast exit
    paddle.seed(0)
    probe_model = GPTForCausalLM(gpt_tiny(
        use_recompute=True, recompute_granularity="search",
        remat_budget_gb=1000.0, use_scan=True))
    probe = probe_model.gpt.remat_plan(2, 16)
    dots_total = next(r["total_bytes"] for r in probe.table
                     if r["policy"] == "save_dots")
    m_s, step_s = train_tiny("search", budget_gb=dots_total / 1e9)
    tiny_plan = m_s.gpt.remat_plan(2, 16)
    m_e, _ = train_tiny(tiny_plan.granularity)
    searched_bitwise = all(
        np_.array_equal(np_.asarray(a._data), np_.asarray(b._data))
        for a, b in zip(m_s.parameters(), m_e.parameters()))
    gates["remat_search_bitwise_vs_explicit"] = (
        searched_bitwise and tiny_plan.policy == "save_dots"
        and step_s.program_cache_size == 1)
    log(f"remat searched ({tiny_plan.policy}) vs explicit: "
        f"bitwise={searched_bitwise}, cache entries="
        f"{step_s.program_cache_size}")

    # ---- 3b. int8 matmul analytic error bound -------------------------
    rs = np_.random.RandomState(0)
    x = jnp.asarray(rs.randn(64, 512), jnp.float32)
    w = jnp.asarray(rs.randn(512, 256), jnp.float32)
    w_i8, scale = pm.quantize_channelwise(w, 8, axis=1)
    y_q = pm.int8_weight_only_matmul(x, w_i8, scale)
    # reference + error in f64 on host, so fp32 accumulation noise
    # cannot blur the bound check
    x64 = np_.asarray(x, np_.float64)
    w64 = np_.asarray(w, np_.float64)
    deq = np_.asarray(w_i8, np_.float64) * (
        np_.asarray(scale, np_.float64) / 127.0)
    err = np_.abs(x64 @ w64 - x64 @ deq)
    bound = np_.asarray(pm.weight_quant_error_bound(x, scale),
                        np_.float64)
    within = bool((err <= bound + 1e-9).all())
    # the kernel/XLA product must match its own dequantized reference
    y_ref = np_.asarray(x64 @ deq, np_.float32)
    kernel_ok = bool(np_.allclose(np_.asarray(y_q), y_ref,
                                  rtol=2e-5, atol=2e-4))
    gates["int8_error_within_analytic_bound"] = within and kernel_ok
    # non-vacuous: the same bound must CATCH a payload quantized with
    # half the claimed resolution (4-bit error against an 8-bit bound)
    w_i4, scale4 = pm.quantize_channelwise(w, 4, axis=1)
    deq4 = np_.asarray(w_i4, np_.float64) * (
        np_.asarray(scale4, np_.float64) / 7.0)
    err4 = np_.abs(x64 @ w64 - x64 @ deq4)
    violated = bool((err4 > bound).any())
    informative = bool(bound.max() < np_.abs(x64 @ w64).max())
    gates["int8_bound_nonvacuous"] = violated and informative
    log(f"int8 bound: max err {err.max():.4f} <= max bound "
        f"{bound.max():.4f} (within={within}); 4-bit payload violates:"
        f" {violated}")
    # the Pallas kernel lowering (interpret here, MXU tiles on TPU)
    # computes the same dequantized product
    y_pal = pm.int8_weight_only_matmul(x[:32], w_i8, scale,
                                       block_m=32, block_n=128,
                                       block_k=128, interpret=True)
    pallas_ok = bool(np_.allclose(np_.asarray(y_pal),
                                  (np_.asarray(x64[:32] @ deq,
                                               np_.float32)),
                                  rtol=2e-5, atol=2e-4))
    gates["int8_pallas_kernel_parity"] = pallas_ok

    # ---- 3c. fused optimizer bitwise ----------------------------------
    def opt_run(fused):
        paddle.seed(3)
        cfg = gpt_tiny(use_scan=True)
        m = GPTForCausalLM(cfg)
        m = paddle.amp.decorate(m, level="O2", dtype="bfloat16")
        o = opt.AdamW(learning_rate=1e-3, weight_decay=0.01,
                      parameters=m.parameters(), multi_precision=True,
                      fused=fused)
        step = paddle.jit.train_step(
            lambda ids, lab: m(ids, labels=lab)[1], o, layers=[m])
        rs2 = np_.random.RandomState(11)
        for _ in range(3):
            ids = paddle.to_tensor(
                rs2.randint(0, 128, (2, 16)).astype(np_.int32))
            step(ids, ids)
        params = [np_.asarray(p._data).copy() for p in m.parameters()]
        states = [np_.asarray(leaf).copy()
                  for p in m.parameters()
                  for leaf in jax.tree_util.tree_leaves(
                      o._states[id(p)])]
        return params, states

    pe, se = opt_run(False)
    pf_, sf = opt_run(True)
    fused_bitwise = (all(np_.array_equal(a, b) for a, b in zip(pe, pf_))
                     and all(np_.array_equal(a, b)
                             for a, b in zip(se, sf)))
    gates["fused_optimizer_bitwise"] = fused_bitwise
    log(f"fused AdamW vs eager through train_step (multi-precision): "
        f"params+moments bitwise={fused_bitwise}")

    # ---- 4. perf_doctor round-trip ------------------------------------
    def write_stream(d, ph):
        os.makedirs(d, exist_ok=True)
        fields = ph.step_record_fields()
        rec = {"type": "step", "rank": 0,
               "total_s": fields["modeled_step_s"],
               "compute_s": fields["modeled_step_s"],
               "input_wait_s": 0.0, "collective_s": 0.0, "host_s": 0.0,
               "tokens": tokens}
        rec.update(fields)
        with open(os.path.join(d, "metrics_rank_0.jsonl"), "w") as f:
            for s in range(6):
                f.write(json.dumps(dict(rec, step=s)) + "\n")

    stream_dir = os.environ.get("BENCH_SPEED_METRICS_DIR")
    tmp = tempfile.mkdtemp(prefix="bench_speed_")
    d_base = os.path.join(tmp, "base")
    d_cand = stream_dir or os.path.join(tmp, "cand")
    d_cand2 = os.path.join(tmp, "cand2")
    write_stream(d_base, base)
    write_stream(d_cand, cand)
    write_stream(d_cand2, cand)
    rep_c = perf_doctor.summarize(perf_doctor.load_streams(d_cand))
    mfu_lane = rep_c["aggregate"].get("mfu_modeled")
    gates["perf_doctor_mfu_lane"] = (
        mfu_lane is not None
        and abs(mfu_lane - cand.mfu_modeled()) < 1e-9
        and "MFU" in perf_doctor.format_summary(rep_c, d_cand))
    d_same = perf_doctor.diff(
        rep_c, perf_doctor.summarize(perf_doctor.load_streams(d_cand2)))
    gates["identical_streams_diff_exactly_zero"] = (
        d_same["total_delta_pct"] == 0.0 and not d_same["regressed"])
    d_impr = perf_doctor.diff(
        perf_doctor.summarize(perf_doctor.load_streams(d_base)), rep_c)
    gates["diff_reports_modeled_improvement"] = (
        d_impr["verdict_source"] == "modeled"
        and d_impr["total_delta_pct"] < -9.0
        and not d_impr["regressed"])

    ok = all(gates.values())
    result = {
        "metric": "single_chip_modeled_step_improvement",
        "value": round(improvement, 4),
        "unit": "fraction of PR 9 HEAD modeled step time removed "
                "(cost x rate, zero wall-clock A/B)",
        "modeled": {
            "config": "BENCH_r05 GPT (hidden 1024, layers 24, seq "
                      "1024, batch 8, vocab 32768, bf16)",
            "baseline_step_ms": round(t_base * 1e3, 3),
            "candidate_step_ms": round(t_cand * 1e3, 3),
            "baseline_breakdown": base.breakdown(),
            "candidate_breakdown": cand.breakdown(),
            "mfu_modeled": {"base": round(base.mfu_modeled(), 4),
                            "cand": round(cand.mfu_modeled(), 4)},
            "modeled_tokens_per_s": {
                "base": round(tokens / t_base, 1),
                "cand": round(tokens / t_cand, 1)},
            "rates": {"peak_tflops": PEAK / 1e12,
                      "hbm_gbps": HBMBW / 1e9,
                      "hbm_budget_gb": HBM_BUDGET / 1e9},
        },
        "remat_plan": {
            "policy": plan.policy, "granularity": plan.granularity,
            "fits": plan.fits,
            "total_gb": round(plan.total_bytes / 1e9, 3),
            "budget_gb": HBM_BUDGET / 1e9,
            "overhead_ms": round(plan.overhead_s * 1e3, 3),
            "table": [
                {k: (round(v, 6) if isinstance(v, float) else v)
                 for k, v in r.items()} for r in plan.table],
        },
        "gates": gates,
        "ok": ok,
        "note": "parity gates executed on CPU at tiny geometry; "
                "BENCH-geometry figures are deterministic cost x rate "
                "under pinned v5e rates — wall-clock is unreliable in "
                "this sandbox",
    }
    return result


SCENARIO = registry.register(registry.Scenario(
    name="single-chip-speed",
    artifact="SPEED_r01.json",
    build=build,
    description="single-chip raw speed: remat policy search, int8 "
                "weight-only lm_head, fused optimizer, modeled "
                "cost x rate step improvement + perf_doctor round-trip",
    model={"config": "BENCH_r05 GPT", "hidden": 1024, "layers": 24,
           "seq": 1024, "batch": 8, "vocab": 32768},
    parallelism={"chips": 1},
    trace={"kind": "modeled", "steps": 6},
    gates=("remat_policy_fits_budget",
           "modeled_step_cost_improves_ge_10pct",
           "remat_search_bitwise_vs_explicit",
           "int8_error_within_analytic_bound",
           "int8_bound_nonvacuous",
           "int8_pallas_kernel_parity",
           "fused_optimizer_bitwise",
           "perf_doctor_mfu_lane",
           "identical_streams_diff_exactly_zero",
           "diff_reports_modeled_improvement"),
    streams={"metrics": "BENCH_SPEED_METRICS_DIR"},
))
