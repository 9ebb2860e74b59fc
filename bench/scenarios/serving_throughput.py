"""Scenario: the ``--serving-throughput`` per-token economics gate
(artifact ``SERVING_THROUGHPUT_r01.json``).
"""

import os

from ..artifact import bench_scratch, log

from . import registry


def build(scenario):
    """``--serving-throughput``: the per-token economics gate (ISSUE
    14) — copy-on-write prefix caching, speculative decoding, and the
    online-softmax/split-K flash-decode kernel, all deterministic
    (XLA cost model x seeded traces x virtual clock — ZERO wall-clock
    anywhere; run twice, SERVING_THROUGHPUT_r01.json is
    byte-identical).

    Gates:
      1. **Prefix caching** — a shared-system-prompt trace (48-token
         system prefix, per-request suffixes padding to the SAME
         prefill bucket so cached KV is bitwise what a private
         prefill would write): KV bytes/request (allocator handouts,
         shares are free) reduced >= 2x vs the no-sharing run, with
         token-CRC equality — sharing is exact, not approximate.
      2. **Speculation** — an acceptance-controlled oracle drafter
         pinned at 70%: modeled tokens/s uplift >= 1.5x vs the
         non-speculative run on a decode-bound trace, token-CRC
         equality (wrong drafts are REJECTED by the in-program
         verify; the stream never changes), measured acceptance
         within 2 points of the 70% setpoint.
      3. **32k kernel** — deterministic accounting under pinned v5e
         rates: the PR 9 single-softmax kernel's whole-context VMEM
         scratch CANNOT fit at 32k (feasible=False — it has no
         latency to model), the split-K kernel fits and its modeled
         decode latency stays within 1.25x the pure KV-read roofline;
         the split body EXECUTES bitwise (fp32) against its dense
         mirrored reference and allclose against the global-softmax
         reference at a multi-split context.
      4. **int4 weight-only** (ROADMAP item 4 satellite) — the
         analytic error bound HOLDS at 4 bits against an f64
         reference AND is NON-VACUOUS (a 2-bit payload violates it;
         it beats the trivial |y| bound), through the packed-nibble
         storage path.
      5. **PR 11/12 composition** — the four reliability drills
         (kill / transient / overload / hot-swap) run with prefix
         caching + speculation ENABLED: token-for-token vs their
         clean twins, allocator + prefix-cache ledger drains clean,
         and the PR 12 integer-picosecond decomposition identity
         stays exact on every finished request.
    """
    import io
    import shutil
    import zlib
    from contextlib import redirect_stdout

    import jax.numpy as jnp
    import numpy as np_
    import paddle2_tpu as paddle
    from paddle2_tpu.distributed.fault_tolerance import chaos
    from paddle2_tpu.kernels import pallas_matmul as pm
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    from paddle2_tpu.observability import metrics, tracing
    from paddle2_tpu.serving import (
        EngineConfig, EngineFailoverRouter, HotSwapController,
        ReliabilityConfig, ServingEngine, SpeculativeConfig,
        paged_attention_decode, paged_attention_reference,
        paged_attention_split_reference, simulate_router,
        simulate_serving, poisson_trace)
    from paddle2_tpu.serving import paged_attention as pa
    from paddle2_tpu.serving.simulate import cost_seconds
    from paddle2_tpu.tools import perf_doctor, serve_doctor

    metrics_dir = bench_scratch(
        "serving_throughput_metrics",
        env_var="BENCH_SERVING_THROUGHPUT_METRICS_DIR")
    trace_root = bench_scratch(
        "serving_throughput_traces",
        env_var="BENCH_SERVING_THROUGHPUT_TRACE_DIR")
    for d in (metrics_dir, trace_root):
        shutil.rmtree(d, ignore_errors=True)   # streams append

    paddle.seed(0)
    cfg = gpt_tiny(use_scan=False, max_position_embeddings=128)
    model = GPTForCausalLM(cfg)
    VOCAB = cfg.vocab_size
    gates = {}

    def make_engine(prefix=False, spec=None, reliability=None,
                    num_blocks=64):
        return ServingEngine(model, config=EngineConfig(
            block_size=16, num_blocks=num_blocks, max_batch=8,
            prefill_budget_tokens=128, max_model_len=128,
            enable_prefix_cache=prefix, spec=spec,
            reliability=reliability))

    # ---- shared-system-prompt trace: every prompt = 48-token system
    # prefix + an 8/16-token suffix, so totals (56/64) pad to the SAME
    # 64-token prefill bucket — equal padded widths keep the cached
    # prefix KV bitwise identical to what each request's own prefill
    # writes, which is what makes sharing EXACT (1-ulp row-grouping
    # drift across buckets would make it merely close)
    rng = np_.random.default_rng(11)
    sys_prompt = rng.integers(0, VOCAB, size=48).tolist()
    N_REQ, GEN = 24, 16
    shared_trace = []
    t_arr = 0.0
    for i in range(N_REQ):
        sfx = rng.integers(0, VOCAB,
                           size=(8 if i % 2 else 16)).tolist()
        t_arr += float(rng.exponential(1e-5))   # saturating burst
        shared_trace.append({"arrival_t": t_arr,
                             "prompt": sys_prompt + sfx,
                             "max_new_tokens": GEN})

    def crc(engine, n):
        payload = b"".join(
            np_.asarray(engine.sequence(i).generated,
                        np_.int64).tobytes() for i in range(n))
        return zlib.crc32(payload) & 0xFFFFFFFF

    metrics.enable(metrics_dir, rank=0, flush_steps=1)

    # ---- run A: plain (no sharing, no speculation) — THE reference
    eng_a = make_engine()
    rep_a = simulate_serving(eng_a, [dict(r) for r in shared_trace])
    crc_a = crc(eng_a, N_REQ)
    truth = {i: list(eng_a.sequence(i).generated)
             for i in range(N_REQ)}

    # ---- run B: prefix caching only — the KV-bytes gate
    eng_b = make_engine(prefix=True)
    rep_b = simulate_serving(eng_b, [dict(r) for r in shared_trace])
    crc_b = crc(eng_b, N_REQ)
    kv_ratio = (rep_a.kv_bytes_per_request
                / max(rep_b.kv_bytes_per_request, 1.0))
    gates["prefix_kv_bytes_per_request_2x"] = kv_ratio >= 2.0
    gates["prefix_token_crc_equal"] = crc_b == crc_a
    log(f"serving-throughput prefix: KV/req "
        f"{rep_a.kv_bytes_per_request:,.0f}B -> "
        f"{rep_b.kv_bytes_per_request:,.0f}B ({kv_ratio:.2f}x, "
        f"gate >= 2) hits={rep_b.prefix_hits} "
        f"misses={rep_b.prefix_misses} crc_equal={crc_b == crc_a}")

    # ---- run C: prefix + speculation at a controlled 70% acceptance.
    # The oracle drafts from run A's token streams, choosing per round
    # how many leading drafts are TRUE so the running acceptance
    # tracks the setpoint; the wrong tail proves the verify pass
    # rejects without perturbing the stream.
    class OracleDrafter:
        def __init__(self, truth, k, target):
            self.truth, self.k, self.target = truth, k, target
            self.acc = 0
            self.prop = 0

        def __call__(self, seq):
            t = self.truth.get(seq.req_id)
            if t is None:
                return []
            done = len(seq.generated)
            room = seq.request.max_new_tokens - done
            k = min(self.k, room - 1)
            if k < 1 or done >= len(t):
                return []
            best_w, best_err = 0, None
            for w in range(k + 1):
                err = abs((self.acc + w) / (self.prop + k)
                          - self.target)
                if best_err is None or err < best_err:
                    best_w, best_err = w, err
            drafts = list(t[done:done + best_w])
            while len(drafts) < k:
                j = done + len(drafts)
                wrong = (t[j] + 1) % VOCAB if j < len(t) else 1
                drafts.append(int(wrong))
            self.acc += best_w
            self.prop += k
            return drafts

    drafter = OracleDrafter(truth, k=3, target=0.70)
    eng_c = make_engine(prefix=True, spec=SpeculativeConfig(
        num_draft_tokens=3, draft_fn=drafter))
    rep_c = simulate_serving(eng_c, [dict(r) for r in shared_trace])
    crc_c = crc(eng_c, N_REQ)
    gates["spec_token_crc_equal"] = crc_c == crc_a

    # ---- runs D/E: the THROUGHPUT half of the speculation gate on a
    # decode-bound workload (long generations, short prompts): a
    # decode step is dominated by the bytes every step streams
    # regardless of row count — on a chip, the weights — so a
    # (k+1)-row verify step emits ~1 + 0.7k tokens for little more
    # than a 1-row step's bytes (the flash-decode economics). WIDTH
    # CALIBRATION (PR 21): these two runs serve a hidden-512 model
    # (8 heads x 64), for the reason written out in bench_serving —
    # at gpt_tiny's hidden 64 the modeled per-row cost exceeds the
    # whole weight set, and the uplift only ever showed there because
    # the old decode program copied a layer out of the KV pool every
    # step. The engine batches TWO sequences (was four): speculation
    # is a small-batch tool — its (k+1) verify rows per sequence are
    # nearly free only while the step is bound by the weight stream,
    # and under the CPU-nominal rates of this clock (ridge 2 FLOP/byte)
    # an f32 step turns compute-bound past ~8 rows, where every verify
    # row costs a full row. The saturating shared trace above stays
    # the EXACTNESS half (crc_c).
    wide = GPTForCausalLM(gpt_tiny(
        use_scan=False, hidden_size=512, num_heads=8,
        max_position_embeddings=128))
    N_D, GEN_D = 12, 48
    spec_trace = []
    t_arr = 0.0
    for i in range(N_D):
        t_arr += float(rng.exponential(1e-6))
        spec_trace.append({
            "arrival_t": t_arr,
            "prompt": rng.integers(0, VOCAB, size=16).tolist(),
            "max_new_tokens": GEN_D})

    def make_decode_engine(spec=None):
        return ServingEngine(wide, config=EngineConfig(
            block_size=16, num_blocks=128, max_batch=2,
            prefill_budget_tokens=128, max_model_len=128, spec=spec))

    eng_d = make_decode_engine()
    rep_d = simulate_serving(eng_d, [dict(r) for r in spec_trace])
    crc_d = crc(eng_d, N_D)
    truth_d = {i: list(eng_d.sequence(i).generated)
               for i in range(N_D)}
    drafter_d = OracleDrafter(truth_d, k=3, target=0.70)
    eng_e = make_decode_engine(spec=SpeculativeConfig(
        num_draft_tokens=3, draft_fn=drafter_d))
    rep_e = simulate_serving(eng_e, [dict(r) for r in spec_trace])
    crc_e = crc(eng_e, N_D)
    uplift = rep_e.tokens_per_s / max(rep_d.tokens_per_s, 1e-12)
    gates["spec_decode_trace_crc_equal"] = crc_e == crc_d
    gates["spec_tokens_per_s_uplift_1p5x"] = uplift >= 1.5
    gates["spec_acceptance_at_setpoint"] = (
        rep_e.spec_rejected > 0
        and abs(rep_e.spec_acceptance - 0.70) <= 0.02)
    log(f"serving-throughput spec: {rep_d.tokens_per_s:,.0f} -> "
        f"{rep_e.tokens_per_s:,.0f} modeled tok/s ({uplift:.2f}x, "
        f"gate >= 1.5) acceptance={rep_e.spec_acceptance:.3f} "
        f"(accepted={rep_e.spec_accepted} "
        f"rejected={rep_e.spec_rejected}) steps {rep_d.decode_steps}"
        f"->{rep_e.decode_steps} combined-crc_equal={crc_c == crc_a}")

    metrics.flush()
    metrics.export_prometheus()
    metrics.disable()

    # doctors see the new economics: raw counters in perf_doctor,
    # derived rates in serve_doctor's THROUGHPUT section
    pd_rep = perf_doctor.summarize(perf_doctor.load_streams(metrics_dir),
                                   warmup=0)
    cnt = pd_rep.get("counters") or {}
    thr = serve_doctor.load_throughput(metrics_dir)
    # the metrics window covered runs B..E: the joined ledgers must
    # reproduce the sim reports' own counts exactly
    acc_all = rep_c.spec_accepted + rep_e.spec_accepted
    rej_all = rep_c.spec_rejected + rep_e.spec_rejected
    gates["doctors_surface_economics"] = (
        cnt.get("serving_prefix_hits_total", 0) > 0
        and cnt.get("serving_spec_accepted_total", 0) == acc_all > 0
        and thr["spec_acceptance"] is not None
        and abs(thr["spec_acceptance"]
                - acc_all / max(acc_all + rej_all, 1)) < 1e-9
        and thr["prefix_hit_rate"] is not None)

    # ---- 32k-context kernel gate (pinned v5e rates — deterministic
    # on every host; the PR 9 body has no latency to model at 32k)
    PEAK, HBMBW = 197e12, 819e9
    CTX32K, H32, D32 = 32768, 16, 128
    m_old = pa.modeled_decode_latency_s(
        CTX32K, num_heads=H32, head_dim=D32, dtype="bfloat16",
        peak_flops=PEAK, hbm_bps=HBMBW)
    pps_auto = pa.auto_pages_per_split(
        -(-CTX32K // 16), 16, D32, "bfloat16")
    m_new = pa.modeled_decode_latency_s(
        CTX32K, num_heads=H32, head_dim=D32, dtype="bfloat16",
        pages_per_split=pps_auto, peak_flops=PEAK, hbm_bps=HBMBW)
    ideal_s = m_new["kv_bytes"] / HBMBW
    gates["kernel_32k_single_softmax_infeasible"] = \
        not m_old["feasible"]
    gates["kernel_32k_split_feasible_near_roofline"] = (
        m_new["feasible"] and m_new["n_splits"] > 1
        and m_new["latency_s"] <= 1.25 * ideal_s)
    # executed evidence at a multi-split context (fast on CPU)
    krng = np_.random.default_rng(5)
    bs_k, Hk, Dk, ctx_k = 16, 2, 16, 160        # 10 pages
    n_pg = -(-ctx_k // bs_k)
    kq = krng.normal(size=(1, 1, Hk, Dk)).astype(np_.float32)
    # one layer's pool, a token's heads merged into one row:
    # [N, bs, H*D] (the kernel takes the whole model's, [L, ...])
    kp = krng.normal(size=(24, bs_k, Hk * Dk)).astype(np_.float32)
    vp = krng.normal(size=(24, bs_k, Hk * Dk)).astype(np_.float32)
    tb = krng.permutation(np_.arange(1, 24))[:n_pg][None, :] \
        .astype(np_.int32)
    o_split = paged_attention_decode(
        jnp.asarray(kq), jnp.asarray(kp)[None], jnp.asarray(vp)[None], tb,
        np_.asarray([ctx_k]), pages_per_split=3)
    r_split = paged_attention_split_reference(
        jnp.asarray(kq), jnp.asarray(kp), jnp.asarray(vp), tb,
        np_.asarray([ctx_k]), pages_per_split=3)
    r_glob = paged_attention_reference(
        jnp.asarray(kq), jnp.asarray(kp), jnp.asarray(vp), tb,
        np_.asarray([ctx_k]))
    # the kernel sums per page then across pages, its mirror per row:
    # a few ulp in fp32, not bitwise (tests/test_serving.py KERNEL_TOL)
    gates["kernel_split_matches_mirror"] = bool(np_.allclose(
        np_.asarray(o_split), np_.asarray(r_split),
        rtol=2e-6, atol=2e-6))
    gates["kernel_split_allclose_vs_global"] = bool(np_.allclose(
        np_.asarray(o_split), np_.asarray(r_glob),
        rtol=2e-6, atol=2e-6))
    log(f"serving-throughput 32k: single-softmax scratch "
        f"{m_old['scratch_vmem_bytes']/2**20:.1f}MiB infeasible="
        f"{not m_old['feasible']}; split pps={pps_auto} "
        f"({m_new['n_splits']} splits, "
        f"{m_new['scratch_vmem_bytes']/2**20:.1f}MiB) modeled "
        f"{m_new['latency_s']*1e3:.3f}ms <= 1.25x roofline "
        f"{ideal_s*1e3:.3f}ms")

    # ---- int4 weight-only: bound holds + non-vacuous (ROADMAP 4)
    qrng = np_.random.default_rng(7)
    xq = jnp.asarray(qrng.normal(size=(32, 64)), jnp.float32)
    wq = jnp.asarray(qrng.normal(size=(64, 128)), jnp.float32)
    w_i4, s4 = pm.quantize_channelwise(wq, 4, axis=1)
    packed = pm.pack_int4(w_i4)
    y4 = pm.int4_weight_only_matmul(xq, packed, s4)
    x64 = np_.asarray(xq, np_.float64)
    w64 = np_.asarray(wq, np_.float64)
    y_ref = x64 @ w64
    bound4 = np_.asarray(pm.weight_quant_error_bound(xq, s4, 4),
                         np_.float64)
    err4 = np_.abs(np_.asarray(y4, np_.float64) - y_ref)
    holds = bool((err4 <= bound4 + 1e-6).all())
    w_i2, s2 = pm.quantize_channelwise(wq, 2, axis=1)
    y2 = pm.int8_weight_only_matmul(xq, w_i2, s2, quant_bits=2)
    err2 = np_.abs(np_.asarray(y2, np_.float64) - y_ref)
    violated = bool((err2 > bound4).any())
    informative = bool(bound4.max() < np_.abs(y_ref).max())
    gates["int4_bound_holds"] = holds
    gates["int4_bound_nonvacuous"] = violated and informative
    log(f"serving-throughput int4: bound holds={holds} (max err "
        f"{err4.max():.4f} <= max bound {bound4.max():.4f}); 2-bit "
        f"payload violates={violated}; informative={informative}")

    # ---- PR 11/12 composition: the four reliability drills with
    # prefix caching + speculation ENABLED (n-gram self-draft — the
    # drill traces use a narrow token range so drafts actually fire)
    probe = make_engine()
    simulate_serving(probe, poisson_trace(
        2, rate_per_s=100.0, prompt_lens=[16, 24],
        gen_tokens=[12, 24], vocab=VOCAB, seed=1))
    b1_key = min(probe.runner._decode_costs)
    decode_s = cost_seconds(probe.runner.decode_cost(b1_key))
    probe_interval_s = 2.0 * decode_s
    base_capacity = 1.0 / decode_s
    mean_gen = 18.0

    def drill_trace(n, seed, rate, priorities=False):
        t = poisson_trace(n, rate_per_s=rate, prompt_lens=[16, 24],
                          gen_tokens=[12, 24], vocab=8, seed=seed)
        if priorities:
            for i, r in enumerate(t):
                r["priority"] = 1 if i % 3 == 0 else 0
        return t

    def run_drill(name, n_engines, rel=None, arm=None, n=16, seed=101,
                  rate=None, priorities=False, on_round=None,
                  features=True):
        rate = rate if rate is not None else \
            2.0 * base_capacity / mean_gen
        tdir = os.path.join(trace_root, name)
        shutil.rmtree(tdir, ignore_errors=True)
        tracing.enable(tdir, rank=0)
        if arm:
            chaos.arm(arm)
        spec = SpeculativeConfig(num_draft_tokens=3) if features \
            else None
        router = EngineFailoverRouter(
            [make_engine(prefix=features, spec=spec, reliability=rel,
                         num_blocks=40) for _ in range(n_engines)],
            probe_interval_s=probe_interval_s)
        rep = simulate_router(
            router,
            [dict(r) for r in drill_trace(n, seed, rate, priorities)],
            on_round=on_round)
        # fired set read BEFORE disarm (disarm drops the injector and
        # its ledger with it)
        fired = {k for k, _ in chaos.fired_log()}
        chaos.disarm()
        tracing.flush()
        tracing.disable()
        return router, rep, tdir, fired

    def router_crc(router, rep):
        payload = b"".join(
            np_.asarray(router.sequence(r).generated,
                        np_.int64).tobytes() for r in rep.rids)
        return zlib.crc32(payload) & 0xFFFFFFFF

    def decomp_exact(tdir, rep):
        dec = tracing.decompose(tracing.load_trace_dir(tdir))
        fin = {t: c for t, c in dec.items() if c["finished"]}
        return (len(fin) == rep.completed
                and all(c["exact"] for c in fin.values()), len(fin))

    # drill 1: engine kill -> failover, token-for-token vs clean twin
    r_clean, rep_clean, d_clean, _ = run_drill("kill_clean", 2)
    r_kill, rep_kill, d_kill, _ = run_drill("kill", 2,
                                            arm="kill_engine:4:1")
    ok_kill, fin_kill = decomp_exact(d_kill, rep_kill)
    gates["compose_kill_token_for_token"] = (
        rep_kill.completed == rep_clean.completed == 16
        and router_crc(r_kill, rep_kill)
        == router_crc(r_clean, rep_clean)
        and rep_kill.failovers == 1)
    gates["compose_kill_decomposition_exact"] = ok_kill
    # drill 2: transient faults (drop + corrupt) token-invisible, and
    # the allocator + prefix-cache ledger closes: every non-cached
    # block back on the free list, every cached block held ONLY by
    # the cache
    r1_clean, rep1_clean, _, _ = run_drill("tr_clean", 1)
    r_tr, rep_tr, d_tr, fired = run_drill(
        "transient", 1, arm="drop_decode_step:3,corrupt_block_table:5:1")
    eng_tr = r_tr.engines[0]
    cache_tr = eng_tr.prefix_cache
    ok_tr, _ = decomp_exact(d_tr, rep_tr)
    gates["compose_transient_token_invisible"] = (
        fired == {"drop_decode_step", "corrupt_block_table"}
        and rep_tr.completed == 16
        and router_crc(r_tr, rep_tr)
        == router_crc(r1_clean, rep1_clean))
    gates["compose_transient_ledger_closes"] = (
        eng_tr.allocator.free_count + len(cache_tr.held_blocks())
        == eng_tr.allocator.num_blocks - 1
        and all(eng_tr.allocator.refcount(b) == 1
                for b in cache_tr.held_blocks()))
    gates["compose_transient_decomposition_exact"] = ok_tr
    # drill 3: overload burst vs bounded queue + priorities
    r_over, rep_over, d_over, _ = run_drill(
        "overload", 1, rel=ReliabilityConfig(max_queue_depth=6),
        n=40, seed=202, rate=10.0 * base_capacity / mean_gen,
        priorities=True)
    shed_prios = [s.priority for s in r_over.engines[0].scheduler.shed]
    shed_n = rep_over.shed + rep_over.rejected
    ok_over, _ = decomp_exact(d_over, rep_over)
    gates["compose_overload_sheds_lowest_only"] = (
        0 < shed_n <= 24 and all(p == 0 for p in shed_prios)
        and rep_over.completed == rep_over.submitted - rep_over.shed)
    gates["compose_overload_decomposition_exact"] = ok_over
    # drill 4: staged hot-swap + rollback, census vs no-swap twin
    r_ref, rep_ref, _, _ = run_drill("swap_ref", 2, n=16, seed=303)
    census_ref = [e.num_decode_programs for e in r_ref.engines]
    swap_state = {}

    def on_round(rt, clock, idx):
        ctl = swap_state.get("ctl")
        if ctl is None:
            new_w = [w * 1.001
                     if "float" in str(getattr(w, "dtype", "")) else w
                     for w in rt.engines[0].runner._weights()]
            ctl = swap_state["ctl"] = HotSwapController(
                rt.engines, new_w)
        if idx in (6, 9):
            ctl.stage_next(now=clock)
        elif idx == 14 and ctl.state == "committed":
            ctl.rollback(now=clock)

    r_swap, rep_swap, d_swap, _ = run_drill("swap", 2, n=16, seed=303,
                                            on_round=on_round)
    census_swap = [e.num_decode_programs for e in r_swap.engines]
    ctl = swap_state["ctl"]
    ok_swap, _ = decomp_exact(d_swap, rep_swap)
    gates["compose_hot_swap_zero_dropped_census"] = (
        rep_swap.completed == 16 and ctl.state == "rolled_back"
        and census_swap == census_ref)
    gates["compose_hot_swap_decomposition_exact"] = ok_swap
    log(f"serving-throughput compose: kill crc_eq="
        f"{gates['compose_kill_token_for_token']} transient_ok="
        f"{gates['compose_transient_token_invisible']} overload shed="
        f"{shed_n} swap census {census_swap} vs {census_ref}; "
        f"decomposition exact on all four drills="
        f"{ok_kill and ok_tr and ok_over and ok_swap}")

    result = {
        "metric": "serving_throughput_next_tier",
        "value": round(uplift, 3),
        "unit": "x modeled tokens/s at 70% acceptance "
                "(prefix+spec vs plain)",
        "prefix": {
            "kv_bytes_per_request_plain":
                round(rep_a.kv_bytes_per_request, 1),
            "kv_bytes_per_request_shared":
                round(rep_b.kv_bytes_per_request, 1),
            "kv_reduction_x": round(kv_ratio, 3),
            "hits": rep_b.prefix_hits,
            "misses": rep_b.prefix_misses,
            "tokens_crc": crc_b,
        },
        "speculation": {
            "tokens_per_s_plain": round(rep_d.tokens_per_s, 1),
            "tokens_per_s_spec": round(rep_e.tokens_per_s, 1),
            "uplift_x": round(uplift, 3),
            "acceptance": round(rep_e.spec_acceptance, 4),
            "accepted": rep_e.spec_accepted,
            "rejected": rep_e.spec_rejected,
            "decode_steps_plain": rep_d.decode_steps,
            "decode_steps_spec": rep_e.decode_steps,
            "decode_trace_tokens_crc": crc_e,
            "combined_tokens_crc": crc_c,
        },
        "reference_tokens_crc": crc_a,
        "kernel_32k": {
            "single_softmax_scratch_mib":
                round(m_old["scratch_vmem_bytes"] / 2 ** 20, 2),
            "single_softmax_feasible": m_old["feasible"],
            "split_pages_per_split": pps_auto,
            "split_n_splits": m_new["n_splits"],
            "split_scratch_mib":
                round(m_new["scratch_vmem_bytes"] / 2 ** 20, 2),
            "split_modeled_latency_ms":
                round(m_new["latency_s"] * 1e3, 4),
            "kv_roofline_ms": round(ideal_s * 1e3, 4),
        },
        "int4": {
            "max_err": round(float(err4.max()), 6),
            "max_bound": round(float(bound4.max()), 6),
            "two_bit_violates": violated,
        },
        "compose": {
            "kill_completed": rep_kill.completed,
            "kill_failovers": rep_kill.failovers,
            "transient_completed": rep_tr.completed,
            "overload_shed": shed_n,
            "swap_census": census_swap,
            "decomposed_finished": fin_kill,
        },
        "gates": gates,
    }
    return result


SCENARIO = registry.register(registry.Scenario(
    name="serving-throughput",
    artifact="SERVING_THROUGHPUT_r01.json",
    build=build,
    description="prefix caching, speculation at 70% acceptance, the "
                "32k split-K kernel accounting, int4 bound, and the "
                "four reliability drills with prefix+spec on",
    model={"net": "gpt_tiny", "max_position_embeddings": 128},
    parallelism={"engines": 2},
    trace={"kind": "poisson + shared-system-prompt",
           "spec_acceptance": 0.7},
    gates=("prefix_kv_bytes_per_request_2x", "prefix_token_crc_equal",
           "spec_token_crc_equal", "spec_decode_trace_crc_equal",
           "spec_tokens_per_s_uplift_1p5x",
           "spec_acceptance_at_setpoint", "doctors_surface_economics",
           "kernel_32k_single_softmax_infeasible",
           "kernel_32k_split_feasible_near_roofline",
           "kernel_split_matches_mirror",
           "kernel_split_allclose_vs_global", "int4_bound_holds",
           "int4_bound_nonvacuous", "compose_kill_token_for_token",
           "compose_kill_decomposition_exact",
           "compose_transient_token_invisible",
           "compose_transient_ledger_closes",
           "compose_transient_decomposition_exact",
           "compose_overload_sheds_lowest_only",
           "compose_overload_decomposition_exact",
           "compose_hot_swap_zero_dropped_census",
           "compose_hot_swap_decomposition_exact",),
    streams={"metrics": "BENCH_SERVING_THROUGHPUT_METRICS_DIR",
             "traces": "BENCH_SERVING_THROUGHPUT_TRACE_DIR"},
))
