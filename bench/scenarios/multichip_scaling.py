"""Scenario: the ``--multichip-scaling`` hybrid-parallel scaling gate.
The stdout JSON line carries every gate; the artifact
(``MULTICHIP_256_r01.json``) is the 256-chip ladder alone, which the
build writes itself (``indent=1``, sorted keys) to
``$BENCH_MULTICHIP_ARTIFACT`` or the default directory.
"""

import json
import os

from ..artifact import artifact_path as default_artifact_path
from ..artifact import bench_scratch, log, write_artifact

from . import registry


def build(scenario):
    """Pod-scale hybrid-parallel scaling gate (BASELINE config 4: GPT-3
    1.3B, tp+pp, 32 chips) — cost x rate, ZERO wall-clock A/B.

    Three layers of evidence, all deterministic:

    1. **Bitwise parity** (executed on the 8-virtual-device CPU mesh):
       the comm-efficiency paths must be pure schedule shapes —
       bucketed dp grad reduction == per-leaf reduction, and ZeRO-3
       layer-ahead prefetch == eager gather-all, bit for bit.
    2. **Modeled 32-chip scaling efficiency** (cost x rate): the full
       GPT-1.3B tp=2 x pp=4 geometry's per-chip FLOPs + per-collective
       wire bytes (tp activation all-reduces on ICI, pp microbatch
       p2p, bucketed dp grad reduce on DCN) under the observability
       LinkModel + overlap split. Efficiency 8->32 chips =
       modeled_step(8) / modeled_step(32), gated >= 85%. The same
       model WITHOUT bucketing (one monolithic exposed grad reduce)
       must fail the gate — bucketing+overlap is load-bearing, not
       decorative.
    3. **exposed-comm %** via perf_doctor: the bucketed stream's
       exposed-comm share must DROP vs the unbucketed baseline, read
       back through the same CLI CI uses, so overlap regressions are
       attributable.
    4. **The 256-chip ladder** (BASELINE config 5: ERNIE-3.0-XL-class
       ZeRO-3 across DCN slices, 8 -> 32 -> 64 -> 128 -> 256):
       executed bitwise/1-ulp parities for the four ladder levers
       (hierarchical ICI/DCN collectives, interleaved-VPP v>1 vs v=1,
       DCN-aware bucket sizing, collective-matmul fused vs unfused),
       then the cost x rate ladder itself — modeled 8->256 efficiency
       gated >= 0.90 with the FLAT configuration (flat collectives,
       v=1, monolithic grad reduce, exposed tp gather) required to
       FAIL the same gate and every lever required to be individually
       load-bearing. Composes the reliability plane at scale: a
       modeled 256-chip kill-and-rescale drill (detect -> quarantine
       -> re-form -> buddy fetch -> warm-cache compile -> replay, all
       priced through the cost model) gating recovery cost SUBLINEAR
       in world size. Emits the byte-identical MULTICHIP_256_r01.json
       artifact plus ici/dcn-split perf_doctor streams.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np_
    import paddle2_tpu as paddle
    import paddle2_tpu.nn as nn
    import paddle2_tpu.optimizer as opt
    import paddle2_tpu.distributed as dist
    from paddle2_tpu.distributed.bucket import (BucketPlan, bucketed_pmean,
                                                plan_buckets)
    from paddle2_tpu.distributed.spec_layout import SpecLayout
    from paddle2_tpu.observability.cost_model import (
        DEFAULT_DCN_GBPS, DEFAULT_ICI_GBPS, CollectiveTraffic, LinkModel,
        StepCost)

    gates = {}
    info = {}

    # ---- 1a. bucketed vs per-leaf dp grad reduction: bitwise (traced,
    # shard_map over the hybrid mesh's dp axis — the exact primitive
    # pipeline_spmd_1f1b(grad_bucket_bytes=) dispatches)
    layout = SpecLayout()
    mesh = dist.init_mesh(layout.mesh_axes(dp=2, pp=2, fsdp=1, tp=2))
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map
    rs = np_.random.RandomState(0)
    # GPT-ish mixed-shape/mixed-dtype grad tree (weights, bias, norm)
    tree = {
        "wqkv": jnp.asarray(rs.randn(64, 192), jnp.float32),
        "wo": jnp.asarray(rs.randn(64, 64), jnp.float32),
        "ffn": [jnp.asarray(rs.randn(64, 256), jnp.float32),
                jnp.asarray(rs.randn(256, 64), jnp.float32)],
        "bias": jnp.asarray(rs.randn(256), jnp.float32),
        "norm": jnp.asarray(rs.randn(64), jnp.bfloat16),
    }

    def per_leaf(t):
        return jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, "dp"), t)

    def bucketed(t):
        return bucketed_pmean(t, "dp", 4096.0)  # tiny -> many buckets

    specs = jax.tree_util.tree_map(lambda _: P(), tree)
    run_pl = jax.jit(shard_map(per_leaf, mesh=mesh, in_specs=(specs,),
                               out_specs=specs))
    run_bk = jax.jit(shard_map(bucketed, mesh=mesh, in_specs=(specs,),
                               out_specs=specs))
    a = jax.tree_util.tree_leaves(run_pl(tree))
    b = jax.tree_util.tree_leaves(run_bk(tree))
    bucketed_bitwise = all(
        np_.array_equal(np_.asarray(x), np_.asarray(y))
        for x, y in zip(a, b))
    gates["bucketed_grads_bitwise"] = bucketed_bitwise
    # dispatch-count story at the DEFAULT bucket size (parity above ran
    # a tiny limit to force the multi-bucket split path): mixed-dtype
    # leaves coalesce to one bucket per dtype
    n_leaves = len(a)
    n_buckets = len(plan_buckets(
        [(tuple(g.shape), g.dtype)
         for g in jax.tree_util.tree_leaves(tree)], 25e6))
    gates["buckets_coalesce_dispatches"] = n_buckets < n_leaves
    info["bucket_dispatches"] = {"per_leaf": n_leaves,
                                 "bucketed_25mb": n_buckets}
    log(f"bucketed-vs-per-leaf pmean: bitwise={bucketed_bitwise} "
        f"({n_leaves} leaves -> {n_buckets} buckets @ 25MB)")

    # ---- 1b. ZeRO-3 prefetch vs eager gather-all: bitwise through the
    # compiled train step (the schedule the 256-chip config runs)
    def run_zero3(prefetch, depth=1):
        dist.init_mesh({"sharding": 8})
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 32), nn.Tanh(),
                            nn.Linear(32, 8))
        o = opt.Adam(learning_rate=1e-2, parameters=net.parameters())
        _, o, _ = dist.group_sharded_parallel(
            net, o, "p_g_os", prefetch=prefetch, prefetch_depth=depth)
        step = paddle.jit.train_step(
            lambda x, y: ((net(x) - y) ** 2).mean(), o, layers=[net])
        rs2 = np_.random.RandomState(1)
        for _ in range(3):
            step(paddle.to_tensor(rs2.randn(16, 8).astype(np_.float32)),
                 paddle.to_tensor(rs2.randn(16, 8).astype(np_.float32)))
        return [np_.asarray(p._data).copy() for p in net.parameters()]

    w_eager = run_zero3(False)
    w_pref = run_zero3(True, depth=1)
    prefetch_bitwise = all(np_.array_equal(x, y)
                           for x, y in zip(w_eager, w_pref))
    gates["zero3_prefetch_bitwise"] = prefetch_bitwise
    log(f"zero3 prefetch-vs-eager: bitwise={prefetch_bitwise}")

    # ---- 2. cost x rate scaling model: GPT-1.3B tp=2 x pp=4 hybrid,
    # 8 -> 32 logical chips (dp 1 -> 4). Rates pinned explicitly so the
    # gate is deterministic on every host.
    H, L, NH, V, T = 2048, 24, 16, 50304, 2048
    TP, PP = 2, 4
    B_REP = 8                       # sequences per dp replica per step
    PEAK, HBM = 197e12, 819e9       # v5e nominal
    BUCKET_MB = float(os.environ.get("BENCH_BUCKET_MB", 25.0))
    # ONE shared pair of wire-rate constants across every lane (and
    # both uses below): duplicated inline literals would silently drift
    # and make efficiencies incomparable between the 32 and 256 lanes
    n_params = V * H + T * H + 12 * L * H * H
    link = layout.link_model(ici_gbps=DEFAULT_ICI_GBPS,
                             dcn_gbps=DEFAULT_DCN_GBPS)

    def hybrid_step_cost(n_chips, bucketed=True):
        dp = n_chips // (TP * PP)
        tokens_rep = B_REP * T
        flops_chip = 6.0 * n_params * tokens_rep / (TP * PP)
        t = CollectiveTraffic()
        # tp: Megatron 2 fwd + 2 bwd activation all-reduces per layer,
        # full [B, T, H] bf16 payload, ICI, critical-path (exposed)
        for _ in range(L):
            for _k in range(4):
                t.add("all_reduce_sum", B_REP * T * H * 2,
                      axes=(layout.tp_axis,), group_size=TP)
        # pp: microbatch activations fwd+bwd, point-to-point, pipelined
        # behind compute (overlappable)
        M = 8
        for _ in range(M):
            t.add("ppermute", (B_REP / M) * T * H * 2 * 2,
                  axes=(layout.pp_axis,), group_size=PP,
                  overlappable=True)
        # dp: grad all-reduce of this chip's param shard (f32), DCN.
        # Bucketed: the deterministic plan, every bucket but the last
        # overlapping the backward still producing later buckets.
        # Unbucketed: one monolithic reduce serialized behind the LAST
        # grad — fully exposed.
        if dp > 1:
            shard_elems = n_params // (TP * PP)
            per_layer = [((shard_elems // L,), np_.float32)
                         for _ in range(L)]
            if bucketed:
                plan = BucketPlan(per_layer, BUCKET_MB * 1e6)
                plan.traffic(op="all_reduce_sum",
                             axes=(layout.data_axis,), group_size=dp,
                             traffic=t)
            else:
                t.add("all_reduce_sum", shard_elems * 4,
                      axes=(layout.data_axis,), group_size=dp)
        return StepCost(flops=flops_chip, hbm_bytes=0.0, traffic=t,
                        link=link, peak_flops=PEAK, hbm_bps=HBM)

    c8 = hybrid_step_cost(8)
    c32 = hybrid_step_cost(32)
    c32_naive = hybrid_step_cost(32, bucketed=False)
    eff = c8.step_time_modeled_s() / c32.step_time_modeled_s()
    eff_naive = c8.step_time_modeled_s() / c32_naive.step_time_modeled_s()
    gates["scaling_efficiency_ge_85pct"] = eff >= 0.85
    # the unbucketed model must FAIL the same gate: the efficiency is
    # bought by bucketing+overlap, not by the link model being generous
    gates["naive_fails_without_overlap"] = eff_naive < 0.85
    log(f"modeled 8->32 efficiency: bucketed {eff:.3f}, "
        f"unbucketed {eff_naive:.3f}")

    # ---- 3. exposed-comm % through perf_doctor (the attribution CI
    # reads): modeled per-step records for both schedules
    import tempfile
    from paddle2_tpu.tools import perf_doctor

    def write_stream(d, cost):
        ov = cost.overlap()
        rec = {"type": "step", "rank": 0, "total_s":
               cost.step_time_modeled_s(),
               "compute_s": cost.compute_s(),
               "collective_s": ov["exposed_s"],
               "input_wait_s": 0.0, "host_s": 0.0,
               "exposed_comm_s": ov["exposed_s"]}
        with open(os.path.join(d, "metrics_rank_0.jsonl"), "w") as f:
            for s in range(6):
                f.write(json.dumps(dict(rec, step=s)) + "\n")

    tmp = tempfile.mkdtemp(prefix="multichip_scaling_")
    d_naive = os.path.join(tmp, "unbucketed")
    d_buck = os.path.join(tmp, "bucketed")
    os.makedirs(d_naive); os.makedirs(d_buck)
    write_stream(d_naive, c32_naive)
    write_stream(d_buck, c32)
    rep_naive = perf_doctor.summarize(perf_doctor.load_streams(d_naive))
    rep_buck = perf_doctor.summarize(perf_doctor.load_streams(d_buck))
    pct_naive = rep_naive["per_rank"][0]["exposed_comm_pct"]
    pct_buck = rep_buck["per_rank"][0]["exposed_comm_pct"]
    gates["exposed_comm_drops"] = pct_buck < pct_naive
    gates["perf_doctor_reports_exposed_comm"] = (
        "exposed-comm" in perf_doctor.format_summary(rep_buck, d_buck))
    log(f"exposed-comm %: unbucketed {pct_naive:.1f} -> bucketed "
        f"{pct_buck:.1f}")

    # ================== 4. THE 256-CHIP LADDER (BASELINE config 5) =====
    import math
    from paddle2_tpu.distributed.bucket import (
        DEFAULT_BUCKET_MB, bucketed_hierarchical_pmean,
        link_bucket_bytes)
    from paddle2_tpu.distributed.collective import (hierarchical_pmean,
                                                    hierarchical_psum)
    from paddle2_tpu.distributed.fleet import pipeline_spmd_1f1b
    from paddle2_tpu.kernels.pallas_matmul import (allgather_matmul,
                                                   matmul_allgather)
    from paddle2_tpu.observability.cost_model import (
        DEFAULT_DCN_LATENCY_US, DEFAULT_ICI_LATENCY_US,
        pipeline_bubble_fraction)

    # the ladder artifact reports exactly the gates THIS section adds
    # (a name-prefix filter once leaked a section-3 gate into it)
    _pre_ladder_gates = set(gates)

    # hierarchical/ring results are replicated in VALUE but typed
    # device-varying — the shared wrapper disables the rep check both
    # jax generations spell differently
    from paddle2_tpu.distributed.collective import (
        shard_map_unchecked as _sm)

    # ---- 4a. hierarchical vs flat collectives, executed on the
    # virtual mesh split 2 DCN slices x 4 ICI chips. The hierarchical
    # schedule REASSOCIATES the additions (per-slice partials first) —
    # identical elements, different tree — so the bitwise gate runs on
    # an integer-valued payload (every association sums exactly: any
    # difference is a schedule bug, not rounding) and random f32 is
    # additionally pinned to 1-ulp agreement, the same two-sided
    # contract PR 13 used for the split-K merge.
    hmesh = dist.init_mesh({"dp_dcn": 2, "dp_ici": 4})
    rs4 = np_.random.RandomState(4)
    x_int = jnp.asarray(
        rs4.randint(-64, 64, size=(37, 19)).astype(np_.float32))
    x_flt = jnp.asarray(rs4.randn(37, 19).astype(np_.float32))

    def _flat_psum(v):
        return jax.lax.psum(v, ("dp_dcn", "dp_ici"))

    def _hier_psum(v):
        return hierarchical_psum(v, "dp_ici", "dp_dcn")

    spec1 = (P(),)
    run_flat = jax.jit(_sm(_flat_psum, hmesh, spec1, P()))
    run_hier = jax.jit(_sm(_hier_psum, hmesh, spec1, P()))
    a_int = np_.asarray(run_flat(x_int))
    h_int = np_.asarray(run_hier(x_int))
    a_flt = np_.asarray(run_flat(x_flt))
    h_flt = np_.asarray(run_hier(x_flt))
    gates["hierarchical_int_bitwise_vs_flat"] = np_.array_equal(a_int,
                                                                h_int)
    gates["hierarchical_float_1ulp_vs_flat"] = bool(
        np_.allclose(a_flt, h_flt, rtol=2e-7, atol=0.0))
    # bucketed tree form: fused flat payloads over the same schedule
    tree4 = {"w": x_int, "b": jnp.asarray(
        rs4.randint(-64, 64, size=(23,)).astype(np_.float32))}
    tspec = jax.tree_util.tree_map(lambda _: P(), tree4)

    def _flat_tree(t):
        return jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, ("dp_dcn", "dp_ici")), t)

    def _hier_tree(t):
        return bucketed_hierarchical_pmean(t, "dp_ici", "dp_dcn", 512.0)

    bt_flat = jax.tree_util.tree_leaves(
        jax.jit(_sm(_flat_tree, hmesh, (tspec,), tspec))(tree4))
    bt_hier = jax.tree_util.tree_leaves(
        jax.jit(_sm(_hier_tree, hmesh, (tspec,), tspec))(tree4))
    gates["hierarchical_bucketed_int_bitwise"] = all(
        np_.array_equal(np_.asarray(p), np_.asarray(q))
        for p, q in zip(bt_flat, bt_hier))
    log(f"hierarchical vs flat: int bitwise="
        f"{gates['hierarchical_int_bitwise_vs_flat']}, float 1-ulp="
        f"{gates['hierarchical_float_1ulp_vs_flat']}, bucketed="
        f"{gates['hierarchical_bucketed_int_bitwise']}")

    # ---- 4b. interleaved-VPP: v>1 vs v=1 of the SAME 8-virtual-stage
    # model, bitwise (the interleaving is a pure schedule shape)
    rs5 = np_.random.RandomState(5)
    PV, BV, DV, MV = 8, 4, 16, 8
    Wp = jnp.asarray(rs5.randn(PV, DV, DV).astype(np_.float32) * 0.3)
    bp = jnp.asarray(rs5.randn(PV, DV).astype(np_.float32) * 0.1)
    xp = jnp.asarray(rs5.randn(MV, BV, DV).astype(np_.float32))
    yp = jnp.asarray(rs5.randn(MV, BV, DV).astype(np_.float32))

    def _stage(pv, shared, xx, sidx):
        Wl, bl = pv
        return jnp.tanh(xx @ Wl + bl)

    def _sloss(out, lab):
        return ((out - lab) ** 2).mean()

    dist.init_mesh({"pp": 8})
    l_v1, g_v1 = pipeline_spmd_1f1b(_stage, (Wp, bp), xp, yp, _sloss)
    dist.init_mesh({"pp": 4, "dp": 2})
    l_v2, g_v2 = pipeline_spmd_1f1b(_stage, (Wp, bp), xp, yp, _sloss,
                                    virtual_stages=2)
    gates["vpp_v2_bitwise_vs_v1"] = (
        np_.float32(l_v1) == np_.float32(l_v2)
        and all(np_.array_equal(np_.asarray(p), np_.asarray(q))
                for p, q in zip(g_v1, g_v2)))
    # composed with dp + bucketed grad reduce (the ladder's actual
    # schedule shape): v=2 x dp=2 vs v=1 x dp=2, bitwise
    dist.init_mesh({"pp": 4, "dp": 2})
    l_d1, g_d1 = pipeline_spmd_1f1b(_stage, (Wp[:4], bp[:4]), xp, yp,
                                    _sloss, dp_axis="dp")
    dist.init_mesh({"pp": 2, "dp": 2, "mp": 2})
    l_d2, g_d2 = pipeline_spmd_1f1b(_stage, (Wp[:4], bp[:4]), xp, yp,
                                    _sloss, dp_axis="dp",
                                    virtual_stages=2,
                                    grad_bucket_bytes=512.0)
    gates["vpp_dp_bucketed_bitwise"] = (
        np_.float32(l_d1) == np_.float32(l_d2)
        and all(np_.array_equal(np_.asarray(p), np_.asarray(q))
                for p, q in zip(g_d1, g_d2)))
    log(f"interleaved-VPP: v2-vs-v1 bitwise="
        f"{gates['vpp_v2_bitwise_vs_v1']}, dp+buckets composed="
        f"{gates['vpp_dp_bucketed_bitwise']}")

    # ---- 4c. collective matmul: fused vs unfused, bitwise (both the
    # input-gather ring and the epilogue output-gather form)
    cmesh = dist.init_mesh({"mp": 4, "dp": 2})
    rs6 = np_.random.RandomState(6)
    xa = jnp.asarray(rs6.randn(32, 24).astype(np_.float32))
    wa = jnp.asarray(rs6.randn(24, 16).astype(np_.float32))
    wb = jnp.asarray(rs6.randn(24, 32).astype(np_.float32))

    def _ag_unfused(xs, ww):
        return jax.lax.all_gather(xs, "mp", axis=0, tiled=True) @ ww

    def _ag_fused(xs, ww):
        return allgather_matmul(xs, ww, "mp")

    u_in = np_.asarray(jax.jit(_sm(_ag_unfused, cmesh,
                                   (P("mp"), P()), P()))(xa, wa))
    f_in = np_.asarray(jax.jit(_sm(_ag_fused, cmesh,
                                   (P("mp"), P()), P()))(xa, wa))
    gates["collective_matmul_input_bitwise"] = np_.array_equal(u_in,
                                                               f_in)

    def _ep_unfused(xx, ws):
        return jax.lax.all_gather(xx @ ws, "mp", axis=1, tiled=True)

    def _ep_fused(xx, ws):
        return matmul_allgather(xx, ws, "mp", tiles=4)

    u_ep = np_.asarray(jax.jit(_sm(_ep_unfused, cmesh,
                                   (P(), P(None, "mp")), P()))(xa, wb))
    f_ep = np_.asarray(jax.jit(_sm(_ep_fused, cmesh,
                                   (P(), P(None, "mp")), P()))(xa, wb))
    gates["collective_matmul_epilogue_bitwise"] = np_.array_equal(u_ep,
                                                                  f_ep)
    log(f"collective matmul: input-gather bitwise="
        f"{gates['collective_matmul_input_bitwise']}, epilogue bitwise="
        f"{gates['collective_matmul_epilogue_bitwise']}")

    # ---- 4d. DCN-aware bucket sizing: pure deterministic function of
    # (param order, link class); the latency-dominated DCN hop must
    # pick a strictly larger target than ICI under the alpha+beta model
    alink = layout.link_model(
        ici_gbps=DEFAULT_ICI_GBPS, dcn_gbps=DEFAULT_DCN_GBPS,
        ici_latency_us=DEFAULT_ICI_LATENCY_US,
        dcn_latency_us=DEFAULT_DCN_LATENCY_US)
    tgt_ici = link_bucket_bytes(alink, (layout.fsdp_axis,))
    tgt_dcn = link_bucket_bytes(alink, (layout.data_axis,))
    gates["dcn_bucket_target_gt_ici"] = tgt_dcn > tgt_ici
    lad_avals = [((1024, 1024), np_.float32) for _ in range(64)]
    pl_a = plan_buckets(lad_avals, tgt_dcn)
    pl_b = plan_buckets(list(lad_avals), tgt_dcn)
    gates["dcn_plan_deterministic"] = pl_a == pl_b
    info["bucket_targets_mb"] = {"ici": round(tgt_ici / 1e6, 3),
                                 "dcn": round(tgt_dcn / 1e6, 3)}

    # ---- 4e. the modeled ladder itself: ERNIE-3.0-XL-class ZeRO-3
    # across DCN slices. Geometry: tp=2 x pp=4 model-parallel group
    # (constant across rungs so per-chip work is constant — weak
    # scaling), ZeRO-3/fsdp=4 within the 32-chip ICI slice, dp across
    # DCN slices: 8 -> 32 -> 64 -> 128 -> 256 chips.
    H5, L5, V5, T5 = 2560, 32, 50304, 2048
    TP5, PP5, FSDP5 = 2, 4, 4
    M5, VS5 = 16, 4                 # microbatches, virtual stages
    B5 = 16                         # seqs per model-parallel group
    n_params5 = V5 * H5 + T5 * H5 + 12 * L5 * H5 * H5
    grad_bytes5 = n_params5 // (TP5 * PP5) * 4      # f32 grads/chip
    ag_bytes5 = n_params5 // (TP5 * PP5) * 2        # bf16 params/chip
    # the non-DCN-aware baseline bucket: what an ALPHA-BLIND
    # (bandwidth-only, i.e. pre-ladder) cost model prefers. With
    # dispatches free, shrinking buckets strictly improves the model
    # (same total bytes, smaller exposed tail, finer overlap) — so an
    # alpha-blind autotuner walks DOWN from the 25 MB default toward
    # fine-grained buckets; 4 MB stands in for that optimum. The gate
    # below DEMONSTRATES the preference rather than asserting it, so
    # this baseline is an honest alternative, not a strawman.
    ICI_SIZED_BUCKET = 4e6
    fsdp_ax, dcn_ax = layout.fsdp_axis, layout.data_axis

    def ladder_step_cost(n_chips, hierarchical=True, vpp=True,
                         dcn_buckets=True, collective_mm=True,
                         grad_bucket=None, link=None):
        link = link if link is not None else alink
        fsdp = min(FSDP5, n_chips // (TP5 * PP5))
        dcn = n_chips // (TP5 * PP5 * fsdp)
        flops_chip = 6.0 * n_params5 * (B5 * T5) / (TP5 * PP5)
        bubble = pipeline_bubble_fraction(PP5, M5, VS5 if vpp else 1)
        t = CollectiveTraffic()
        # tp activation collectives: Megatron 4 per layer per
        # microbatch, [B_micro, T, H] bf16 — hidden inside MXU time by
        # the collective matmul, on the critical path without it
        tp_payload = (B5 // M5) * T5 * H5 * 2
        for _ in range(M5 * (L5 // PP5) * 4):
            t.add("all_reduce_sum", tp_payload, axes=(layout.tp_axis,),
                  group_size=TP5, overlappable=collective_mm)
        if fsdp > 1:
            # ZeRO-3 param all-gather, one dispatch per layer group per
            # pass (fwd + bwd regather), prefetch-overlapped (PR 8)
            n_ag = 2 * (L5 // PP5)
            for _ in range(n_ag):
                t.add("all_gather", ag_bytes5 / (L5 // PP5),
                      axes=(fsdp_ax,), group_size=fsdp,
                      overlappable=True)
        if fsdp * dcn > 1:
            if hierarchical and dcn > 1:
                # hierarchical grad sync, bucketed: in-slice ICI
                # reduce-scatter, cross-slice DCN all-reduce of the
                # 1/fsdp partials, in-slice all-gather. Bucket size
                # targets the LATENCY-DOMINATED hop: the DCN dispatch
                # carries bucket/fsdp bytes, so the full-tensor bucket
                # is fsdp x the per-link target
                tgt = (grad_bucket if grad_bucket is not None
                       else tgt_dcn if dcn_buckets else ICI_SIZED_BUCKET)
                bucket = tgt * fsdp
                n_b = max(1, math.ceil(grad_bytes5 / bucket))
                for i in range(n_b):
                    b = min(bucket, grad_bytes5 - i * bucket)
                    t.add_hierarchical_all_reduce(
                        b, ici_axes=(fsdp_ax,), dcn_axes=(dcn_ax,),
                        ici_group=fsdp, dcn_group=dcn,
                        overlappable=i < n_b - 1)
            elif dcn == 1:
                # single slice: plain bucketed ZeRO grad reduce on ICI
                tgt = tgt_ici if dcn_buckets else ICI_SIZED_BUCKET
                n_b = max(1, math.ceil(grad_bytes5 / tgt))
                for i in range(n_b):
                    b = min(tgt, grad_bytes5 - i * tgt)
                    t.add("all_reduce_sum", b, axes=(fsdp_ax,),
                          group_size=fsdp, overlappable=i < n_b - 1)
            else:
                # FLAT: the PR 8 machinery as it exists — bucketed,
                # overlap-capable — but reduced over the combined
                # (fsdp x dcn) group, so EVERY byte is charged at the
                # slow DCN hop and every bucket dispatch pays the DCN
                # setup latency (alpha is always exposed). This is the
                # honest non-hierarchical baseline: the hierarchy's
                # win is moving the bulk of the bytes (and dispatches)
                # onto ICI, not the bucketing itself.
                tgt = tgt_dcn if dcn_buckets else ICI_SIZED_BUCKET
                n_b = max(1, math.ceil(grad_bytes5 / tgt))
                for i in range(n_b):
                    b = min(tgt, grad_bytes5 - i * tgt)
                    t.add("all_reduce_sum", b,
                          axes=(fsdp_ax, dcn_ax), group_size=fsdp * dcn,
                          overlappable=i < n_b - 1)
        return StepCost(flops=flops_chip * (1.0 + bubble),
                        hbm_bytes=0.0, traffic=t, link=link,
                        peak_flops=PEAK, hbm_bps=HBM)

    RUNGS = (8, 32, 64, 128, 256)
    base8 = ladder_step_cost(8)
    t8 = base8.step_time_modeled_s()
    ladder_rows = []
    for n_chips in RUNGS:
        c_full = ladder_step_cost(n_chips)
        c_flat = ladder_step_cost(n_chips, hierarchical=False,
                                  vpp=False, dcn_buckets=False,
                                  collective_mm=False)
        by_cls = c_full.exposed_network_by_class()
        ladder_rows.append({
            "chips": n_chips,
            "efficiency": round(t8 / c_full.step_time_modeled_s(), 4),
            "efficiency_flat": round(
                t8 / c_flat.step_time_modeled_s(), 4),
            "modeled_step_ms": round(
                c_full.step_time_modeled_s() * 1e3, 2),
            "modeled_step_flat_ms": round(
                c_flat.step_time_modeled_s() * 1e3, 2),
            "exposed_ici_ms": round(by_cls["ici"] * 1e3, 3),
            "exposed_dcn_ms": round(by_cls["dcn"] * 1e3, 3),
        })
    c256 = ladder_step_cost(256)
    c256_flat = ladder_step_cost(256, hierarchical=False, vpp=False,
                                 dcn_buckets=False, collective_mm=False)
    eff_256 = t8 / c256.step_time_modeled_s()
    eff_256_flat = t8 / c256_flat.step_time_modeled_s()
    # lever attribution: drop ONE lever at a time — each must strictly
    # reduce the 8->256 efficiency (load-bearing, not decorative)
    levers = {}
    for name, kw in (
            ("hierarchical", {"hierarchical": False}),
            ("vpp", {"vpp": False}),
            ("dcn_buckets", {"dcn_buckets": False}),
            ("collective_matmul", {"collective_mm": False})):
        levers[name] = round(
            t8 / ladder_step_cost(256, **kw).step_time_modeled_s(), 4)
    gates["ladder_efficiency_8_to_256_ge_90pct"] = eff_256 >= 0.90
    gates["ladder_flat_fails_gate"] = eff_256_flat < 0.90
    gates["ladder_every_rung_ge_90pct"] = all(
        r["efficiency"] >= 0.90 for r in ladder_rows)
    gates["ladder_every_lever_load_bearing"] = all(
        v < round(eff_256, 4) for v in levers.values())
    # the schedule levers must each individually sink the gate
    gates["ladder_vpp_required"] = levers["vpp"] < 0.90
    gates["ladder_collective_matmul_required"] = (
        levers["collective_matmul"] < 0.90)
    # the hierarchy's specific claim: the slow wire carries a FRACTION
    # of the bytes — serial DCN wire time of the non-hierarchical grad
    # sync must exceed the hierarchical one by at least the in-slice
    # aggregation factor's worth (>= 3x here; the exact ratio rides the
    # wire-factor difference between the two algorithms)
    dcn_serial_hier = c256.traffic.overlap_split_by_class(
        alink, c256.compute_s())["dcn"]["serial_s"]
    c256_nohier = ladder_step_cost(256, hierarchical=False)
    dcn_serial_flat = c256_nohier.traffic.overlap_split_by_class(
        alink, c256_nohier.compute_s())["dcn"]["serial_s"]
    gates["ladder_hierarchical_dcn_wire_reduced_3x"] = (
        dcn_serial_flat >= 3.0 * dcn_serial_hier)
    # the DCN-bucket lever's honesty check: under an ALPHA-BLIND
    # (zero-latency) link model the fine ICI-era bucket is at least as
    # good as the 25 MB default (same bytes, smaller exposed tail) —
    # i.e. a pre-ladder autotuner genuinely prefers the baseline this
    # lever is compared against; only the alpha term makes it lose
    link0 = layout.link_model(ici_gbps=DEFAULT_ICI_GBPS,
                              dcn_gbps=DEFAULT_DCN_GBPS)
    t_fine_blind = ladder_step_cost(
        256, grad_bucket=ICI_SIZED_BUCKET,
        link=link0).step_time_modeled_s()
    t_dflt_blind = ladder_step_cost(
        256, grad_bucket=DEFAULT_BUCKET_MB * 1e6,
        link=link0).step_time_modeled_s()
    gates["alpha_blind_model_prefers_fine_buckets"] = (
        t_fine_blind <= t_dflt_blind)
    log(f"256 ladder: eff_full={eff_256:.4f} eff_flat={eff_256_flat:.4f}"
        f" levers={levers} dcn_serial flat/hier = "
        f"{dcn_serial_flat * 1e3:.1f}/{dcn_serial_hier * 1e3:.1f} ms")

    # ---- 4f. 256-chip kill-and-rescale drill, priced end to end: a
    # chip dies mid-step; detect (PR 5 prober cadence) -> quarantine
    # verdict (PR 5 store) -> gang re-formation gossip (log2 fan-in) ->
    # buddy-replica shard fetch over DCN (PR 4 ladder; ckpt reshard
    # narrowing is the fallback) -> warm-cache recompile (PR 6 measured
    # hit) -> one replayed step. Every term is a constant, a log, or a
    # fixed shard transfer — so MTTR grows SUBLINEARLY in world size,
    # which is the gate.
    PROBE_S = 1.0                   # health-prober cadence (PR 5)
    QUARANTINE_S = 0.05             # store write + verdict
    GOSSIP_PER_ROUND_S = 0.1        # rendezvous fan-in per log2 round
    COMPILE_HIT_S = 0.29            # PR 6 measured warm-cache restart
    shard_bytes = 3 * 4 * n_params5 // (TP5 * PP5 * FSDP5)

    def rescale_drill(n_chips):
        fetch_s = alink.seconds(shard_bytes, (dcn_ax,))
        replay_s = ladder_step_cost(n_chips).step_time_modeled_s()
        comp = {
            "detect_s": PROBE_S,
            "quarantine_s": QUARANTINE_S,
            "rendezvous_s": GOSSIP_PER_ROUND_S * math.log2(n_chips),
            "replica_fetch_s": round(fetch_s, 4),
            "compile_s": COMPILE_HIT_S,
            "replay_step_s": round(replay_s, 4),
        }
        comp["mttr_s"] = round(sum(comp.values()), 4)
        return comp

    drills = {n: rescale_drill(n) for n in (32, 64, 128, 256)}
    mttr_ratios = [drills[b]["mttr_s"] / drills[a]["mttr_s"]
                   for a, b in ((32, 64), (64, 128), (128, 256))]
    mttr_budget = float(os.environ.get("BENCH_MTTR_BUDGET_S", "60"))
    gates["rescale_mttr_sublinear"] = all(r < 1.25 for r in mttr_ratios)
    gates["rescale_mttr_under_budget"] = (
        drills[256]["mttr_s"] <= mttr_budget)
    log(f"kill-and-rescale: MTTR 32->256 = "
        f"{drills[32]['mttr_s']:.2f}s -> {drills[256]['mttr_s']:.2f}s "
        f"(doubling ratios {[round(r, 3) for r in mttr_ratios]})")

    # ---- 4g. ici/dcn-split perf_doctor streams + byte-identical
    # artifact (what the CI smoke job runs twice, cmps, and diffs)
    def write_ladder_stream(d, cost):
        os.makedirs(d, exist_ok=True)
        ov = cost.overlap()
        cls = cost.exposed_network_by_class()
        rec = {"type": "step", "rank": 0,
               "total_s": cost.step_time_modeled_s(),
               "compute_s": cost.compute_s(),
               "collective_s": ov["exposed_s"],
               "input_wait_s": 0.0, "host_s": 0.0,
               "exposed_comm_s": ov["exposed_s"],
               "exposed_comm_ici_s": cls["ici"],
               "exposed_comm_dcn_s": cls["dcn"]}
        with open(os.path.join(d, "metrics_rank_0.jsonl"), "w") as f:
            for st in range(6):
                f.write(json.dumps(dict(rec, step=st),
                                   sort_keys=True) + "\n")

    lad_dir = bench_scratch("multichip_256",
                            env_var="BENCH_MULTICHIP_METRICS_DIR")
    d_full = os.path.join(lad_dir, "full")
    d_flat = os.path.join(lad_dir, "flat")
    write_ladder_stream(d_full, c256)
    write_ladder_stream(d_flat, c256_flat)
    rep_full = perf_doctor.summarize(perf_doctor.load_streams(d_full))
    rep_flat = perf_doctor.summarize(perf_doctor.load_streams(d_flat))
    agg_full = rep_full["aggregate"]
    agg_flat = rep_flat["aggregate"]
    gates["perf_doctor_splits_ici_dcn"] = (
        "exposed_comm_ici_pct" in agg_full
        and "exposed_comm_dcn_pct" in agg_full)
    gates["flat_dcn_exposure_grows"] = (
        agg_flat.get("exposed_comm_dcn_pct", 0.0)
        > agg_full.get("exposed_comm_dcn_pct", 0.0))
    diff_text = perf_doctor.format_diff(
        perf_doctor.diff(rep_full, rep_flat))
    gates["perf_doctor_names_dcn_regression"] = (
        "DCN" in diff_text and "OVERLAP REGRESSION" in diff_text)
    log(f"perf_doctor split: full ici/dcn = "
        f"{agg_full.get('exposed_comm_ici_pct', 0.0):.2f}%/"
        f"{agg_full.get('exposed_comm_dcn_pct', 0.0):.2f}%, flat dcn = "
        f"{agg_flat.get('exposed_comm_dcn_pct', 0.0):.2f}%")

    ladder_artifact = {
        "config": "BASELINE 5: ERNIE-3.0-XL-class ZeRO-3 across DCN "
                  "slices (tp=2 x pp=4 x fsdp=4 per 32-chip slice, "
                  "dp over DCN)",
        "geometry": {"hidden": H5, "layers": L5, "vocab": V5,
                     "seq": T5, "params_b": round(n_params5 / 1e9, 2),
                     "tp": TP5, "pp": PP5, "fsdp": FSDP5,
                     "microbatches": M5, "virtual_stages": VS5,
                     "seqs_per_replica": B5},
        "rates": {"peak_tflops": PEAK / 1e12,
                  "ici_gbps": DEFAULT_ICI_GBPS,
                  "dcn_gbps": DEFAULT_DCN_GBPS,
                  "ici_latency_us": DEFAULT_ICI_LATENCY_US,
                  "dcn_latency_us": DEFAULT_DCN_LATENCY_US},
        "bucket_targets_mb": info["bucket_targets_mb"],
        "bubble_fraction": {
            "v1": round(pipeline_bubble_fraction(PP5, M5, 1), 4),
            f"v{VS5}": round(
                pipeline_bubble_fraction(PP5, M5, VS5), 4)},
        "ladder": ladder_rows,
        "efficiency_8_to_256": round(eff_256, 4),
        "efficiency_8_to_256_flat": round(eff_256_flat, 4),
        "lever_attribution_eff_256": levers,
        "rescale_drill": drills,
        "mttr_doubling_ratios": [round(r, 4) for r in mttr_ratios],
        "gates": {k: v for k, v in gates.items()
                  if k not in _pre_ladder_gates},
    }
    artifact_path = os.environ.get(
        "BENCH_MULTICHIP_ARTIFACT") or default_artifact_path(
            scenario.artifact)
    write_artifact(artifact_path, ladder_artifact, indent=1,
                   sort_keys=True, trailing_newline=True)
    log(f"ladder artifact -> {artifact_path}")

    ok = all(gates.values())
    return {
        "metric": "multichip_scaling_efficiency_8_to_256",
        "value": round(eff_256, 4),
        "unit": "modeled step-time ratio (cost x rate, zero wall-clock "
                "A/B)",
        "ladder_256": {
            "efficiency_8_to_256": round(eff_256, 4),
            "efficiency_8_to_256_flat": round(eff_256_flat, 4),
            "lever_attribution": levers,
            "mttr_s_256": drills[256]["mttr_s"],
            "artifact": artifact_path,
        },
        "efficiency_8_to_32_config4": round(eff, 4),
        "scaling": {
            "config": "BASELINE 4: GPT-1.3B tp=2 x pp=4, dp 1->4 "
                      "(8->32 logical chips)",
            "efficiency_bucketed": round(eff, 4),
            "efficiency_unbucketed": round(eff_naive, 4),
            "modeled_step_ms": {
                "chips8": round(c8.step_time_modeled_s() * 1e3, 2),
                "chips32": round(c32.step_time_modeled_s() * 1e3, 2),
                "chips32_unbucketed":
                    round(c32_naive.step_time_modeled_s() * 1e3, 2)},
            "exposed_comm_pct": {"unbucketed": round(pct_naive, 1),
                                 "bucketed": round(pct_buck, 1)},
            "per_chip_flops": c8.flops,
            "wire_bytes_per_chip_32": round(
                c32.traffic.wire_bytes_total()),
            "bucket_mb": BUCKET_MB,
            "rates": {"peak_tflops": PEAK / 1e12,
                      "ici_gbps": DEFAULT_ICI_GBPS,
                      "dcn_gbps": DEFAULT_DCN_GBPS,
                      "dcn_axes": list(layout.dcn_axes)},
            "geometry": {"hidden": H, "layers": L, "heads": NH,
                         "vocab": V, "seq": T,
                         "params_b": round(n_params / 1e9, 2)},
        },
        "parity": {"bucketed_grads_bitwise": bucketed_bitwise,
                   "zero3_prefetch_bitwise": prefetch_bitwise,
                   "bucket_dispatches": info["bucket_dispatches"]},
        "gates": gates,
        "ok": ok,
        "note": "parity executed on the 8-virtual-device CPU mesh; "
                "32-chip figures are deterministic cost x rate "
                "(collective bytes x link model) — wall-clock is "
                "unreliable in this sandbox",
    }


SCENARIO = registry.register(registry.Scenario(
    name="multichip-scaling",
    artifact="MULTICHIP_256_r01.json",
    build=build,
    description="executed bucket/ZeRO-3/hierarchical/VPP/collective-"
                "matmul parities on 8 virtual devices + the modeled "
                "8->32 and 8->256 ladders + kill-and-rescale MTTR",
    model={"config4": "GPT-1.3B tp=2 x pp=4",
           "config5": "ERNIE-3.0-XL-class ZeRO-3, tp=2 x pp=4 x fsdp=4 "
                      "per 32-chip slice"},
    parallelism={"virtual_devices": 8, "modeled_chips": [8, 32, 64,
                                                         128, 256]},
    trace={"kind": "modeled", "steps": 6},
    gates=("bucketed_grads_bitwise", "buckets_coalesce_dispatches",
           "zero3_prefetch_bitwise", "scaling_efficiency_ge_85pct",
           "naive_fails_without_overlap", "exposed_comm_drops",
           "perf_doctor_reports_exposed_comm",
           "hierarchical_int_bitwise_vs_flat",
           "hierarchical_float_1ulp_vs_flat",
           "hierarchical_bucketed_int_bitwise", "vpp_v2_bitwise_vs_v1",
           "vpp_dp_bucketed_bitwise",
           "collective_matmul_input_bitwise",
           "collective_matmul_epilogue_bitwise",
           "dcn_bucket_target_gt_ici", "dcn_plan_deterministic",
           "ladder_efficiency_8_to_256_ge_90pct",
           "ladder_flat_fails_gate", "ladder_every_rung_ge_90pct",
           "ladder_every_lever_load_bearing", "ladder_vpp_required",
           "ladder_collective_matmul_required",
           "ladder_hierarchical_dcn_wire_reduced_3x",
           "alpha_blind_model_prefers_fine_buckets",
           "rescale_mttr_sublinear", "rescale_mttr_under_budget",
           "perf_doctor_splits_ici_dcn", "flat_dcn_exposure_grows",
           "perf_doctor_names_dcn_regression",),
    streams={"metrics": "BENCH_MULTICHIP_METRICS_DIR"},
    writes_own_artifact=True,
))
