"""ISSUE 10 — single-chip raw speed: cost-model remat policy search,
int8/fp8 Pallas matmul paths, fused optimizer step, and the
perf_doctor MFU/roofline lane.

Everything here is deterministic: bitwise comparisons, analytic error
bounds, and cost-model accounting — no wall-clock assertions (gVisor
wall clocks are noise; see ROADMAP gating note).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle2_tpu as paddle
import paddle2_tpu.distributed as dist
import paddle2_tpu.nn as nn
import paddle2_tpu.optimizer as opt
from paddle2_tpu.distributed.fault_tolerance import chaos
from paddle2_tpu.incubate import autotune
from paddle2_tpu.kernels import pallas_fused as pf
from paddle2_tpu.kernels import pallas_matmul as pm
from paddle2_tpu.models import GPTForCausalLM
from paddle2_tpu.models.gpt import gpt_tiny

V5E = dict(peak_flops=197e12, hbm_bps=819e9)


def _search(budget_gb, **over):
    kw = dict(hidden=1024, num_layers=24, num_heads=16, seq=1024,
              batch=8, budget_bytes=budget_gb * 1e9,
              fixed_bytes=336.6e6 * 16, **V5E)
    kw.update(over)
    return autotune.search_remat_policy(**kw)


# ===================================================================
class TestRematSearch:
    def test_big_budget_saves_everything(self):
        plan = _search(16.0)
        assert plan.policy == "save_all"
        assert plan.granularity is None and not plan.use_recompute
        assert plan.fits and plan.overhead_s == 0.0

    def test_budget_ladder_is_monotonic(self):
        """Tighter budgets walk down the candidate ladder in
        overhead order: save_all -> dots_plus_ln -> dots_plus ->
        dots -> save_nothing."""
        chosen = [_search(gb).policy
                  for gb in (16.0, 12.4, 11.8, 10.5, 7.0)]
        assert chosen == ["save_all", "save_dots_plus_ln",
                          "save_dots_plus", "save_dots",
                          "save_nothing"]

    def test_nothing_fits_flags_and_falls_back_minimal(self):
        plan = _search(1.0)
        assert plan.policy == "save_nothing"
        assert not plan.fits          # surfaced, not hidden
        assert plan.total_bytes > plan.budget_bytes

    def test_deterministic_across_calls(self):
        a, b = _search(10.5), _search(10.5)
        assert a.policy == b.policy
        assert a.table == b.table

    def test_offload_candidate_wins_on_fast_link(self):
        """With an (absurdly) fast host link and a budget only the
        minimal-HBM candidates fit, offload beats full recompute."""
        plan = _search(7.0, offload_gbps=1e6)
        assert plan.policy == "offload_dots"
        assert plan.granularity == "offload"

    def test_offload_never_chosen_when_not_wired(self):
        plan = _search(7.0, offload_gbps=1e6, allow_offload=False)
        assert plan.policy == "save_nothing"

    def test_cache_token_distinguishes_policies(self):
        assert _search(16.0).cache_token() != _search(7.0).cache_token()

    def test_fits_accounting_includes_fixed_bytes(self):
        free = _search(16.0, fixed_bytes=0.0)
        assert free.total_bytes < _search(16.0).total_bytes

    def test_table_rows_carry_full_accounting(self):
        plan = _search(16.0)
        names = {r["policy"] for r in plan.table}
        assert {"save_all", "save_dots_plus_ln", "save_dots_plus",
                "save_dots", "save_nothing", "offload_dots"} <= names
        for r in plan.table:
            assert r["total_bytes"] > 0
            assert r["overhead_s"] >= 0.0


# ===================================================================
def _train_gpt(gran, budget_gb=None, steps=3, seed=0, use_scan=True,
               arm=None, reliability=None, zero=False, k=1):
    paddle.seed(seed)
    cfg = gpt_tiny(use_recompute=gran is not None,
                   recompute_granularity=gran or "full",
                   remat_budget_gb=budget_gb, use_scan=use_scan)
    m = GPTForCausalLM(cfg)
    o = opt.Adam(learning_rate=1e-3, parameters=m.parameters())
    if zero:
        dist.init_mesh()
        _, o, _ = dist.group_sharded_parallel(m, o, "p_g_os",
                                              prefetch=True)
    if k > 1:
        o = dist.shard_optimizer(o, gradient_accumulation_steps=k)
    step = paddle.jit.train_step(
        lambda ids, lab: m(ids, labels=lab)[1], o, layers=[m],
        reliability=reliability)
    if arm:
        chaos.arm(arm)
    rs = np.random.RandomState(7)
    for _ in range(steps):
        ids = paddle.to_tensor(
            rs.randint(0, 128, (2, 16)).astype(np.int32))
        step(ids, ids)
    if reliability:
        step.finalize()
    chaos.disarm()
    return m, step


def _weights(m):
    return [np.asarray(p._data).copy() for p in m.parameters()]


_BUDGET_MEMO = {}


def _tiny_budget_for(policy: str) -> float:
    """Budget (GB) that makes the tiny-geometry search resolve to
    ``policy``, read off the model's own plan table."""
    if policy not in _BUDGET_MEMO:
        paddle.seed(0)
        probe = GPTForCausalLM(gpt_tiny(
            use_recompute=True, recompute_granularity="search",
            remat_budget_gb=1000.0))
        plan = probe.gpt.remat_plan(2, 16)
        _BUDGET_MEMO[policy] = next(
            r["total_bytes"] for r in plan.table
            if r["policy"] == policy) / 1e9
    return _BUDGET_MEMO[policy]


class TestRematWiring:
    """The compile-heavy end-to-end wiring drills are slow-marked
    (tier-1 budget): CI still executes the searched-vs-explicit
    bitwise gate on every push through the single-chip-speed-smoke
    job (`bench.py --single-chip-speed`,
    gates["remat_search_bitwise_vs_explicit"])."""

    @pytest.mark.slow
    def test_searched_policy_bitwise_vs_explicit(self):
        budget = _tiny_budget_for("save_dots")
        m_s, step_s = _train_gpt("search", budget_gb=budget)
        plan = m_s.gpt.remat_plan(2, 16)
        assert plan.policy == "save_dots"
        # _prepare_remat resolves BEFORE the cache key is computed:
        # no duplicate compile under a pre-resolution key
        assert step_s.program_cache_size == 1
        m_e, _ = _train_gpt(plan.granularity)
        assert all(np.array_equal(a, b)
                   for a, b in zip(_weights(m_s), _weights(m_e)))

    @pytest.mark.slow
    def test_save_all_resolution_bitwise_vs_no_recompute(self):
        m_s, step_s = _train_gpt("search", budget_gb=1000.0)
        assert m_s.gpt.remat_plan(2, 16).policy == "save_all"
        assert step_s.program_cache_size == 1
        m_e, _ = _train_gpt(None)
        assert all(np.array_equal(a, b)
                   for a, b in zip(_weights(m_s), _weights(m_e)))

    def test_resolution_is_per_shape(self):
        budget = _tiny_budget_for("save_dots")
        paddle.seed(0)
        m = GPTForCausalLM(gpt_tiny(
            use_recompute=True, recompute_granularity="search",
            remat_budget_gb=budget))
        p_small = m.gpt.remat_plan(2, 16)
        p_big = m.gpt.remat_plan(8, 64)    # 16x the activations
        assert p_big.activation_bytes > p_small.activation_bytes
        # a bigger shape can only move DOWN the ladder
        order = ["save_all", "save_dots_plus_ln", "save_dots_plus",
                 "save_dots", "offload_dots", "save_nothing"]
        assert order.index(p_big.policy) >= order.index(p_small.policy)

    @pytest.mark.slow
    def test_alternating_shapes_one_entry_per_shape(self):
        """Regression (review finding): the cache token must be THIS
        shape's, not the last-resolved one — alternating batch shapes
        must compile once per shape, not once per alternation."""
        paddle.seed(0)
        m = GPTForCausalLM(gpt_tiny(
            use_recompute=True, recompute_granularity="search",
            remat_budget_gb=1000.0, use_scan=True))
        o = opt.Adam(learning_rate=1e-3, parameters=m.parameters())
        step = paddle.jit.train_step(
            lambda ids, lab: m(ids, labels=lab)[1], o, layers=[m])
        rs = np.random.RandomState(7)

        def run(b, s):
            ids = paddle.to_tensor(
                rs.randint(0, 128, (b, s)).astype(np.int32))
            step(ids, ids)
        run(2, 16)
        run(4, 16)
        run(2, 16)     # back to the first shape: must hit, not rebuild
        run(4, 16)
        assert step.program_cache_size == 2

    @pytest.mark.slow
    def test_nonscan_fallback_applies_policy(self):
        """use_scan=False routes through distributed.recompute with
        the resolved policy= and still trains."""
        m1, _ = _train_gpt("dots", use_scan=False, steps=2)
        assert all(np.isfinite(w).all() for w in _weights(m1))

    def test_recompute_policy_arg_resolves_names(self):
        from paddle2_tpu.distributed.recompute import resolve_policy
        assert resolve_policy(None) is None
        assert resolve_policy("full") is None
        assert callable(resolve_policy("dots"))
        assert callable(resolve_policy("dots_plus_ln"))
        fn = lambda *a: True
        assert resolve_policy(fn) is fn


class TestRematComposition:
    """Satellite: searched policy x ZeRO-3 prefetch x reliability
    builder x k=4 gradient accumulation stays bitwise vs the
    unsearched baseline on fault-free AND replayed-step sequences.
    Slow-marked like the repo's other full-stack drills (three
    ZeRO+reliability+accumulation train_step builds)."""

    def _run(self, gran, budget=None, arm=None):
        return _train_gpt(gran, budget_gb=budget, steps=8, arm=arm,
                          reliability=True, zero=True, k=4)

    @pytest.mark.slow
    def test_composed_fault_free_and_replayed_bitwise(self):
        """Three composed runs (each: searched remat x ZeRO-3 prefetch
        x reliability builder x k=4 accumulation): clean searched,
        faulted searched (poison_loss mid-accumulation-cycle), faulted
        EXPLICIT-policy. The faulted searched run must detect, rewind,
        replay — and land bitwise on its own clean run (recovery is
        faithful) AND on the faulted unsearched baseline (the searched
        policy is a pure schedule choice under the whole stack)."""
        budget = _tiny_budget_for("save_dots")
        m_sc, _ = self._run("search", budget=budget)
        assert m_sc.gpt.remat_plan(2, 16).policy == "save_dots"
        m_sf, step_sf = self._run("search", budget=budget,
                                  arm="poison_loss:5")
        m_ef, step_ef = self._run("dots", arm="poison_loss:5")
        assert step_sf.stats["retries"] == 1
        assert step_ef.stats["retries"] == 1
        w_sc, w_sf, w_ef = (_weights(m) for m in (m_sc, m_sf, m_ef))
        assert all(np.array_equal(a, b) for a, b in zip(w_sf, w_sc))
        assert all(np.array_equal(a, b) for a, b in zip(w_sf, w_ef))


# ===================================================================
class TestInt8Matmul:
    def _setup(self, m=64, k=512, n=256, seed=0):
        rs = np.random.RandomState(seed)
        x = jnp.asarray(rs.randn(m, k), jnp.float32)
        w = jnp.asarray(rs.randn(k, n), jnp.float32)
        w_i8, scale = pm.quantize_channelwise(w, 8, axis=1)
        return x, w, w_i8, scale

    def test_error_within_analytic_bound(self):
        x, w, w_i8, scale = self._setup()
        x64 = np.asarray(x, np.float64)
        w64 = np.asarray(w, np.float64)
        deq = np.asarray(w_i8, np.float64) * (
            np.asarray(scale, np.float64) / 127.0)
        err = np.abs(x64 @ w64 - x64 @ deq)
        bound = np.asarray(pm.weight_quant_error_bound(x, scale),
                           np.float64)
        assert (err <= bound + 1e-9).all()

    def test_bound_nonvacuous(self):
        """An 8-bit bound must catch a payload quantized at 4 bits —
        and must be tighter than the trivial |y| bound."""
        x, w, _, scale = self._setup()
        w_i4, s4 = pm.quantize_channelwise(w, 4, axis=1)
        x64 = np.asarray(x, np.float64)
        w64 = np.asarray(w, np.float64)
        deq4 = np.asarray(w_i4, np.float64) * (
            np.asarray(s4, np.float64) / 7.0)
        bound = np.asarray(pm.weight_quant_error_bound(x, scale),
                           np.float64)
        assert (np.abs(x64 @ w64 - x64 @ deq4) > bound).any()
        assert bound.max() < np.abs(x64 @ w64).max()

    def test_pallas_kernel_matches_xla_dequant(self):
        x, w, w_i8, scale = self._setup()
        y_xla = pm.int8_weight_only_matmul(x, w_i8, scale)
        y_pal = pm.int8_weight_only_matmul(
            x, w_i8, scale, block_m=32, block_n=128, block_k=128,
            interpret=True)
        np.testing.assert_allclose(np.asarray(y_pal),
                                   np.asarray(y_xla),
                                   rtol=2e-5, atol=2e-4)

    def test_pallas_kernel_multi_k_steps_accumulate(self):
        x, w, w_i8, scale = self._setup(m=32, k=512, n=128)
        y_pal = pm.int8_weight_only_matmul(
            x, w_i8, scale, block_m=32, block_n=128, block_k=128,
            interpret=True)            # 4 K-steps through the scratch
        deq = np.asarray(w_i8, np.float64) * (
            np.asarray(scale, np.float64) / 127.0)
        ref = (np.asarray(x, np.float64) @ deq).astype(np.float32)
        np.testing.assert_allclose(np.asarray(y_pal), ref,
                                   rtol=2e-5, atol=2e-4)

    def test_bias_and_lead_shape(self):
        x, w, w_i8, scale = self._setup()
        bias = jnp.asarray(np.random.RandomState(1).randn(256),
                           jnp.float32)
        y = pm.int8_weight_only_matmul(
            x.reshape(4, 16, 512), w_i8, scale, bias=bias)
        assert y.shape == (4, 16, 256)
        flat = pm.int8_weight_only_matmul(x, w_i8, scale, bias=bias)
        np.testing.assert_array_equal(np.asarray(y).reshape(64, 256),
                                      np.asarray(flat))

    def test_bias_folds_before_cast_on_both_lowerings(self):
        """Regression (review finding): with bf16 activations the
        bias must fold into the f32 epilogue BEFORE the output cast on
        the Pallas path too, so TPU and the XLA fallback round
        identically."""
        rs = np.random.RandomState(9)
        x = jnp.asarray(rs.randn(32, 128), jnp.bfloat16)
        w = jnp.asarray(rs.randn(128, 128), jnp.float32)
        bias = jnp.asarray(rs.randn(128) * 1e-3, jnp.float32)
        w_i8, scale = pm.quantize_channelwise(w, 8, axis=1)
        y_xla = pm.int8_weight_only_matmul(x, w_i8, scale, bias=bias)
        y_pal = pm.int8_weight_only_matmul(
            x, w_i8, scale, bias=bias, block_m=32, block_n=128,
            block_k=128, interpret=True)
        assert y_pal.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(y_pal, np.float32),
                                      np.asarray(y_xla, np.float32))

    def test_int8_int8_int32_accumulation(self):
        rs = np.random.RandomState(2)
        a = jnp.asarray(rs.randint(-127, 128, (32, 256)), jnp.int8)
        b = jnp.asarray(rs.randint(-127, 128, (256, 128)), jnp.int8)
        ref = np.asarray(a, np.int32) @ np.asarray(b, np.int32)
        y_xla = pm.int8_matmul(a, b)
        assert y_xla.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(y_xla), ref)
        y_pal = pm.int8_matmul(a, b, block_m=32, block_n=128,
                               block_k=128, interpret=True)
        np.testing.assert_array_equal(np.asarray(y_pal), ref)

    def test_ragged_shapes_fall_back(self):
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(7, 130), jnp.float32)   # nothing aligns
        w = jnp.asarray(rs.randn(130, 33), jnp.float32)
        w_i8, scale = pm.quantize_channelwise(w, 8, axis=1)
        y = pm.int8_weight_only_matmul(x, w_i8, scale)
        assert y.shape == (7, 33)

    def test_channel_absmax_shared_primitive(self):
        """The observers and the kernels must reduce through ONE
        function — same axis convention, same dtype."""
        from paddle2_tpu.quantization import (ChannelWiseAbsMaxObserver,
                                              channel_absmax)
        rs = np.random.RandomState(6)
        w = jnp.asarray(rs.randn(32, 16), jnp.float32)
        obs = ChannelWiseAbsMaxObserver(quant_axis=1, channels=16)
        obs(paddle.to_tensor(np.asarray(w)))
        np.testing.assert_array_equal(
            np.asarray(obs.raw_scale()),
            np.asarray(channel_absmax(w, axis=1)))


# ===================================================================
class TestFusedOptimizerStep:
    def _loop(self, o_factory, steps=4, seed=0):
        paddle.seed(seed)
        m = nn.Sequential(nn.Linear(16, 33), nn.Tanh(),
                          nn.Linear(33, 16))
        o = o_factory(m)
        rs = np.random.RandomState(seed)
        for _ in range(steps):
            x = paddle.to_tensor(rs.randn(8, 16).astype(np.float32))
            y = paddle.to_tensor(rs.randn(8, 16).astype(np.float32))
            loss = ((m(x) - y) ** 2).mean()
            loss.backward()
            o.step()
            o.clear_grad()
        states = [np.asarray(leaf).copy() for p in m.parameters()
                  for leaf in jax.tree_util.tree_leaves(
                      o._states[id(p)])]
        return [np.asarray(p._data).copy()
                for p in m.parameters()], states

    def _assert_bitwise(self, mk):
        pe, se = self._loop(lambda m: mk(m, False))
        pf_, sf = self._loop(lambda m: mk(m, True))
        assert all(np.array_equal(a, b) for a, b in zip(pe, pf_))
        assert all(np.array_equal(a, b) for a, b in zip(se, sf))

    def test_adamw_f32_bitwise(self):
        self._assert_bitwise(lambda m, fused: opt.AdamW(
            learning_rate=1e-2, parameters=m.parameters(),
            weight_decay=0.01, fused=fused))

    def test_adamw_no_decay_bitwise(self):
        self._assert_bitwise(lambda m, fused: opt.AdamW(
            learning_rate=1e-2, parameters=m.parameters(),
            weight_decay=0.0, fused=fused))

    def test_adamw_grad_clip_bitwise(self):
        self._assert_bitwise(lambda m, fused: opt.AdamW(
            learning_rate=1e-2, parameters=m.parameters(),
            grad_clip=nn.ClipGradByGlobalNorm(0.5), fused=fused))

    def test_momentum_nesterov_bitwise(self):
        self._assert_bitwise(lambda m, fused: opt.Momentum(
            learning_rate=1e-2, momentum=0.9, use_nesterov=True,
            parameters=m.parameters(), weight_decay=0.01,
            fused=fused))

    def test_momentum_plain_bitwise(self):
        self._assert_bitwise(lambda m, fused: opt.Momentum(
            learning_rate=1e-2, momentum=0.9,
            parameters=m.parameters(), fused=fused))

    def test_amsgrad_falls_back_and_matches(self):
        """Unsupported configs silently serve the generic chain —
        fused=True must never change numerics."""
        self._assert_bitwise(lambda m, fused: opt.AdamW(
            learning_rate=1e-2, parameters=m.parameters(),
            amsgrad=True, fused=fused))

    def test_flag_enables_fused(self):
        from paddle2_tpu import flags
        try:
            flags.set_flags({"fused_optimizer_step": True})
            pe, se = self._loop(lambda m: opt.AdamW(
                learning_rate=1e-2, parameters=m.parameters(),
                fused=False))      # explicit ctor kwarg wins over flag
            flags.set_flags({"fused_optimizer_step": False})
            pf_, sf = self._loop(lambda m: opt.AdamW(
                learning_rate=1e-2, parameters=m.parameters()))
            assert all(np.array_equal(a, b) for a, b in zip(pe, pf_))
        finally:
            flags.set_flags({"fused_optimizer_step": False})

    def test_kernel_inplace_aliases_declared(self):
        """The one-pass contract: param and both moments alias their
        outputs (no staging copies)."""
        lr = jnp.float32(1e-2)
        step = jnp.int32(3)
        rs = np.random.RandomState(0)
        p = jnp.asarray(rs.randn(300), jnp.float32)
        g = jnp.asarray(rs.randn(300), jnp.float32)
        m = jnp.asarray(rs.rand(300), jnp.float32)
        v = jnp.asarray(rs.rand(300), jnp.float32)
        # eager twin FIRST: the kernel declares in-place aliases, so
        # its inputs are donated — reading p/m/v after the call is
        # exactly the use-after-donate the aliasing exists to enable
        b1, b2, eps = 0.9, 0.999, 1e-8

        # JITTED twin: op-by-op eager dispatch rounds differently than
        # a compiled chain on the CPU backend (FMA contraction) — the
        # bitwise contract is between COMPILED paths
        @jax.jit
        def twin(p, g, m, v, lr, step):
            t = step.astype(jnp.float32)
            em = b1 * m + (1 - b1) * g
            ev = b2 * v + (1 - b2) * jnp.square(g)
            mhat = em / (1 - b1 ** t)
            vhat = ev / (1 - b2 ** t)
            ep = p - lr * mhat / (jnp.sqrt(vhat) + eps)
            return ep - lr * 0.01 * p, em, ev
        ep, em, ev = (np.asarray(a).copy()
                      for a in twin(p, g, m, v, lr, step))
        np_, nm, nv = pf.fused_adamw_step(p, g, m, v, lr, step,
                                          weight_decay=0.01)
        np.testing.assert_array_equal(np.asarray(np_), np.asarray(ep))
        np.testing.assert_array_equal(np.asarray(nm), np.asarray(em))
        np.testing.assert_array_equal(np.asarray(nv), np.asarray(ev))


# ===================================================================
class TestPerfDoctorMFULane:
    def _write(self, d, mfu_triple=True, scale=1.0, rank=0):
        os.makedirs(d, exist_ok=True)
        rec = {"type": "step", "rank": rank, "total_s": 0.1,
               "compute_s": 0.1, "input_wait_s": 0.0,
               "collective_s": 0.0, "host_s": 0.0, "tokens": 8192,
               "modeled_step_s": 0.1 * scale}
        if mfu_triple:
            rec.update(modeled_flops=19e12, roofline_s=0.1 * scale,
                       peak_flops=197e12)
        with open(os.path.join(d, f"metrics_rank_{rank}.jsonl"),
                  "w") as f:
            for s in range(5):
                f.write(json.dumps(dict(rec, step=s)) + "\n")

    def test_mfu_lane_rendered(self, tmp_path):
        from paddle2_tpu.tools import perf_doctor
        d = str(tmp_path / "a")
        self._write(d)
        rep = perf_doctor.summarize(perf_doctor.load_streams(d))
        mfu = rep["per_rank"][0]["mfu_modeled"]
        assert abs(mfu - 19e12 / (0.1 * 197e12)) < 1e-12
        assert "MFU" in perf_doctor.format_summary(rep, d)

    def test_aggregate_needs_every_rank(self, tmp_path):
        from paddle2_tpu.tools import perf_doctor
        d = str(tmp_path / "b")
        self._write(d, rank=0)
        self._write(d, rank=1, mfu_triple=False)
        rep = perf_doctor.summarize(perf_doctor.load_streams(d))
        assert "mfu_modeled" in rep["per_rank"][0]
        assert "mfu_modeled" not in rep["per_rank"][1]
        assert "mfu_modeled" not in rep["aggregate"]

    def test_mfu_regression_fails_diff(self, tmp_path):
        from paddle2_tpu.tools import perf_doctor
        a, b = str(tmp_path / "base"), str(tmp_path / "cand")
        self._write(a)
        self._write(b, scale=1.5)     # slower roofline -> lower MFU
        d = perf_doctor.diff(
            perf_doctor.summarize(perf_doctor.load_streams(a)),
            perf_doctor.summarize(perf_doctor.load_streams(b)))
        assert d["mfu_modeled"]["regressed"]
        assert d["regressed"]
        assert "MFU REGRESSION" in perf_doctor.format_diff(d)

    def test_identical_streams_zero_and_ok(self, tmp_path):
        from paddle2_tpu.tools import perf_doctor
        a, b = str(tmp_path / "x"), str(tmp_path / "y")
        self._write(a)
        self._write(b)
        d = perf_doctor.diff(
            perf_doctor.summarize(perf_doctor.load_streams(a)),
            perf_doctor.summarize(perf_doctor.load_streams(b)))
        assert d["total_delta_pct"] == 0.0 and not d["regressed"]
        assert not d["mfu_modeled"]["regressed"]

    def test_one_sided_lane_incomparable(self, tmp_path):
        from paddle2_tpu.tools import perf_doctor
        a, b = str(tmp_path / "p"), str(tmp_path / "q")
        self._write(a)
        self._write(b, mfu_triple=False)
        d = perf_doctor.diff(
            perf_doctor.summarize(perf_doctor.load_streams(a)),
            perf_doctor.summarize(perf_doctor.load_streams(b)))
        assert not d["mfu_modeled"]["comparable"]
        assert not d["mfu_modeled"]["regressed"]


# ===================================================================
class TestAutotuneDeterministic:
    def test_model_mode_default_on_cpu(self, monkeypatch):
        monkeypatch.delenv(autotune.AUTOTUNE_MODE_ENV, raising=False)
        assert autotune.autotune_mode() == "model"

    def test_env_forces_measure(self, monkeypatch):
        monkeypatch.setenv(autotune.AUTOTUNE_MODE_ENV, "measure")
        assert autotune.autotune_mode() == "measure"

    def test_model_mode_reproducible(self, monkeypatch):
        monkeypatch.setenv(autotune.AUTOTUNE_MODE_ENV, "model")
        autotune._block_cache.clear()
        q = (2, 2048, 8, 64)
        a = autotune.best_flash_blocks(q, q, True, (512, 1024))
        autotune._block_cache.clear()
        b = autotune.best_flash_blocks(q, q, True, (512, 1024))
        assert a == b

    def test_model_mode_never_dispatches(self, monkeypatch):
        """Deterministic scoring must not touch the device: poison
        the kernel entry point and score anyway."""
        import paddle2_tpu.kernels.pallas_flash as pflash
        monkeypatch.setenv(autotune.AUTOTUNE_MODE_ENV, "model")
        autotune._block_cache.clear()

        def boom(*a, **k):
            raise AssertionError("model mode must not run kernels")
        monkeypatch.setattr(pflash, "flash_attention_bshd", boom)
        q = (2, 4096, 8, 64)
        assert autotune.best_flash_blocks(q, q, False, (512, 1024))
        autotune._block_cache.clear()

    def test_seeded_tie_break_stable(self, monkeypatch):
        monkeypatch.setenv(autotune.AUTOTUNE_SEED_ENV, "42")
        r1 = autotune._tie_rng().randint(100)
        r2 = autotune._tie_rng().randint(100)
        assert r1 == r2
        monkeypatch.setenv(autotune.AUTOTUNE_SEED_ENV, "43")
        # a different seed is a different (but still stable) stream
        assert autotune._tie_rng().randint(100) == \
            autotune._tie_rng().randint(100)


# ===================================================================
@pytest.mark.slow
def test_bench_single_chip_speed_smoke():
    """The full gate, end to end (CI runs it as its own job too)."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench.py", "--single-chip-speed"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"]
    assert out["value"] >= 0.10
