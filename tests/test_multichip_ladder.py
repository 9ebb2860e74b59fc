"""The 256-chip ladder (ISSUE 15): hierarchical ICI/DCN collectives,
interleaved-VPP schedules, DCN-aware (alpha+beta) bucket sizing,
collective-matmul overlap, the perf_doctor ici/dcn exposed-comm split,
and the modeled kill-and-rescale drill pricing."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle2_tpu.distributed as dist
from paddle2_tpu.distributed import mesh as mesh_mod
from paddle2_tpu.distributed.bucket import (
    DEFAULT_BUCKET_MB, bucketed_hierarchical_pmean, link_bucket_bytes,
    plan_buckets, plan_buckets_for_link, _plan)
from paddle2_tpu.distributed.collective import (hierarchical_pmean,
                                                hierarchical_psum)
from paddle2_tpu.distributed.spec_layout import SpecLayout
from paddle2_tpu.observability.cost_model import (
    DEFAULT_DCN_GBPS, DEFAULT_DCN_LATENCY_US, DEFAULT_ICI_GBPS,
    DEFAULT_ICI_LATENCY_US, CollectiveTraffic, LinkModel,
    pipeline_bubble_fraction, wire_bytes)


# shard_map with the varying-manual-axes check off
from paddle2_tpu.distributed.collective import (  # noqa: E402
    shard_map_unchecked as _sm)


# ----------------------------------------------------- alpha+beta links
class TestLinkModelAlphaBeta:
    def test_latency_defaults_zero_keeps_legacy_seconds(self):
        # pre-ladder artifacts are priced by pure bandwidth — the alpha
        # term must default OFF so they stay bitwise unchanged
        lm = LinkModel(ici_gbps=90.0, dcn_gbps=12.5)
        assert lm.latency(("mp",)) == 0.0
        assert lm.latency(("dp_dcn",)) == 0.0
        assert lm.seconds(90e9, ("mp",)) == 1.0

    def test_alpha_plus_beta(self):
        lm = LinkModel(ici_gbps=90.0, dcn_gbps=12.5,
                       ici_latency_us=1.0, dcn_latency_us=250.0)
        assert lm.seconds(12.5e9, ("dp_dcn",)) == \
            pytest.approx(1.0 + 250e-6)
        assert lm.seconds(90e9, ("mp",)) == pytest.approx(1.0 + 1e-6)
        # zero bytes -> zero (a no-op dispatch prices as nothing)
        assert lm.seconds(0.0, ("dp_dcn",)) == 0.0

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("PADDLE_DCN_LATENCY_US", "123.0")
        lm = LinkModel(ici_gbps=90.0, dcn_gbps=12.5)
        assert lm.dcn_latency_s == pytest.approx(123e-6)

    def test_link_class_slowest_hop_wins(self):
        lm = LinkModel()
        assert lm.link_class(("mp", "pp")) == "ici"
        assert lm.link_class(("sharding", "dp_dcn")) == "dcn"
        assert lm.link_class(()) == "ici"


class TestOverlapSplitAlpha:
    def _traffic(self):
        t = CollectiveTraffic()
        t.add("all_reduce_sum", 1e9, axes=("dp_dcn",), group_size=8,
              overlappable=True)
        t.add("all_reduce_sum", 1e9, axes=("dp_dcn",), group_size=8)
        t.add("all_gather", 1e9, axes=("mp",), group_size=4,
              overlappable=True)
        return t

    def test_alpha_always_exposed(self):
        # the bandwidth term of an overlappable dispatch hides under
        # compute; its setup latency cannot — that is what makes bucket
        # COUNT a real cost on latency-dominated links
        lm = LinkModel(ici_gbps=90.0, dcn_gbps=12.5,
                       ici_latency_us=1.0, dcn_latency_us=250.0)
        sp = self._traffic().overlap_split(lm, compute_s=1e9)
        # huge compute budget: everything hideable hides, alphas stay
        assert sp["hidden_s"] == pytest.approx(sp["hideable_s"])
        assert sp["exposed_s"] >= 250e-6 + 1e-6

    def test_serial_identity_exact(self):
        lm = LinkModel(ici_gbps=90.0, dcn_gbps=12.5,
                       ici_latency_us=1.0, dcn_latency_us=250.0)
        for budget in (0.0, 0.01, 1e9):
            sp = self._traffic().overlap_split(lm, compute_s=budget)
            assert sp["serial_s"] == pytest.approx(
                sp["hidden_s"] + sp["exposed_s"], rel=1e-12)

    def test_by_class_sums_to_aggregate(self):
        lm = LinkModel(ici_gbps=90.0, dcn_gbps=12.5,
                       ici_latency_us=1.0, dcn_latency_us=250.0)
        t = self._traffic()
        for budget in (0.0, 0.01, 1e9):
            sp = t.overlap_split(lm, compute_s=budget)
            cls = t.overlap_split_by_class(lm, compute_s=budget)
            for key in ("serial_s", "hideable_s", "hidden_s",
                        "exposed_s"):
                assert cls["ici"][key] + cls["dcn"][key] == \
                    pytest.approx(sp[key], rel=1e-9, abs=1e-15)

    def test_hierarchical_all_reduce_entries(self):
        t = CollectiveTraffic()
        t.add_hierarchical_all_reduce(
            1e9, ici_axes=("sharding",), dcn_axes=("dp_dcn",),
            ici_group=4, dcn_group=8)
        ops = [e["op"] for e in t.entries]
        assert ops == ["reduce_scatter", "all_reduce_sum", "all_gather"]
        # the DCN hop carries only the 1/ici_group partial
        assert t.entries[1]["payload_bytes"] == pytest.approx(0.25e9)
        assert t.entries[1]["wire_bytes"] == pytest.approx(
            wire_bytes("all_reduce_sum", 0.25e9, 8))
        # hierarchical beats the flat all-reduce under a slow DCN
        lm = LinkModel(ici_gbps=90.0, dcn_gbps=12.5)
        flat = CollectiveTraffic()
        flat.add("all_reduce_sum", 1e9, axes=("sharding", "dp_dcn"),
                 group_size=32)
        assert t.seconds(lm) < flat.seconds(lm)


def test_pipeline_bubble_fraction():
    assert pipeline_bubble_fraction(8, 16) == pytest.approx(7 / 16)
    assert pipeline_bubble_fraction(8, 16, 4) == pytest.approx(7 / 64)
    assert pipeline_bubble_fraction(1, 16, 4) == 0.0
    with pytest.raises(ValueError):
        pipeline_bubble_fraction(8, 0)
    with pytest.raises(ValueError):
        pipeline_bubble_fraction(8, 16, 0)


# ------------------------------------------- DCN-aware bucket planning
class TestDcnBucketSizing:
    def _link(self):
        return LinkModel(
            ici_gbps=DEFAULT_ICI_GBPS, dcn_gbps=DEFAULT_DCN_GBPS,
            ici_latency_us=DEFAULT_ICI_LATENCY_US,
            dcn_latency_us=DEFAULT_DCN_LATENCY_US,
            dcn_axes=("dp",))

    def test_dcn_target_strictly_larger(self):
        lm = self._link()
        ici = link_bucket_bytes(lm, ("sharding",))
        dcn = link_bucket_bytes(lm, ("dp",))
        assert ici == DEFAULT_BUCKET_MB * 1e6       # floored at base
        assert dcn > ici                            # latency-dominated

    def test_target_formula(self):
        lm = self._link()
        # alpha <= f * (alpha + B/bw)  =>  B >= alpha * bw * (1-f)/f
        expect = 250e-6 * 12.5e9 * 0.9 / 0.1
        assert link_bucket_bytes(lm, ("dp",)) == pytest.approx(
            max(DEFAULT_BUCKET_MB * 1e6, expect))

    def test_latency_fraction_validated(self):
        lm = self._link()
        for bad in (0.0, 1.0, -0.5, 2.0):
            with pytest.raises(ValueError):
                link_bucket_bytes(lm, ("dp",), latency_fraction=bad)

    def test_plan_for_link_matches_manual(self):
        lm = self._link()
        avals = [((1 << 20,), np.float32) for _ in range(64)]
        assert plan_buckets_for_link(avals, lm, ("dp",)) == \
            plan_buckets(avals, link_bucket_bytes(lm, ("dp",)))

    def test_dcn_scale_per_dtype_tail_accounting(self):
        # DCN-scale sizes: 512 interleaved 4 MB f32 / 2 MB bf16 leaves
        # at the 28 MB DCN target — exactly ONE open tail bucket per
        # dtype, every index exactly once
        lm = self._link()
        avals = []
        for _ in range(256):
            avals.append(((1 << 20,), np.float32))   # 4 MB
            avals.append(((1 << 20,), jnp.bfloat16))  # 2 MB
        target = link_bucket_bytes(lm, ("dp",))
        plan, tail = _plan([(s, d) for s, d in avals], target)
        assert tail == 2
        flat = sorted(i for b in plan for i in b)
        assert flat == list(range(len(avals)))
        for b in plan:
            assert len({str(np.dtype(avals[i][1])) for i in b}) == 1

    def test_plan_pure_function_of_order(self):
        lm = self._link()
        # large enough to split into several buckets at the DCN target
        avals = [((i % 7 + 1, 1 << 20), np.float32) for i in range(64)]
        p1 = plan_buckets_for_link(avals, lm, ("dp",))
        p2 = plan_buckets_for_link(list(avals), lm, ("dp",))
        assert p1 == p2                              # deterministic
        assert len(p1) > 1
        reordered = list(reversed(avals))
        p3 = plan_buckets_for_link(reordered, lm, ("dp",))
        assert p3 != p1                              # order is input


# ------------------------------------------------ hierarchical psum/pmean
class TestHierarchicalCollectives:
    def setup_method(self, method):
        self.mesh = dist.init_mesh({"dp_dcn": 2, "dp_ici": 4})

    def teardown_method(self, method):
        dist.init_mesh({"dp": 8})

    def _run(self, f, x):
        from jax.sharding import PartitionSpec as P
        return np.asarray(
            jax.jit(_sm(f, self.mesh, (P(),), P()))(x))

    def test_int_payload_bitwise_vs_flat(self):
        # exact-arithmetic payload: any summation order is exact, so a
        # bitwise mismatch is a schedule bug, not rounding
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randint(-64, 64, (37, 19)).astype(np.float32))
        flat = self._run(lambda v: jax.lax.psum(v, ("dp_dcn", "dp_ici")),
                         x)
        hier = self._run(
            lambda v: hierarchical_psum(v, "dp_ici", "dp_dcn"), x)
        assert np.array_equal(flat, hier)

    def test_float_payload_one_ulp(self):
        # arbitrary floats reassociate (per-slice partials first) —
        # agreement to ~1 ulp, the caveat every tree all-reduce carries
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.randn(33, 7).astype(np.float32))
        flat = self._run(lambda v: jax.lax.psum(v, ("dp_dcn", "dp_ici")),
                         x)
        hier = self._run(
            lambda v: hierarchical_psum(v, "dp_ici", "dp_dcn"), x)
        np.testing.assert_allclose(flat, hier, rtol=2e-7, atol=0.0)

    def test_pmean_divides_by_combined_degree(self):
        x = jnp.full((8,), 8.0, jnp.float32)
        out = self._run(
            lambda v: hierarchical_pmean(v, ("dp_ici",), ("dp_dcn",)), x)
        np.testing.assert_array_equal(out, np.full((8,), 8.0))

    def test_degenerate_axes(self):
        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.randint(-9, 9, (11,)).astype(np.float32))
        flat = self._run(lambda v: jax.lax.psum(v, ("dp_dcn", "dp_ici")),
                         x)
        only = self._run(
            lambda v: hierarchical_psum(v, (), ("dp_dcn", "dp_ici")), x)
        assert np.array_equal(flat, only)
        ident = self._run(lambda v: hierarchical_psum(v, (), ()), x)
        assert np.array_equal(ident, np.asarray(x))

    def test_caller_constructed_mesh_not_installed(self):
        # the mean divisor and pad count must come from the axes BOUND
        # IN THE TRACE: a Mesh built by hand (never routed through
        # dist.init_mesh) once silently returned the SUM instead of
        # the mean
        from jax.sharding import Mesh, PartitionSpec as P
        dist.init_mesh({"dp": 8})        # installed mesh lacks the axes
        mesh = Mesh(np.array(jax.devices()).reshape(2, 4),
                    ("my_dcn", "my_ici"))
        x = jnp.ones((8,), jnp.float32)
        out = np.asarray(jax.jit(_sm(
            lambda v: hierarchical_pmean(v, "my_ici", "my_dcn"),
            mesh, (P(),), P()))(x))
        np.testing.assert_array_equal(out, np.ones((8,)))

    def test_bucketed_tree_bitwise_on_ints(self):
        from jax.sharding import PartitionSpec as P
        rs = np.random.RandomState(3)
        tree = {"w": jnp.asarray(
                    rs.randint(-64, 64, (13, 5)).astype(np.float32)),
                "b": jnp.asarray(
                    rs.randint(-64, 64, (7,)).astype(np.float32))}
        spec = jax.tree_util.tree_map(lambda _: P(), tree)
        flat = jax.jit(_sm(
            lambda t: jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, ("dp_dcn", "dp_ici")), t),
            self.mesh, (spec,), spec))(tree)
        hier = jax.jit(_sm(
            lambda t: bucketed_hierarchical_pmean(
                t, "dp_ici", "dp_dcn", 128.0),
            self.mesh, (spec,), spec))(tree)
        for a, b in zip(jax.tree_util.tree_leaves(flat),
                        jax.tree_util.tree_leaves(hier)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------- interleaved VPP
class TestInterleavedVPP:
    def _model(self, n_virtual):
        rs = np.random.RandomState(7)
        W = jnp.asarray(rs.randn(n_virtual, 12, 12).astype(np.float32)
                        * 0.3)
        b = jnp.asarray(rs.randn(n_virtual, 12).astype(np.float32)
                        * 0.1)
        x = jnp.asarray(rs.randn(8, 4, 12).astype(np.float32))
        y = jnp.asarray(rs.randn(8, 4, 12).astype(np.float32))

        def stage_fn(p, shared, xx, sidx):
            Wl, bl = p
            return jnp.tanh(xx @ Wl + bl)

        def loss_fn(out, lab):
            return ((out - lab) ** 2).mean()
        return (W, b), x, y, stage_fn, loss_fn

    def test_v2_and_v4_bitwise_vs_v1(self):
        from paddle2_tpu.distributed.fleet import pipeline_spmd_1f1b
        params, x, y, stage_fn, loss_fn = self._model(8)
        dist.init_mesh({"pp": 8})
        l1, g1 = pipeline_spmd_1f1b(stage_fn, params, x, y, loss_fn)
        for v, mesh_axes in ((2, {"pp": 4, "dp": 2}),
                             (4, {"pp": 2, "dp": 4})):
            dist.init_mesh(mesh_axes)
            lv, gv = pipeline_spmd_1f1b(stage_fn, params, x, y, loss_fn,
                                        virtual_stages=v)
            assert np.float32(l1) == np.float32(lv)
            for a, b in zip(g1, gv):
                assert np.array_equal(np.asarray(a), np.asarray(b))
        dist.init_mesh({"dp": 8})

    def test_vpp_composes_with_dp_and_buckets(self):
        from paddle2_tpu.distributed.fleet import pipeline_spmd_1f1b
        params, x, y, stage_fn, loss_fn = self._model(4)
        dist.init_mesh({"pp": 4, "dp": 2})
        l1, g1 = pipeline_spmd_1f1b(stage_fn, params, x, y, loss_fn,
                                    dp_axis="dp")
        dist.init_mesh({"pp": 2, "dp": 2, "mp": 2})
        l2, g2 = pipeline_spmd_1f1b(stage_fn, params, x, y, loss_fn,
                                    dp_axis="dp", virtual_stages=2,
                                    grad_bucket_bytes=256.0)
        assert np.float32(l1) == np.float32(l2)
        for a, b in zip(g1, g2):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        dist.init_mesh({"dp": 8})

    def test_validation(self):
        from jax.sharding import PartitionSpec as P
        from paddle2_tpu.distributed.fleet import pipeline_spmd_1f1b
        params, x, y, stage_fn, loss_fn = self._model(8)
        dist.init_mesh({"pp": 4, "dp": 2})
        try:
            with pytest.raises(ValueError, match="virtual_stages"):
                pipeline_spmd_1f1b(stage_fn, params, x, y, loss_fn,
                                   virtual_stages=0)
            # leading axis must be v * S
            with pytest.raises(ValueError, match="leading axis"):
                pipeline_spmd_1f1b(stage_fn, params, x, y, loss_fn,
                                   virtual_stages=3)
            specs = jax.tree_util.tree_map(
                lambda a: P("pp", *([None] * (a.ndim - 1))), params)
            with pytest.raises(NotImplementedError, match="param_specs"):
                pipeline_spmd_1f1b(stage_fn, params, x, y, loss_fn,
                                   virtual_stages=2, param_specs=specs)
        finally:
            dist.init_mesh({"dp": 8})


# ------------------------------------------------- collective matmul
class TestCollectiveMatmul:
    def setup_method(self, method):
        self.mesh = dist.init_mesh({"mp": 4, "dp": 2})
        rs = np.random.RandomState(11)
        self.x = jnp.asarray(rs.randn(32, 24).astype(np.float32))
        self.w = jnp.asarray(rs.randn(24, 16).astype(np.float32))
        self.w_wide = jnp.asarray(rs.randn(24, 32).astype(np.float32))

    def teardown_method(self, method):
        dist.init_mesh({"dp": 8})

    def test_input_allgather_form_bitwise(self):
        from jax.sharding import PartitionSpec as P
        from paddle2_tpu.kernels.pallas_matmul import allgather_matmul
        unfused = jax.jit(_sm(
            lambda xs, w: jax.lax.all_gather(
                xs, "mp", axis=0, tiled=True) @ w,
            self.mesh, (P("mp"), P()), P()))(self.x, self.w)
        fused = jax.jit(_sm(
            lambda xs, w: allgather_matmul(xs, w, "mp"),
            self.mesh, (P("mp"), P()), P()))(self.x, self.w)
        assert np.array_equal(np.asarray(unfused), np.asarray(fused))

    def test_epilogue_form_bitwise_all_tilings(self):
        from jax.sharding import PartitionSpec as P
        from paddle2_tpu.kernels.pallas_matmul import matmul_allgather
        unfused = jax.jit(_sm(
            lambda x, ws: jax.lax.all_gather(
                x @ ws, "mp", axis=1, tiled=True),
            self.mesh, (P(), P(None, "mp")), P()))(self.x, self.w_wide)
        # tiles down to 2-wide; a 1-wide column tile changes the XLA
        # CPU dot's reduction grouping ~1 ulp (the PR 9 "gemm row
        # count" effect) — the fused path keeps tiles moderate
        for tiles in (1, 2, 4):
            fused = jax.jit(_sm(
                lambda x, ws, t=tiles: matmul_allgather(
                    x, ws, "mp", tiles=t),
                self.mesh, (P(), P(None, "mp")), P()))(
                    self.x, self.w_wide)
            assert np.array_equal(np.asarray(unfused),
                                  np.asarray(fused)), tiles

    def test_quantized_chunk_dot_composes(self):
        # the PR 10 weight-only path slots in as the per-chunk dot —
        # quantized collective matmul, bitwise vs its unfused twin
        from jax.sharding import PartitionSpec as P
        from paddle2_tpu.kernels.pallas_matmul import (
            allgather_matmul, int8_weight_only_matmul,
            quantize_channelwise)
        wq, sc = quantize_channelwise(self.w)
        unfused = jax.jit(_sm(
            lambda xs: int8_weight_only_matmul(
                jax.lax.all_gather(xs, "mp", axis=0, tiled=True),
                wq, sc),
            self.mesh, (P("mp"),), P()))(self.x)
        fused = jax.jit(_sm(
            lambda xs: allgather_matmul(
                xs, self.w, "mp",
                matmul_fn=lambda c, _w: int8_weight_only_matmul(
                    c, wq, sc)),
            self.mesh, (P("mp"),), P()))(self.x)
        assert np.array_equal(np.asarray(unfused), np.asarray(fused))

    def test_tp1_degenerates_to_plain_dot(self):
        from paddle2_tpu.kernels.pallas_matmul import allgather_matmul
        out = allgather_matmul(self.x, self.w, "unused", axis_size=1)
        assert np.array_equal(np.asarray(out),
                              np.asarray(self.x @ self.w))

    def test_tiles_must_divide(self):
        from paddle2_tpu.kernels.pallas_matmul import matmul_allgather
        with pytest.raises(ValueError, match="tiles"):
            matmul_allgather(self.x, self.w, "mp", axis_size=1, tiles=5)

    def test_traffic_priced_overlappable(self):
        from paddle2_tpu.kernels.pallas_matmul import (
            collective_matmul_traffic)
        t = collective_matmul_traffic(1e8, tp=4, axes=("mp",))
        assert len(t.entries) == 1
        e = t.entries[0]
        assert e["overlappable"] and e["op"] == "all_gather"
        assert e["wire_bytes"] == pytest.approx(
            wire_bytes("all_gather", 1e8, 4))
        # the fused schedule hides under an ample compute budget where
        # the unfused (non-overlappable) gather stays exposed
        lm = LinkModel(ici_gbps=90.0, dcn_gbps=12.5)
        assert t.overlap_split(lm, 1.0)["exposed_s"] == 0.0
        unfused = CollectiveTraffic()
        unfused.add("all_gather", 1e8, axes=("mp",), group_size=4)
        assert unfused.overlap_split(lm, 1.0)["exposed_s"] > 0.0


# --------------------------------------- perf_doctor ici/dcn split
class TestPerfDoctorLinkSplit:
    def _write(self, d, ici_s, dcn_s, total=0.1):
        os.makedirs(d, exist_ok=True)
        rec = {"type": "step", "rank": 0, "total_s": total,
               "compute_s": total - ici_s - dcn_s, "input_wait_s": 0.0,
               "host_s": 0.0, "collective_s": ici_s + dcn_s,
               "exposed_comm_s": ici_s + dcn_s,
               "exposed_comm_ici_s": ici_s,
               "exposed_comm_dcn_s": dcn_s}
        with open(os.path.join(d, "metrics_rank_0.jsonl"), "w") as f:
            for s in range(4):
                f.write(json.dumps(dict(rec, step=s)) + "\n")

    def test_summary_and_aggregate_split(self, tmp_path):
        from paddle2_tpu.tools import perf_doctor
        d = str(tmp_path / "s")
        self._write(d, ici_s=0.01, dcn_s=0.03)
        rep = perf_doctor.summarize(perf_doctor.load_streams(d))
        e = rep["per_rank"][0]
        assert e["exposed_comm_ici_pct"] == pytest.approx(10.0)
        assert e["exposed_comm_dcn_pct"] == pytest.approx(30.0)
        agg = rep["aggregate"]
        assert agg["exposed_comm_ici_pct"] == pytest.approx(10.0)
        assert agg["exposed_comm_dcn_pct"] == pytest.approx(30.0)
        text = perf_doctor.format_summary(rep, d)
        assert "ici" in text and "dcn" in text

    def test_aggregate_gated_on_every_rank(self, tmp_path):
        # one rank without the split lane -> no aggregate class figure
        # (same rule as the modeled/MFU lanes)
        from paddle2_tpu.tools import perf_doctor
        d = str(tmp_path / "mixed")
        self._write(d, ici_s=0.01, dcn_s=0.03)
        rec = {"type": "step", "rank": 1, "total_s": 0.1,
               "compute_s": 0.1, "input_wait_s": 0.0, "host_s": 0.0,
               "collective_s": 0.0}
        with open(os.path.join(d, "metrics_rank_1.jsonl"), "w") as f:
            for s in range(4):
                f.write(json.dumps(dict(rec, step=s)) + "\n")
        rep = perf_doctor.summarize(perf_doctor.load_streams(d))
        assert "exposed_comm_ici_pct" not in rep["aggregate"]

    def test_diff_names_dcn_regression(self, tmp_path):
        from paddle2_tpu.tools import perf_doctor
        base_d = str(tmp_path / "base")
        cand_d = str(tmp_path / "cand")
        self._write(base_d, ici_s=0.005, dcn_s=0.002)
        self._write(cand_d, ici_s=0.005, dcn_s=0.04)
        base = perf_doctor.summarize(perf_doctor.load_streams(base_d))
        cand = perf_doctor.summarize(perf_doctor.load_streams(cand_d))
        d = perf_doctor.diff(base, cand)
        assert d["exposed_comm_pct"]["dcn"]["new"] > \
            d["exposed_comm_pct"]["dcn"]["base"]
        text = perf_doctor.format_diff(d)
        assert "DCN OVERLAP REGRESSION" in text
        assert "ICI" not in text.replace("OVERLAP", "")  # ici did not

    def test_identical_streams_diff_zero(self, tmp_path):
        from paddle2_tpu.tools import perf_doctor
        a = str(tmp_path / "a")
        b = str(tmp_path / "b")
        self._write(a, ici_s=0.01, dcn_s=0.02)
        self._write(b, ici_s=0.01, dcn_s=0.02)
        ra = perf_doctor.summarize(perf_doctor.load_streams(a))
        rb = perf_doctor.summarize(perf_doctor.load_streams(b))
        d = perf_doctor.diff(ra, rb)
        assert d["total_delta_pct"] == pytest.approx(0.0)
        assert not d["regressed"]
        assert "OVERLAP REGRESSION" not in perf_doctor.format_diff(d)


def test_spec_layout_split_link_classes():
    layout = SpecLayout()
    ici, dcn = layout.split_link_classes(("mp", "dp", "sharding"))
    assert ici == ("mp", "sharding")
    assert dcn == ("dp",)


# ----------------------------------------------------- bench smoke
@pytest.mark.slow
def test_bench_multichip_scaling_smoke(tmp_path):
    """The full lane passes and its 256 artifact is byte-identical
    across two runs (the CI cmp gate)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    art_a = str(tmp_path / "a.json")
    art_b = str(tmp_path / "b.json")
    outs = []
    for art in (art_a, art_b):
        env["BENCH_MULTICHIP_ARTIFACT"] = art
        env["BENCH_MULTICHIP_METRICS_DIR"] = str(
            tmp_path / ("m_" + os.path.basename(art)))
        p = subprocess.run(
            [sys.executable, "bench.py", "--multichip-scaling"],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    assert outs[0]["ok"] and outs[0]["value"] >= 0.90
    assert outs[0]["ladder_256"]["efficiency_8_to_256_flat"] < 0.90
    with open(art_a, "rb") as fa, open(art_b, "rb") as fb:
        assert fa.read() == fb.read()
