"""Pod-scale hybrid-parallel comm-efficiency layer: gradient bucketing,
ZeRO-3 prefetch, ICI/DCN spec layout, XLA overlap flags, and the
cost-model overlap accounting (ISSUE 8)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle2_tpu as paddle
import paddle2_tpu.nn as nn
import paddle2_tpu.optimizer as opt
import paddle2_tpu.distributed as dist
from paddle2_tpu.distributed import mesh as mesh_mod
from paddle2_tpu.distributed.bucket import (DEFAULT_BUCKET_MB, BucketPlan,
                                            GradientBucketManager,
                                            bucketed_pmean, bucketed_psum,
                                            plan_buckets)
from paddle2_tpu.distributed.spec_layout import SpecLayout, hybrid_mesh
from paddle2_tpu.observability.cost_model import (CollectiveTraffic,
                                                  LinkModel, StepCost)

W = 8


def _shard_map():
    from jax import shard_map
    return shard_map


# ------------------------------------------------------- bucket planning
class TestPlanBuckets:
    def test_every_index_exactly_once(self):
        avals = [((4, 4), np.float32), ((100,), np.float32),
                 ((3,), np.float16), ((8, 8), np.float32)]
        plan = plan_buckets(avals, 128.0)
        flat = sorted(i for b in plan for i in b)
        assert flat == list(range(len(avals)))

    def test_reverse_order_and_size_target(self):
        # 10 x 100-byte f32 params, 250-byte buckets -> packed from the
        # LAST param backwards, 2 per bucket
        avals = [((25,), np.float32)] * 10
        plan = plan_buckets(avals, 250.0)
        assert plan[0] == [9, 8]
        assert all(len(b) == 2 for b in plan)

    def test_dtype_never_mixes(self):
        avals = [((4,), np.float32), ((4,), np.float16),
                 ((4,), np.float32)]
        plan = plan_buckets(avals, 1e9)
        for b in plan:
            dts = {str(np.dtype(avals[i][1])) for i in b}
            assert len(dts) == 1

    def test_deterministic(self):
        avals = [((i + 1, 7), np.float32) for i in range(20)]
        assert plan_buckets(avals, 1000.0) == plan_buckets(avals, 1000.0)

    def test_oversize_param_gets_own_bucket(self):
        avals = [((4,), np.float32), ((1000,), np.float32),
                 ((4,), np.float32)]
        plan = plan_buckets(avals, 64.0)
        assert [1] in plan

    def test_interleaved_dtypes_coalesce(self):
        # per-layer [f16 weight, f32 norm gain] interleave: one open
        # bucket PER DTYPE keeps coalescing across the transitions —
        # the old close-on-transition rule degenerated to ~one dispatch
        # per param on exactly the mixed-precision models bucketing
        # exists for
        avals = []
        for _ in range(8):
            avals.append(((64,), np.float16))
            avals.append(((4,), np.float32))
        plan = plan_buckets(avals, 1e9)
        assert len(plan) == 2            # one f16 + one f32 bucket
        for b in plan:
            dts = {str(np.dtype(avals[i][1])) for i in b}
            assert len(dts) == 1
        flat = sorted(i for b in plan for i in b)
        assert flat == list(range(len(avals)))

    def test_plan_traffic_marks_all_but_last_overlappable(self):
        plan = BucketPlan([((25,), np.float32)] * 6, 250.0)
        t = plan.traffic(axes=("dp",), group_size=4)
        marks = [e["overlappable"] for e in t.entries]
        assert marks == [True] * (len(plan.buckets) - 1) + [False]
        assert t.payload_bytes_total() == plan.total_nbytes()

    def test_plan_traffic_exposes_one_tail_bucket_per_dtype(self):
        # mixed precision leaves one OPEN bucket per dtype at scan end;
        # all of them hold last-completing grads with nothing left to
        # overlap — modeling any of them as hidden makes the scaling-
        # efficiency gate optimistic
        avals = []
        for _ in range(8):
            avals.append(((64,), np.float16))
            avals.append(((4,), np.float32))
        plan = BucketPlan(avals, 1e9)
        assert len(plan.buckets) == 2 and plan.tail_count == 2
        t = plan.traffic(axes=("dp",), group_size=4)
        assert [e["overlappable"] for e in t.entries] == [False, False]


# ------------------------------------------------- traced bucketed reduce
class TestBucketedReduceTraced:
    @pytest.fixture(autouse=True)
    def _mesh(self):
        dist.init_mesh()  # {"dp": 8}
        yield

    def _tree(self):
        rs = np.random.RandomState(0)
        return {
            "w1": jnp.asarray(rs.randn(16, 24), jnp.float32),
            "w2": [jnp.asarray(rs.randn(24, 8), jnp.float32),
                   jnp.asarray(rs.randn(8), jnp.float32)],
            "n": jnp.asarray(rs.randn(16), jnp.bfloat16),
        }

    @pytest.mark.parametrize("red", ["pmean", "psum"])
    def test_bitwise_vs_per_leaf(self, red):
        from jax.sharding import PartitionSpec as P
        tree = self._tree()
        fused = bucketed_pmean if red == "pmean" else bucketed_psum
        leaf_fn = jax.lax.pmean if red == "pmean" else jax.lax.psum
        specs = jax.tree_util.tree_map(lambda _: P(), tree)
        sm = _shard_map()
        ref = jax.jit(sm(
            lambda t: jax.tree_util.tree_map(
                lambda g: leaf_fn(g, "dp"), t),
            mesh=mesh_mod.get_mesh(), in_specs=(specs,), out_specs=specs))
        # 128-byte buckets force multi-bucket fusion + dtype splits
        got = jax.jit(sm(
            lambda t: fused(t, "dp", 128.0),
            mesh=mesh_mod.get_mesh(), in_specs=(specs,), out_specs=specs))
        for x, y in zip(jax.tree_util.tree_leaves(ref(tree)),
                        jax.tree_util.tree_leaves(got(tree))):
            assert np.array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------- eager bucket sync
class _FakeParam:
    def __init__(self, grad_np):
        self.trainable = True
        self.grad = paddle.to_tensor(grad_np)


def _rank_major(rs, shape):
    return rs.randn(W, *shape).astype(np.float32)


class TestGradientBucketManager:
    @pytest.fixture(autouse=True)
    def _mesh(self):
        dist.init_mesh()
        yield

    @pytest.mark.parametrize("k", [1, 4])
    def test_fused_sync_bitwise_vs_per_param(self, k):
        """Fused bucketed all_reduce == per-param all_reduce, bit for
        bit, including k-microstep accumulated grads (bank locally,
        sync ONCE at the boundary)."""
        rs = np.random.RandomState(3)
        shapes = [(4, 6), (6,), (2, 3, 2), (5,)]
        micro = [[_rank_major(rs, s) for s in shapes] for _ in range(k)]
        accum = [np.sum([m[i] for m in micro], axis=0)
                 for i in range(len(shapes))]

        params = [_FakeParam(a.copy()) for a in accum]
        mgr = GradientBucketManager(params, bucket_mb=1e-4)  # 100 B
        n = mgr.sync()
        assert n == mgr.last_num_dispatches
        assert n >= 1

        for p, a in zip(params, accum):
            ref = paddle.to_tensor(a.copy())
            dist.all_reduce(ref)
            assert np.array_equal(p.grad.numpy(), ref.numpy())

    def test_plan_measures_logical_bytes_not_rank_major(self):
        """Regression: single-controller grads are [W, ...] rank-major;
        bucket_mb must target what ONE rank ships, not W x that —
        otherwise every bucket holds 1/W of the intended payload."""
        rs = np.random.RandomState(0)
        # 3 grads of logical 4 kB (rank-major 32 kB); 16 kB buckets fit
        # all three logically, none W-inflated
        params = [_FakeParam(_rank_major(rs, (1000,)))
                  for _ in range(3)]
        mgr = GradientBucketManager(params, bucket_mb=0.016)
        assert mgr.sync() == 1
        assert mgr.plan().total_nbytes() == 3 * 1000 * 4

    def test_fewer_dispatches_than_params(self):
        rs = np.random.RandomState(0)
        params = [_FakeParam(_rank_major(rs, (4,))) for _ in range(10)]
        mgr = GradientBucketManager(params, bucket_mb=DEFAULT_BUCKET_MB)
        assert mgr.sync() == 1          # all f32, all fit one bucket
        assert len(mgr.plan().buckets) == 1

    def test_none_grads_skipped(self):
        p = _FakeParam(_rank_major(np.random.RandomState(0), (4,)))
        q = _FakeParam(_rank_major(np.random.RandomState(1), (4,)))
        q.grad = None
        mgr = GradientBucketManager([p, q])
        assert mgr.sync() == 1

    def test_multiprocess_requires_full_grad_set(self, monkeypatch):
        # multi-controller: the plan is computed per-rank with no
        # negotiation, so a rank-divergent unused-parameter set would
        # pair mismatched fused payloads — must raise, not desync
        from paddle2_tpu.distributed import collective
        p = _FakeParam(_rank_major(np.random.RandomState(0), (4,)))
        q = _FakeParam(_rank_major(np.random.RandomState(1), (4,)))
        q.grad = None
        mgr = GradientBucketManager([p, q])
        monkeypatch.setattr(collective, "_multiprocess", lambda: True)
        with pytest.raises(ValueError, match="identical grad set"):
            mgr.sync()

    def test_fused_all_reduce_avg(self):
        rs = np.random.RandomState(7)
        g = _rank_major(rs, (3, 3))
        t1 = paddle.to_tensor(g.copy())
        t2 = paddle.to_tensor(g.copy())
        dist.all_reduce(t1, op=dist.ReduceOp.AVG)
        from paddle2_tpu.distributed.collective import fused_all_reduce
        fused_all_reduce([t2], op=dist.ReduceOp.AVG)
        assert np.array_equal(t1.numpy(), t2.numpy())

    def test_fused_all_reduce_is_package_level(self):
        from paddle2_tpu.distributed import collective
        assert dist.fused_all_reduce is collective.fused_all_reduce

    def test_fused_all_reduce_rejects_stale_plan(self):
        # a cached plan for a DIFFERENT grad set must raise, not
        # silently skip reducing the uncovered tensors (cross-rank
        # desync with no error)
        rs = np.random.RandomState(1)
        ts = [paddle.to_tensor(_rank_major(rs, (4,))) for _ in range(3)]
        short = BucketPlan([((4,), np.float32)] * 2, 1e9)
        with pytest.raises(ValueError, match="cover"):
            dist.fused_all_reduce(ts, plan=short)
        wrong_shape = BucketPlan([((5,), np.float32)] * 3, 1e9)
        with pytest.raises(ValueError, match="shapes"):
            dist.fused_all_reduce(ts, plan=wrong_shape)


# -------------------------------------------------------- ZeRO-3 prefetch
def _zero3_run(prefetch, depth=1, k=1, reliability=None, steps=4):
    dist.init_mesh({"sharding": 8})
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 32), nn.Tanh(), nn.Linear(32, 8))
    o = opt.Adam(learning_rate=1e-2, parameters=net.parameters())
    _, o, _ = dist.group_sharded_parallel(net, o, "p_g_os",
                                          prefetch=prefetch,
                                          prefetch_depth=depth)
    if k > 1:
        o = dist.shard_optimizer(o, gradient_accumulation_steps=k)
    step = paddle.jit.train_step(
        lambda x, y: ((net(x) - y) ** 2).mean(), o, layers=[net],
        reliability=reliability)
    rs = np.random.RandomState(1)
    losses = []
    for _ in range(steps):
        loss = step(paddle.to_tensor(rs.randn(16, 8).astype(np.float32)),
                    paddle.to_tensor(rs.randn(16, 8).astype(np.float32)))
        losses.append(float(np.asarray(loss._data)))
    if reliability:
        step.finalize()
    return losses, [np.asarray(p._data).copy() for p in net.parameters()], \
        net, o, step


class TestZero3Prefetch:
    def test_prefetch_bitwise_vs_eager(self):
        _, w0, _, _, _ = _zero3_run(False)
        _, w1, _, _, _ = _zero3_run(True, depth=1)
        _, w2, _, _, _ = _zero3_run(True, depth=2)
        for a, b in zip(w0, w1):
            assert np.array_equal(a, b)
        for a, b in zip(w0, w2):
            assert np.array_equal(a, b)

    @pytest.mark.parametrize("k", [1, 4])
    def test_prefetch_bitwise_under_reliability_step(self, k):
        """The reliability= compiled step (instrumented program,
        snapshots, packed sentinel) composes with prefetch — and with
        k-microstep gradient accumulation — and stays bitwise vs the
        eager-gather reliability step."""
        _, w0, _, _, _ = _zero3_run(False, k=k, reliability=True,
                                    steps=2 * k)
        _, w1, _, _, _ = _zero3_run(True, k=k, reliability=True,
                                    steps=2 * k)
        for a, b in zip(w0, w1):
            assert np.array_equal(a, b)

    def test_prefetch_keys_distinct_program(self):
        _, _, _, _, s_eager = _zero3_run(False)
        _, _, _, _, s_pref = _zero3_run(True)
        assert s_eager.program_cache_size == 1
        assert s_pref.program_cache_size == 1

    def test_layer_param_groups(self):
        from paddle2_tpu.distributed.sharding import layer_param_groups
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 4))
        params = [p for p in net.parameters()]
        groups = layer_param_groups([net], params)
        flat = [i for g in groups for i in g]
        assert sorted(flat) == list(range(len(params)))
        # weight+bias of one Linear stay in one group
        assert [0, 1] in groups and [2, 3] in groups

    def test_layer_param_groups_leftover(self):
        from paddle2_tpu.distributed.sharding import layer_param_groups
        paddle.seed(0)
        net = nn.Linear(4, 4)
        loose = paddle.to_tensor(np.zeros((2, 2), np.float32))
        params = list(net.parameters()) + [loose]
        groups = layer_param_groups([net], params)
        assert groups[-1] == [len(params) - 1]


# ---------------------------------------- ShardedOptimizer state round-trip
class TestShardedOptimizerStateRoundTrip:
    def test_placement_metadata_round_trips(self):
        dist.init_mesh({"sharding": 8})
        paddle.seed(0)
        net = nn.Linear(8, 8)
        o = opt.Adam(learning_rate=1e-2, parameters=net.parameters())
        _, o, _ = dist.group_sharded_parallel(net, o, "p_g_os")
        state = o.state_dict()
        assert state["_zero_placement"] == {"level": 3,
                                            "axis": "sharding"}

    def test_level_mismatch_raises_before_touching_state(self):
        dist.init_mesh({"sharding": 8})
        paddle.seed(0)
        net = nn.Linear(8, 8)
        o = opt.Adam(learning_rate=1e-2, parameters=net.parameters())
        _, o3, _ = dist.group_sharded_parallel(net, o, "p_g_os")
        o3._inner._step_count = 7
        state = o3.state_dict()
        from paddle2_tpu.distributed.sharding import ShardedOptimizer
        inner1 = opt.Adam(learning_rate=1e-2,
                          parameters=net.parameters())
        o1 = ShardedOptimizer(inner1, level="os")
        with pytest.raises(ValueError, match="ZeRO level mismatch"):
            o1.set_state_dict(state)
        # the mismatch must be caught BEFORE the inner restore: a
        # caller catching it (elastic ladder) continues with its own
        # state intact, not a half-applied checkpoint
        assert inner1._step_count == 0

    def test_axis_mismatch_raises(self):
        dist.init_mesh({"sharding": 8})
        paddle.seed(0)
        net = nn.Linear(8, 8)
        o = opt.Adam(learning_rate=1e-2, parameters=net.parameters())
        _, o3, _ = dist.group_sharded_parallel(net, o, "p_g_os")
        state = o3.state_dict()
        state["_zero_placement"] = {"level": 3, "axis": "dp"}
        with pytest.raises(ValueError, match="shard-axis mismatch"):
            o3.set_state_dict(state)

    def test_elastic_restore_of_prefetch_run_stays_bitwise(self):
        """PR 4 elastic path: snapshot a ZeRO-3 prefetch run mid-
        training, restore into a FRESH replica (state passes through
        host numpy, like a checkpoint read), continue — bitwise equal
        to the uninterrupted run, and the restored states are RE-SHARDED
        (not silently replicated)."""
        def build():
            dist.init_mesh({"sharding": 8})
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(8, 32), nn.Tanh(),
                                nn.Linear(32, 8))
            o = opt.Adam(learning_rate=1e-2,
                         parameters=net.parameters())
            _, o, _ = dist.group_sharded_parallel(
                net, o, "p_g_os", prefetch=True)
            step = paddle.jit.train_step(
                lambda x, y: ((net(x) - y) ** 2).mean(), o,
                layers=[net])
            return net, o, step

        rs = np.random.RandomState(2)
        batches = [(rs.randn(16, 8).astype(np.float32),
                    rs.randn(16, 8).astype(np.float32))
                   for _ in range(4)]

        net_a, o_a, step_a = build()
        for x, y in batches:
            step_a(paddle.to_tensor(x), paddle.to_tensor(y))
        ref = [np.asarray(p._data).copy() for p in net_a.parameters()]

        net_b, o_b, step_b = build()
        for x, y in batches[:2]:
            step_b(paddle.to_tensor(x), paddle.to_tensor(y))
        saved = o_b.state_dict()
        # checkpoint realism: state crosses the host as plain numpy
        from paddle2_tpu.framework.tensor import Tensor
        saved = jax.tree_util.tree_map(
            lambda v: Tensor(np.asarray(v._data).copy())
            if isinstance(v, Tensor) else v, saved)
        w_saved = [np.asarray(p._data).copy()
                   for p in net_b.parameters()]

        net_c, o_c, step_c = build()
        for p, w in zip(net_c.parameters(), w_saved):
            from paddle2_tpu.distributed.sharding import (_place,
                                                          _shard_spec)
            p._replace_data(_place(jnp.asarray(w),
                                   _shard_spec(jnp.asarray(w),
                                               "sharding")))
        o_c.set_state_dict(saved)
        for x, y in batches[2:]:
            step_c(paddle.to_tensor(x), paddle.to_tensor(y))
        got = [np.asarray(p._data).copy() for p in net_c.parameters()]
        for a, b in zip(ref, got):
            assert np.array_equal(a, b)

        # restore re-established the shard placement
        inner = o_c._inner
        sharded = False
        for p in net_c.parameters():
            st = inner._states.get(id(p))
            if st is None or p.shape[0] % 8 != 0:
                continue
            m = st["m"] if "m" in st else list(st.values())[0]
            if hasattr(m._data if hasattr(m, "_data") else m,
                       "sharding"):
                arr = m._data if hasattr(m, "_data") else m
                if arr.sharding.shard_shape(
                        tuple(arr.shape))[0] == p.shape[0] // 8:
                    sharded = True
        assert sharded


# ------------------------------------------------------------ spec layout
class TestSpecLayout:
    def test_mesh_axes_order_dcn_outermost(self):
        lo = SpecLayout()
        axes = lo.mesh_axes(dp=2, pp=2, fsdp=1, tp=2)
        assert list(axes) == ["dp", "pp", "sharding", "mp"]
        assert axes == {"dp": 2, "pp": 2, "sharding": 1, "mp": 2}

    def test_param_specs_name_the_axes(self):
        from jax.sharding import PartitionSpec as P
        lo = SpecLayout()
        assert lo.qkv_projection() == P("sharding", "mp")
        assert lo.attn_output() == P("mp", "sharding")
        assert lo.norm_scale() == P()
        assert lo.batch(2) == P(("dp", "sharding"), None)

    def test_link_model_charges_dp_as_dcn(self):
        lo = SpecLayout()
        link = lo.link_model(ici_gbps=90.0, dcn_gbps=10.0)
        assert link.is_dcn("dp")
        assert not link.is_dcn("mp")
        assert link.bandwidth("dp") == 10.0e9

    def test_hybrid_mesh_installs(self):
        mesh, lo = hybrid_mesh(dp=2, pp=2, fsdp=1, tp=2)
        assert mesh is mesh_mod.get_mesh()
        assert mesh_mod.axis_degrees() == {"dp": 2, "pp": 2,
                                           "sharding": 1, "mp": 2}
        assert mesh_mod.group_size(("dp", "mp")) == 4

    def test_dcn_axes_env(self, monkeypatch):
        dist.init_mesh()
        monkeypatch.setenv("PADDLE_DCN_AXES", "dp, foo")
        assert mesh_mod.dcn_axes() >= {"dp", "foo"}

    def test_dcn_axes_sees_installed_layout(self):
        # hybrid_mesh prices dp traffic at DCN bandwidth via the
        # layout's link model; mesh.dcn_axes() must report the SAME
        # set without needing PADDLE_DCN_AXES exported
        hybrid_mesh(dp=2, pp=2, fsdp=1, tp=2)
        assert "dp" in mesh_mod.dcn_axes()
        # a later plain init_mesh without a dp axis drops the stale
        # declaration
        dist.init_mesh({"sharding": 8})
        assert "dp" not in mesh_mod.dcn_axes()

    def test_is_dcn_matches_link_model_rule(self, monkeypatch):
        lo = SpecLayout()
        assert lo.is_dcn("dp")
        assert not lo.is_dcn("mp")
        assert lo.is_dcn("dcn_slice")        # the name convention
        monkeypatch.setenv("PADDLE_DCN_AXES", "pp")
        assert lo.is_dcn("pp")               # the env list


# --------------------------------------------------------- XLA perf flags
class TestMultichipXlaFlags:
    def test_tokens_round_trip_flag_values(self):
        from paddle2_tpu import flags as F
        try:
            toks = F.multichip_xla_flag_tokens()
            assert all(t.endswith("=true") for t in toks)
            F.set_flags({"xla_async_collectives": False})
            toks = F.multichip_xla_flag_tokens()
            off = [t for t in toks if t.endswith("=false")]
            assert off and all("async" in t or "fusion" in t
                               for t in off)
        finally:
            F.set_flags({"xla_async_collectives": True})

    def test_noop_on_cpu_env(self):
        from paddle2_tpu.flags import apply_multichip_xla_env
        env = {"JAX_PLATFORMS": "cpu", "LIBTPU_INIT_ARGS": "--foo=1"}
        assert apply_multichip_xla_env(env) == "--foo=1"
        assert env == {"JAX_PLATFORMS": "cpu",
                       "LIBTPU_INIT_ARGS": "--foo=1"}

    def test_applies_on_tpu_env_idempotently(self):
        from paddle2_tpu.flags import apply_multichip_xla_env
        env = {"JAX_PLATFORMS": "tpu"}
        first = apply_multichip_xla_env(env)
        assert "--xla_tpu_enable_latency_hiding_scheduler=true" in first
        second = apply_multichip_xla_env(env)
        assert second == first                       # no duplicates
        # the TPU runtime reads LIBTPU_INIT_ARGS; jaxlib aborts on a
        # --xla_tpu_* token in XLA_FLAGS, so that is never written
        assert env["LIBTPU_INIT_ARGS"] == first
        assert "XLA_FLAGS" not in env

    def test_operator_value_wins(self):
        from paddle2_tpu.flags import apply_multichip_xla_env
        env = {"JAX_PLATFORMS": "tpu",
               "LIBTPU_INIT_ARGS":
               "--xla_tpu_enable_latency_hiding_scheduler=false"}
        out = apply_multichip_xla_env(env)
        assert out.count("xla_tpu_enable_latency_hiding_scheduler") == 1
        assert "--xla_tpu_enable_latency_hiding_scheduler=false" in out

    def test_explicit_platform_overrides_env(self):
        from paddle2_tpu.flags import apply_multichip_xla_env
        env = {"JAX_PLATFORMS": "tpu"}
        assert apply_multichip_xla_env(env, platform="cpu") == ""
        assert "LIBTPU_INIT_ARGS" not in env

    def test_vfio_alone_is_not_tpu(self, monkeypatch):
        # GPU-passthrough VMs expose /dev/vfio/* too; injecting the
        # TPU-only XLA flags there aborts XLA startup
        import glob as glob_mod
        from paddle2_tpu import flags as F
        monkeypatch.setattr(
            glob_mod, "glob",
            lambda pat: ["/dev/vfio/0"] if pat == "/dev/vfio/*" else [])
        assert F._probe_tpu_devices() is False

    def test_accel_device_is_tpu(self, monkeypatch):
        import glob as glob_mod
        from paddle2_tpu import flags as F
        monkeypatch.setattr(
            glob_mod, "glob",
            lambda pat: ["/dev/accel0"] if pat == "/dev/accel*" else [])
        assert F._probe_tpu_devices() is True

    def test_vfio_with_google_pci_is_tpu(self, monkeypatch, tmp_path):
        import glob as glob_mod
        from paddle2_tpu import flags as F
        vendor = tmp_path / "vendor"
        vendor.write_text("0x1AE0\n")
        def fake_glob(pat):
            if pat == "/dev/vfio/*":
                return ["/dev/vfio/7"]
            if pat.startswith("/sys/bus/pci"):
                return [str(vendor)]
            return []
        monkeypatch.setattr(glob_mod, "glob", fake_glob)
        assert F._probe_tpu_devices() is True


# ------------------------------------------------- cost model overlap split
class TestOverlapAccounting:
    def _link(self):
        return LinkModel(ici_gbps=100.0, dcn_gbps=10.0, dcn_axes=("dp",))

    def test_split_sums_exactly(self):
        t = CollectiveTraffic()
        t.add("all_reduce_sum", 1e9, axes=("mp",), group_size=2)
        t.add("all_reduce_sum", 1e9, axes=("dp",), group_size=4,
              overlappable=True)
        sp = t.overlap_split(self._link(), compute_s=0.05)
        assert sp["serial_s"] == pytest.approx(
            sp["hidden_s"] + sp["exposed_s"])
        assert sp["hidden_s"] == pytest.approx(0.05)

    def test_all_hidden_when_compute_dominates(self):
        t = CollectiveTraffic()
        t.add("all_reduce_sum", 1e6, axes=("dp",), group_size=4,
              overlappable=True)
        sp = t.overlap_split(self._link(), compute_s=10.0)
        assert sp["exposed_s"] == pytest.approx(0.0)
        assert sp["hidden_s"] == pytest.approx(sp["hideable_s"])

    def test_non_overlappable_always_exposed(self):
        t = CollectiveTraffic()
        t.add("all_reduce_sum", 1e9, axes=("mp",), group_size=2)
        sp = t.overlap_split(self._link(), compute_s=100.0)
        assert sp["exposed_s"] == pytest.approx(sp["serial_s"])
        assert t.exposed_wire_bytes() == t.wire_bytes_total()
        assert t.overlappable_wire_bytes() == 0.0

    def test_step_cost_modeled_time_and_fraction(self):
        t = CollectiveTraffic()
        t.add("all_reduce_sum", 1e9, axes=("dp",), group_size=4,
              overlappable=True)
        t.add("all_reduce_sum", 2e8, axes=("dp",), group_size=4)
        c = StepCost(flops=1e12, hbm_bytes=0.0, traffic=t,
                     link=self._link(), peak_flops=1e14, hbm_bps=1e12)
        ov = c.overlap()
        assert c.step_time_modeled_s() == pytest.approx(
            c.compute_s() + ov["exposed_s"])
        assert 0.0 < c.exposed_comm_fraction() < 1.0
        roof = c.roofline()
        for key in ("exposed_network_s", "hidden_network_s",
                    "exposed_comm_fraction", "step_time_modeled_s"):
            assert key in roof
        # lower bound (perfect overlap) never exceeds the modeled time
        assert c.step_time_lower_bound_s() <= c.step_time_modeled_s()


# ------------------------------------------------ perf_doctor exposed-comm
class TestPerfDoctorExposedComm:
    def _write(self, d, recs):
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "metrics_rank_0.jsonl"), "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")

    def _steps(self, exposed=None, collective=0.0, n=4):
        out = []
        for s in range(n):
            rec = {"type": "step", "rank": 0, "step": s, "total_s": 1.0,
                   "input_wait_s": 0.0, "compute_s": 0.8,
                   "collective_s": collective,
                   "host_s": 0.2 - collective}
            if exposed is not None:
                rec["exposed_comm_s"] = exposed
            out.append(rec)
        return out

    def test_modeled_field_preferred(self, tmp_path):
        from paddle2_tpu.tools import perf_doctor
        d = str(tmp_path / "m")
        self._write(d, self._steps(exposed=0.25, collective=0.1))
        rep = perf_doctor.summarize(perf_doctor.load_streams(d))
        e = rep["per_rank"][0]
        assert e["exposed_comm_source"] == "modeled"
        assert e["exposed_comm_pct"] == pytest.approx(25.0)
        assert rep["aggregate"]["exposed_comm_pct"] == pytest.approx(25.0)

    def test_collective_wall_fallback(self, tmp_path):
        from paddle2_tpu.tools import perf_doctor
        d = str(tmp_path / "w")
        self._write(d, self._steps(collective=0.1))
        rep = perf_doctor.summarize(perf_doctor.load_streams(d))
        e = rep["per_rank"][0]
        assert e["exposed_comm_source"] == "collective-wall"
        assert e["exposed_comm_pct"] == pytest.approx(10.0)

    def test_summary_and_diff_report_it(self, tmp_path):
        from paddle2_tpu.tools import perf_doctor
        a = str(tmp_path / "a")
        b = str(tmp_path / "b")
        self._write(a, self._steps(exposed=0.05))
        self._write(b, self._steps(exposed=0.30))
        ra = perf_doctor.summarize(perf_doctor.load_streams(a))
        rb = perf_doctor.summarize(perf_doctor.load_streams(b))
        assert "exposed-comm" in perf_doctor.format_summary(ra, a)
        d = perf_doctor.diff(ra, rb)
        assert d["exposed_comm_pct"]["new"] > \
            d["exposed_comm_pct"]["base"]
        assert d["exposed_comm_pct"]["comparable"]
        assert "OVERLAP REGRESSION" in perf_doctor.format_diff(d)

    def test_diff_mixed_sources_not_flagged_as_regression(self,
                                                          tmp_path):
        """A modeled stream diffed against a collective-wall fallback
        stream is a metric-SOURCE change, not an overlap change — the
        regression tag must not fire."""
        from paddle2_tpu.tools import perf_doctor
        a = str(tmp_path / "a")
        b = str(tmp_path / "b")
        self._write(a, self._steps(collective=0.0))    # wall fallback
        self._write(b, self._steps(exposed=0.30))      # modeled
        ra = perf_doctor.summarize(perf_doctor.load_streams(a))
        rb = perf_doctor.summarize(perf_doctor.load_streams(b))
        d = perf_doctor.diff(ra, rb)
        assert not d["exposed_comm_pct"]["comparable"]
        txt = perf_doctor.format_diff(d)
        assert "OVERLAP REGRESSION" not in txt
        assert "incomparable" in txt


# ----------------------------------------------------- 1F1B bucketed grads
@pytest.mark.parametrize("bucket_bytes", [64.0, 1e6])
def test_1f1b_bucketed_dp_grads_bitwise(bucket_bytes):
    """pipeline_spmd_1f1b(grad_bucket_bytes=) == the per-leaf dp pmean
    path, bitwise, through the compiled dp x pp hybrid pipeline (same
    setup as test_compiled_1f1b_dp_sharded_batches_parity)."""
    from paddle2_tpu.distributed.fleet.spmd_pipeline import (
        pipeline_spmd_1f1b)

    dist.init_mesh({"pp": 4, "dp": 2})
    S_pp, M, B, H = 4, 4, 4, 8           # B=4 splits 2-way over dp
    rs = np.random.RandomState(0)
    Wstk = jnp.asarray(rs.randn(S_pp, H, H) * 0.3, jnp.float32)
    bstk = jnp.asarray(rs.randn(S_pp, H) * 0.3, jnp.float32)
    x = jnp.asarray(rs.randn(M, B, H), jnp.float32)
    y = jnp.asarray(rs.randn(M, B, H), jnp.float32)

    def stage_fn(p, shared, xx, sidx):
        w, bb = p
        return jnp.tanh(xx @ w + bb)

    def loss_fn(out, label):
        return jnp.mean((out - label) ** 2)

    ref = pipeline_spmd_1f1b(stage_fn, (Wstk, bstk), x, y, loss_fn,
                             dp_axis="dp")
    # 64 B: one bucket per leaf (the multi-dispatch path); 1 MB: every
    # f32 leaf coalesces into ONE fused payload
    got = pipeline_spmd_1f1b(stage_fn, (Wstk, bstk), x, y, loss_fn,
                             dp_axis="dp",
                             grad_bucket_bytes=bucket_bytes)
    assert np.array_equal(np.asarray(ref[0]), np.asarray(got[0]))
    for a, b in zip(jax.tree_util.tree_leaves(ref[1]),
                    jax.tree_util.tree_leaves(got[1])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------- gang smoke test
@pytest.mark.slow
@pytest.mark.gang
def test_multichip_scaling_bench_smoke():
    """The dp x tp x pp scaling gate end-to-end on 8 virtual devices —
    the exact command CI runs."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py"),
         "--multichip-scaling"],
        capture_output=True, text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] is True
    assert rec["value"] >= 0.85
    assert rec["scaling"]["exposed_comm_pct"]["bucketed"] < \
        rec["scaling"]["exposed_comm_pct"]["unbucketed"]
    assert all(rec["gates"].values())
