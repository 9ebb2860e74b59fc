"""MoE layer + expert parallelism (reference incubate moe_layer.py:263,
gshard/switch gates)."""

import numpy as np
import pytest

import paddle2_tpu as paddle
import paddle2_tpu.nn as nn
import paddle2_tpu.optimizer as opt
from paddle2_tpu.incubate import MoELayer, SwitchGate, TopKGate


@pytest.fixture(autouse=True)
def _no_mesh_left_by_another_file():
    """A layer here is single-device unless its test installs a mesh:
    one that an earlier file of the same worker left installed shards
    the experts (``MoELayer._expert_axis``) and re-places their weights,
    and the optimizer step then meets two device sets (seen once in six
    workers' file order, PR 36)."""
    from paddle2_tpu.distributed import mesh as mesh_mod
    prev = mesh_mod.get_mesh(auto_init=False)
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(prev)


def _experts(n, d, h):
    return [nn.Sequential(nn.Linear(d, h), nn.GELU(), nn.Linear(h, d))
            for _ in range(n)]


def test_moe_forward_shapes_and_combine():
    paddle.seed(0)
    d = 16
    moe = MoELayer(d, _experts(4, d, 32), top_k=2, capacity_factor=2.0)
    x = paddle.randn([6, 8, d])
    y = moe(x)
    assert tuple(y.shape) == (6, 8, d)
    assert moe.aux_loss is not None
    aux = float(moe.aux_loss.numpy())
    assert np.isfinite(aux) and aux >= 1.0 - 1e-3  # >=1 by Cauchy-Schwarz


def test_moe_single_expert_equals_dense():
    """With one expert, generous capacity, top-1: MoE == expert(x)."""
    paddle.seed(0)
    d = 8
    expert = nn.Linear(d, d)
    moe = MoELayer(d, [expert], gate=SwitchGate(d, 1, capacity_factor=64.0))
    x = paddle.randn([4, d])
    y = moe(x)
    ref = expert(x)
    np.testing.assert_allclose(y.numpy(), ref.numpy(), rtol=1e-4, atol=1e-5)


def test_moe_trains_and_routes():
    """Gradients reach both experts and the router; aux loss finite."""
    paddle.seed(1)
    d = 8
    moe = MoELayer(d, _experts(2, d, 16), top_k=1, capacity_factor=4.0)
    o = opt.Adam(learning_rate=1e-2, parameters=moe.parameters())
    x = paddle.randn([16, d])
    target = paddle.randn([16, d])
    import paddle2_tpu.nn.functional as F
    first = None
    for step in range(12):
        y = moe(x)
        loss = F.mse_loss(y, target) + moe.aux_loss * 0.01
        loss.backward()
        o.step()
        o.clear_grad()
        v = float(loss.numpy())
        if first is None:
            first = v
    assert v < first, (first, v)
    assert moe.gate.wg.weight.grad is None  # cleared
    # capacity math
    assert moe.gate.capacity(64) == 128  # 4.0 * 1 * 64 / 2


def test_moe_expert_parallel_sharding():
    """Experts shard over the mp axis on the 8-dev mesh; output matches the
    unsharded run."""
    import paddle2_tpu.distributed as dist
    from paddle2_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 8,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 1}
    fleet.init(strategy=strategy)
    paddle.seed(0)
    d = 8
    moe = MoELayer(d, _experts(8, d, 16), top_k=2, capacity_factor=4.0)
    x = paddle.randn([16, d])
    y = moe(x)
    assert tuple(y.shape) == (16, d)
    assert np.isfinite(y.numpy()).all()
    dist.init_mesh({"dp": 8})  # restore


def test_moe_under_to_static():
    paddle.seed(0)
    d = 8
    moe = MoELayer(d, _experts(2, d, 16), top_k=2, capacity_factor=4.0)
    x = paddle.randn([8, d])
    eager = moe(x).numpy()
    st = paddle.jit.to_static(lambda t: moe(t))
    out = st(x)
    np.testing.assert_allclose(out.numpy(), eager, rtol=1e-4, atol=1e-5)


def test_sort_dispatch_matches_dense():
    """The O(S*M) scatter/gather dispatch must equal the dense GShard
    einsum formulation — outputs AND gradients."""
    import paddle2_tpu as paddle
    from paddle2_tpu import nn
    from paddle2_tpu.incubate.moe import MoELayer

    def build(mode):
        paddle.seed(0)
        experts = [nn.Sequential(nn.Linear(16, 32), nn.GELU(),
                                 nn.Linear(32, 16)) for _ in range(4)]
        return MoELayer(d_model=16, experts=experts, top_k=2,
                        dispatch_mode=mode)

    rs = np.random.RandomState(0)
    xv = rs.randn(2, 24, 16).astype(np.float32)
    outs, grads = {}, {}
    for mode in ("dense", "sort"):
        m = build(mode)
        x = paddle.to_tensor(xv.copy())
        x.stop_gradient = False
        out = m(x)
        (out ** 2).sum().backward()
        outs[mode] = out.numpy()
        grads[mode] = x.grad.numpy()
    np.testing.assert_allclose(outs["sort"], outs["dense"],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(grads["sort"], grads["dense"],
                               rtol=1e-4, atol=1e-5)


def test_dispatch_mode_auto_and_validation():
    import pytest as _pytest
    from paddle2_tpu import nn
    from paddle2_tpu.incubate.moe import MoELayer
    experts = [nn.Linear(8, 8) for _ in range(2)]
    with _pytest.raises(ValueError):
        MoELayer(8, experts, dispatch_mode="bogus")
    m = MoELayer(8, experts, dispatch_mode="auto")
    assert m._mode() in ("sort", "dense")


# -- capacity audit (ISSUE 19): drops deterministic, counted, surfaced --

def test_capacity_tiebreak_lower_token_index_wins_last_slot():
    """Regression pin on the drop order at an exactly-full expert: the
    in-expert position is a cumsum over token order, so the LOWER token
    index wins the last slot — every run, every host."""
    import jax.numpy as jnp
    from paddle2_tpu.incubate.moe import (_topk_pieces, dispatch_stats,
                                          token_ledger_closes)
    # 4 tokens, all preferring expert 0, capacity 2: tokens 0 and 1
    # take the slots; 2 and 3 drop (zero combine weight)
    logits = jnp.asarray(np.tile([[5.0, 0.0]], (4, 1)), jnp.float32)
    idxs, gates, poss, _ = _topk_pieces(logits, 1, 2)
    np.testing.assert_array_equal(np.asarray(poss[0]), [0, 1, 2, 3])
    g = np.asarray(gates[0])
    assert (g[:2] > 0).all() and (g[2:] == 0).all()
    stats = dispatch_stats(np.asarray(idxs), np.asarray(poss), 2, 2)
    assert stats["dropped_per_expert"].tolist() == [2, 0]
    assert stats["tokens_residual"] == 2
    assert token_ledger_closes(stats)
    # interleaved preference, capacity 1: within each expert the
    # earlier token still wins
    lg = jnp.asarray([[5.0, 0.0], [0.0, 5.0], [5.0, 0.0], [0.0, 5.0]],
                     jnp.float32)
    idxs, gates, poss, _ = _topk_pieces(lg, 1, 1)
    keep = np.asarray(poss[0]) < 1
    np.testing.assert_array_equal(keep, [True, True, False, False])


def test_capacity_rounding_edges():
    """cf below 1.0 and token counts not divisible by num_experts: the
    capacity is ceil'd and floored at top_k."""
    gate = TopKGate(8, 4, top_k=2, capacity_factor=0.5)
    assert gate.capacity(10) == 3      # ceil(0.5 * 2 * 10 / 4) = 3
    assert gate.capacity(4) == 2       # floor: max(top_k, ceil(1)) = 2
    tight = TopKGate(8, 4, top_k=2, capacity_factor=0.01)
    assert tight.capacity(400) == 2    # floor holds at any scale
    # a forward at S % E != 0 with a sub-1.0 cf: drops are counted and
    # the ledger still closes, no expert over capacity
    paddle.seed(0)
    moe = MoELayer(8, _experts(4, 8, 16), top_k=2, capacity_factor=0.5,
                   collect_stats=True)
    from paddle2_tpu.incubate.moe import token_ledger_closes
    y = moe(paddle.randn([7, 8]))
    assert tuple(y.shape) == (7, 8)
    st = moe.last_stats
    assert st is not None and token_ledger_closes(st)
    assert int(st["routed_per_expert"].max()) <= st["capacity"]


def test_topk_picks_are_distinct_experts():
    """The k picks of one token never name the same expert twice (the
    remaining-probs masking), even when k == num_experts."""
    import jax.numpy as jnp
    from paddle2_tpu.incubate.moe import _topk_pieces
    rs = np.random.RandomState(0)
    lg = jnp.asarray(rs.randn(32, 2), jnp.float32)
    idxs, gates, _, _ = _topk_pieces(lg, 2, 32)
    a, b = np.asarray(idxs[0]), np.asarray(idxs[1])
    assert (a != b).all()
    # normalized combine weights sum to 1 when nothing dropped
    tot = np.asarray(gates).sum(axis=0)
    np.testing.assert_allclose(tot, 1.0, rtol=1e-5)


def test_gate_numerics_match_f64_reference():
    """The jitted f32 gate against the float64 numpy oracle: routing
    decisions exact, gate probs and both router losses within f32
    tolerance."""
    from paddle2_tpu.incubate.moe import router_reference_f64
    paddle.seed(0)
    gate = TopKGate(16, 4, top_k=2, capacity_factor=1.25)
    rs = np.random.RandomState(3)
    x = paddle.to_tensor(rs.randn(24, 16).astype(np.float32))
    idxs, gates, poss, aux = gate.pieces(x)
    aux_t, z_t = gate.router_losses(x)
    ref = router_reference_f64(gate.wg(x).numpy(), 2, gate.capacity(24))
    np.testing.assert_array_equal(np.asarray(idxs.numpy()), ref["idxs"])
    np.testing.assert_array_equal(np.asarray(poss.numpy()), ref["poss"])
    np.testing.assert_allclose(gates.numpy(), ref["gates"],
                               rtol=1e-4, atol=1e-6)
    assert abs(float(aux.numpy()) - ref["aux"]) <= 1e-4 * abs(ref["aux"])
    assert abs(float(aux_t.numpy()) - ref["aux"]) \
        <= 1e-4 * abs(ref["aux"])
    assert abs(float(z_t.numpy()) - ref["z_loss"]) \
        <= 1e-4 * abs(ref["z_loss"])


def test_collect_stats_surfaces_drops_and_counters():
    """collect_stats publishes the exact dispatch ledger and the moe_*
    counters; the default path keeps last_stats None (no readback)."""
    from paddle2_tpu.incubate.moe import token_ledger_closes
    from paddle2_tpu.observability import metrics
    paddle.seed(0)
    quiet = MoELayer(8, _experts(4, 8, 16), top_k=2,
                     capacity_factor=0.25)
    quiet(paddle.randn([16, 8]))
    assert quiet.last_stats is None
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        pl = metrics.enable(td, rank=0, flush_steps=1)
        try:
            paddle.seed(0)
            moe = MoELayer(8, _experts(4, 8, 16), top_k=2,
                           capacity_factor=0.25, collect_stats=True)
            moe(paddle.randn([16, 8]))
            st = moe.last_stats
            assert st["dropped_picks"] > 0 and token_ledger_closes(st)
            snap = pl.snapshot()["counters"]
            assert sum(snap["moe_tokens_routed_total"].values()) \
                == st["routed_picks"]
            assert sum(snap["moe_tokens_dropped_total"].values()) \
                == st["dropped_picks"]
        finally:
            metrics.disable()
