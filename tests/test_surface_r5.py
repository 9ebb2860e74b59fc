"""Round-5 surface completion part 2: distributed extras (spawn env
contract, object collectives, entry attrs, datasets, sharding stages),
static places/EMA/metrics/serialization, incubate graph ops, vision
detection ops (roi_pool/prior_box/yolo_box/matrix_nms/yolo_loss),
ASGD/Rprop, saved_tensors_hooks. Namespace parity pinned against the
reference __all__ lists."""

import os
import re

import numpy as np
import pytest

import paddle2_tpu as paddle
import paddle2_tpu.distributed as dist
import paddle2_tpu.static as static
from paddle2_tpu.vision import ops as vops

REF = "/root/reference/python/paddle"


@pytest.mark.parametrize("mod,path", [
    ("paddle2_tpu.distributed", f"{REF}/distributed/__init__.py"),
    ("paddle2_tpu.incubate", f"{REF}/incubate/__init__.py"),
    ("paddle2_tpu.static", f"{REF}/static/__init__.py"),
    ("paddle2_tpu.optimizer", f"{REF}/optimizer/__init__.py"),
    ("paddle2_tpu.autograd", f"{REF}/autograd/__init__.py"),
    ("paddle2_tpu.jit", f"{REF}/jit/__init__.py"),
    ("paddle2_tpu.vision.ops", f"{REF}/vision/ops.py"),
])
def test_namespace_parity(mod, path):
    import importlib
    if not os.path.isfile(path):
        pytest.skip(f"the reference's {path} is not on this host")
    ref = open(path).read()
    m = re.search(r"__all__\s*=\s*\[(.*?)\]", ref, re.S)
    names = set(re.findall(r"['\"](\w+)['\"]", m.group(1)))
    ours = set(dir(importlib.import_module(mod)))
    assert names - ours == set(), f"{mod} missing {names - ours}"


def test_object_collectives():
    dist.init_mesh()
    out = []
    dist.scatter_object_list(out, [{"r": i} for i in range(8)], src=0)
    assert out[3] == {"r": 3}
    objs = ["a"]
    dist.broadcast_object_list(objs, src=0)
    assert objs == ["a"]
    with pytest.raises(ValueError):
        dist.scatter_object_list([], [1, 2], src=0)


def test_entry_attrs_and_ps_binding():
    from paddle2_tpu.distributed import ps
    e = dist.CountFilterEntry(2)
    assert e._to_attr() == "count_filter_entry:2"
    assert dist.ProbabilityEntry(0.5)._to_attr() == "probability_entry:0.5"
    assert dist.ShowClickEntry("show", "click")._to_attr() == \
        "show_click_entry:show:click"
    dist.init_mesh({"dp": 8})
    t = ps.SparseTable(8, 2, rule="naive", initial_range=0.2,
                       entry=dist.CountFilterEntry(2), seed=1)
    ids = np.array([3], np.int32)
    assert np.all(np.asarray(t.pull(ids)) == 0.0)   # cold
    assert np.abs(np.asarray(t.pull(ids))).sum() > 0  # warm
    with pytest.raises(NotImplementedError):
        ps.SparseTable(8, 2, entry=dist.ProbabilityEntry(0.5))


def test_in_memory_and_queue_dataset(tmp_path):
    p = tmp_path / "part-0"
    p.write_text("1 2\n3 4\n5 6\n")
    ds = dist.InMemoryDataset()
    ds.init(batch_size=2)
    ds.set_filelist([str(p)])
    ds.load_into_memory()
    assert ds.get_memory_data_size() == 3
    ds.local_shuffle(seed=0)
    batches = list(ds)
    assert len(batches) == 2 and len(batches[0]) == 2
    q = dist.QueueDataset()
    q.init(batch_size=3)
    q.set_filelist([str(p)])
    assert list(q) == [[[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]]]
    with pytest.raises(NotImplementedError, match="pipe_command"):
        ds.init(pipe_command="cat")


def test_sharding_stage_classes_place_accumulators():
    import paddle2_tpu.optimizer as opt
    import paddle2_tpu.nn as nn
    dist.init_mesh({"dp": 8})
    paddle.seed(0)
    model = nn.Linear(16, 16)
    o = dist.shard_optimizer(opt.Adam(learning_rate=0.1,
                                      parameters=model.parameters()),
                             dist.ShardingStage1())
    x = paddle.randn([4, 16])
    (model(x) ** 2).mean().backward()
    o.step()
    inner = o._inner
    p0 = model.parameters()[0]
    state = inner._states[id(p0)]
    import jax
    leaves = [a for a in jax.tree_util.tree_leaves(state)
              if hasattr(a, "sharding") and a.ndim > 0]
    assert any("dp" in (a.sharding.spec or ()) for a in leaves), \
        [a.sharding for a in leaves]
    # stage 3 also shards the parameter itself
    model2 = nn.Linear(16, 16)
    o2 = dist.shard_optimizer(opt.Adam(learning_rate=0.1,
                                       parameters=model2.parameters()),
                              dist.ShardingStage3())
    (model2(x) ** 2).mean().backward()
    o2.step()
    p = model2.parameters()[0]
    assert p._data.sharding.spec[0] == "dp"
    assert dist.shard_scaler(paddle.amp.GradScaler()) is not None


def _spawn_worker(path):
    import os
    with open(f"{path}.{os.environ['PADDLE_TRAINER_ID']}", "w") as f:
        f.write(os.environ["PADDLE_TRAINERS_NUM"])


def test_spawn_runs_workers_with_env(tmp_path):
    # func must be module-level picklable (the reference's documented
    # contract, spawn.py:480)
    dist.spawn(_spawn_worker, args=(str(tmp_path / "out"),), nprocs=2,
               join=True, env={"JAX_PLATFORMS": "cpu"})
    assert (tmp_path / "out.0").read_text() == "2"
    assert (tmp_path / "out.1").read_text() == "2"


def test_distributed_split_linear_and_embedding():
    dist.init_mesh({"dp": 4, "mp": 2})
    paddle.seed(0)
    x = paddle.randn([4, 8])
    y = dist.split(x, (8, 6), operation="linear", axis=1,
                   num_partitions=2)
    assert tuple(y.shape) == (4, 6)
    ids = paddle.to_tensor(np.array([[0, 5], [3, 7]]))
    e = dist.split(ids, (8, 4), operation="embedding", num_partitions=2)
    assert tuple(e.shape) == (2, 2, 4)
    with pytest.raises(ValueError, match="num_partitions"):
        dist.split(x, (8, 6), operation="linear", num_partitions=4)
    dist.init_mesh()


def test_static_places_and_program_state(tmp_path):
    assert len(static.cpu_places()) >= 1
    assert len(static.cuda_places()) >= 1
    w = static.create_parameter([3, 3], "float32", name="w0")
    g = static.create_global_var([2], 1.5, "float32", name="g0")
    np.testing.assert_allclose(g.numpy(), [1.5, 1.5])
    prog = static.Program()
    prog._live[id(w)] = w    # what recording an op with w does
    path = str(tmp_path / "model")
    static.save(prog, path)
    orig = w.numpy().copy()
    w._replace_data(np.zeros((3, 3), np.float32))
    static.load(prog, path)
    np.testing.assert_allclose(w.numpy(), orig)
    state = static.load_program_state(path)
    assert "w0" in state
    with static.scope_guard(static.global_scope()):
        pass
    comp = static.CompiledProgram(prog, static.BuildStrategy())
    assert comp._program is prog
    with pytest.raises(NotImplementedError):
        static.IpuStrategy()


def test_static_ema_and_metrics():
    w = paddle.to_tensor(np.array([1.0], np.float32), stop_gradient=False)
    ema = static.ExponentialMovingAverage(0.5)
    ema.update(parameters=[w])
    w._replace_data(np.array([3.0], np.float32))
    ema.update()
    ema.apply()
    mid = w.numpy()[0]
    assert 1.0 < mid < 3.0
    ema.restore()
    assert w.numpy()[0] == 3.0
    acc = static.accuracy(
        paddle.to_tensor(np.array([[0.1, 0.9], [0.8, 0.2]], np.float32)),
        paddle.to_tensor(np.array([[1], [1]])))
    assert np.isclose(float(acc.numpy()), 0.5)
    scores = np.array([[0.3, 0.7], [0.6, 0.4], [0.2, 0.8], [0.9, 0.1]],
                      np.float32)
    labels = np.array([1, 0, 1, 0])
    a, _, _ = static.auc(paddle.to_tensor(scores),
                         paddle.to_tensor(labels))
    assert float(a.numpy()) > 0.95   # perfectly separable


def test_incubate_graph_reindex_doc_example():
    import paddle2_tpu.incubate as inc
    x = paddle.to_tensor(np.array([0, 1, 2]))
    nb = paddle.to_tensor(np.array([8, 9, 0, 4, 7, 6, 7]))
    ct = paddle.to_tensor(np.array([2, 3, 2], np.int32))
    src, dst, nodes = inc.graph_reindex(x, nb, ct)
    assert nodes.numpy().tolist() == [0, 1, 2, 8, 9, 4, 7, 6]
    assert src.numpy().tolist() == [3, 4, 0, 5, 6, 7, 6]
    assert dst.numpy().tolist() == [0, 0, 1, 1, 1, 2, 2]


def test_incubate_sampling_and_fused_softmax():
    import paddle2_tpu.incubate as inc
    row = paddle.to_tensor(np.array([1, 2, 2]))
    colptr = paddle.to_tensor(np.array([0, 0, 1, 3]))
    nb, ct = inc.graph_sample_neighbors(
        row, colptr, paddle.to_tensor(np.array([2, 1])), sample_size=1)
    assert ct.numpy().tolist() == [1, 1]
    m = inc.softmax_mask_fuse_upper_triangle(paddle.randn([1, 1, 4, 4]))
    out = m.numpy()
    assert np.allclose(out.sum(-1), 1.0, atol=1e-5)
    assert np.allclose(out[0, 0, 0, 1:], 0.0)
    sm = inc.softmax_mask_fuse(paddle.randn([1, 1, 2, 4]),
                               paddle.zeros([1, 1, 2, 4]))
    assert np.allclose(sm.numpy().sum(-1), 1.0, atol=1e-5)
    s = inc.identity_loss(paddle.to_tensor(np.array([1.0, 3.0],
                                                    np.float32)), "mean")
    assert np.isclose(float(s.numpy()), 2.0)


def test_roi_pool_and_prior_box():
    x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
    out = vops.roi_pool(paddle.to_tensor(x),
                        paddle.to_tensor(np.array([[0, 0, 3, 3]],
                                                  np.float32)),
                        paddle.to_tensor(np.array([1], np.int32)), (2, 2))
    np.testing.assert_allclose(out.numpy()[0, 0], [[5, 7], [13, 15]])
    layer = vops.RoIPool((2, 2))
    np.testing.assert_allclose(
        layer(paddle.to_tensor(x),
              paddle.to_tensor(np.array([[0, 0, 3, 3]], np.float32)),
              paddle.to_tensor(np.array([1], np.int32))).numpy(),
        out.numpy())
    feat = paddle.zeros([1, 8, 4, 4])
    img = paddle.zeros([1, 3, 32, 32])
    boxes, var = vops.prior_box(feat, img, [8.0], [16.0], [2.0],
                                flip=True, clip=True)
    # A = 1 (ar=1,min) + 2 (ar=2 + flipped 0.5) + 1 (sqrt(min*max)) = 4
    assert tuple(boxes.shape) == (4, 4, 4, 4)
    b = boxes.numpy()
    assert (b >= 0).all() and (b <= 1).all()
    assert tuple(var.shape) == (4, 4, 4, 4)
    np.testing.assert_allclose(var.numpy()[0, 0, 0], [0.1, 0.1, 0.2, 0.2])


def test_yolo_box_decode_math():
    A, H, W, C = 1, 2, 2, 1
    x = np.zeros((1, A * (5 + C), H, W), np.float32)
    x[0, 4] = 10.0    # conf ~ 1
    x[0, 5] = 10.0    # class prob ~ 1
    boxes, scores = vops.yolo_box(
        paddle.to_tensor(x),
        paddle.to_tensor(np.array([[16, 16]], np.int32)),
        [4, 4], C, 0.5, 8, clip_bbox=False)
    b = boxes.numpy().reshape(H, W, A, 4)
    # cell (0,0): center = (0.5/2)*16 = 4, w = h = 4 -> [2, 2, 6, 6]
    np.testing.assert_allclose(b[0, 0, 0], [2, 2, 6, 6], atol=1e-4)
    np.testing.assert_allclose(scores.numpy().max(), 1.0, atol=1e-3)


def test_matrix_nms_decays_overlaps():
    bb = np.array([[[0, 0, 10, 10], [0.5, 0.5, 10, 10],
                    [20, 20, 30, 30]]], np.float32)
    sc = np.array([[[0.9, 0.85, 0.7]]], np.float32)
    out, idx, num = vops.matrix_nms(paddle.to_tensor(bb),
                                    paddle.to_tensor(sc), 0.1, 0.05,
                                    10, 5, return_index=True,
                                    background_label=-1)
    o = out.numpy()
    assert int(num.numpy()[0]) == 3
    # the heavily-overlapped second box decays below the isolated third
    top = o[o[:, 1].argsort()[::-1]]
    assert top[0, 1] == pytest.approx(0.9, abs=1e-5)
    decayed = o[1:, 1]
    assert (decayed < 0.9).all()


def test_yolo_loss_differentiable_and_ordered():
    rng = np.random.RandomState(0)
    xt = paddle.to_tensor(rng.randn(2, 2 * 7, 4, 4).astype(np.float32),
                          stop_gradient=False)
    gtb = np.zeros((2, 3, 4), np.float32)
    gtb[0, 0] = [0.5, 0.5, 0.4, 0.3]
    gtl = np.zeros((2, 3), np.int32)
    loss = vops.yolo_loss(xt, paddle.to_tensor(gtb),
                          paddle.to_tensor(gtl), [10, 13, 16, 30],
                          [0, 1], 2, 0.7, 8)
    v = loss.numpy()
    assert v.shape == (2,) and np.isfinite(v).all()
    assert v[0] > v[1]          # the sample WITH a gt has extra loss
    loss.sum().backward()
    assert np.isfinite(xt.grad.numpy()).all()


def test_saved_tensors_hooks_pack_unpack():
    from paddle2_tpu.autograd import PyLayer, saved_tensors_hooks
    calls = {"pack": 0, "unpack": 0}

    def pack(t):
        calls["pack"] += 1
        return np.asarray(t.numpy())

    def unpack(a):
        calls["unpack"] += 1
        return paddle.to_tensor(a)

    class Square(PyLayer):
        @staticmethod
        def forward(ctx, x):
            ctx.save_for_backward(x)
            return x * x

        @staticmethod
        def backward(ctx, dy):
            (x,) = ctx.saved_tensor()
            return dy * 2 * x

    x = paddle.to_tensor(np.array([3.0], np.float32),
                         stop_gradient=False)
    with saved_tensors_hooks(pack, unpack):
        y = Square.apply(x)
    y.sum().backward()              # unpack happens OUTSIDE the context
    np.testing.assert_allclose(x.grad.numpy(), [6.0])
    assert calls == {"pack": 1, "unpack": 1}
