"""Round-5 surface completion part 3: sparse subsystem depth,
new distributions, transforms (affine/perspective/hue), fleet classes,
audio IO, text datasets, fft hfft family, nn.utils parametrizations,
device helpers — with the full-namespace parity sweep pinned."""

import math
import os
import re

import numpy as np
import pytest

import paddle2_tpu as paddle

REF = "/root/reference/python/paddle"


@pytest.mark.parametrize("mod,path", [
    ("paddle2_tpu", f"{REF}/__init__.py"),
    ("paddle2_tpu.fft", f"{REF}/fft.py"),
    ("paddle2_tpu.sparse", f"{REF}/sparse/__init__.py"),
    ("paddle2_tpu.distribution", f"{REF}/distribution/__init__.py"),
    ("paddle2_tpu.profiler", f"{REF}/profiler/__init__.py"),
    ("paddle2_tpu.text", f"{REF}/text/__init__.py"),
    ("paddle2_tpu.audio", f"{REF}/audio/__init__.py"),
    ("paddle2_tpu.vision.models", f"{REF}/vision/models/__init__.py"),
    ("paddle2_tpu.vision.transforms",
     f"{REF}/vision/transforms/__init__.py"),
    ("paddle2_tpu.distributed.fleet",
     f"{REF}/distributed/fleet/__init__.py"),
    ("paddle2_tpu.quantization", f"{REF}/quantization/__init__.py"),
    ("paddle2_tpu.geometric", f"{REF}/geometric/__init__.py"),
    ("paddle2_tpu.nn.initializer", f"{REF}/nn/initializer/__init__.py"),
    ("paddle2_tpu.nn.utils", f"{REF}/nn/utils/__init__.py"),
    ("paddle2_tpu.device", f"{REF}/device/__init__.py"),
])
def test_namespace_parity_sweep(mod, path):
    import importlib
    if not os.path.isfile(path):
        pytest.skip(f"the reference's {path} is not on this host")
    ref = open(path).read()
    m = re.search(r"__all__\s*=\s*\[(.*?)\]", ref, re.S)
    names = set(re.findall(r"['\"]([\w.]+)['\"]", m.group(1)))
    ours = set(dir(importlib.import_module(mod)))
    missing = {n for n in names - ours if not n.startswith("_")}
    assert missing == set(), f"{mod} missing {missing}"


# ---------------------------------------------------------------- sparse

def test_sparse_unary_preserves_structure():
    import paddle2_tpu.sparse as sp
    coo = sp.sparse_coo_tensor(
        paddle.to_tensor(np.array([[0, 1], [1, 0]])),
        paddle.to_tensor(np.array([4.0, 9.0], np.float32)), (2, 2))
    r = sp.sqrt(coo)
    assert isinstance(r, sp.SparseCooTensor)
    np.testing.assert_allclose(np.asarray(r.values().numpy()), [2.0, 3.0])
    assert sp.neg(coo).values().numpy().tolist() == [-4.0, -9.0]


def test_sparse_coalesce_mv_sddmm():
    import paddle2_tpu.sparse as sp
    dup = sp.sparse_coo_tensor(
        paddle.to_tensor(np.array([[0, 0], [1, 1]])),
        paddle.to_tensor(np.array([1.0, 2.0], np.float32)), (2, 2))
    c = sp.coalesce(dup)
    assert c.nnz() == 1 and float(c.values().numpy()[0]) == 3.0
    d = np.array([[1, 0, 2], [0, 3, 0]], np.float32)
    csr = sp._dense_to_csr(d)
    v = np.array([1.0, 2.0, 3.0], np.float32)
    np.testing.assert_allclose(sp.mv(csr, paddle.to_tensor(v)).numpy(),
                               d @ v)
    rng = np.random.RandomState(0)
    A = rng.randn(3, 4).astype(np.float32)
    B = rng.randn(4, 3).astype(np.float32)
    mask = sp._dense_to_csr(np.array([[1, 0, 1], [0, 1, 0], [1, 1, 0]],
                                     np.float32))
    mm = sp.masked_matmul(paddle.to_tensor(A), paddle.to_tensor(B), mask)
    exp = (A @ B)[np.asarray(mask.to_dense().numpy()) != 0]
    np.testing.assert_allclose(np.asarray(mm.values().numpy()), exp,
                               rtol=1e-5)


def test_sparse_transpose_reshape_sum():
    import paddle2_tpu.sparse as sp
    coo = sp.sparse_coo_tensor(
        paddle.to_tensor(np.array([[0, 1], [1, 0]])),
        paddle.to_tensor(np.array([4.0, 9.0], np.float32)), (2, 3))
    t = sp.transpose(coo, [1, 0])
    np.testing.assert_allclose(np.asarray(t.to_dense().numpy()),
                               np.asarray(coo.to_dense().numpy()).T)
    r = sp.reshape(coo, (3, 2))
    assert r.shape == [3, 2]
    assert float(sp.sum(coo).numpy()) == 13.0


def test_sparse_nn_softmax_and_subm_conv():
    import paddle2_tpu.sparse as sp
    import paddle2_tpu.sparse.nn as snn
    sm = snn.Softmax()(sp._dense_to_csr(
        np.array([[1., 2., 0.], [0., 1., 1.]], np.float32)))
    sd = np.asarray(sm.to_dense().numpy())
    np.testing.assert_allclose(sd[0, :2].sum(), 1.0, rtol=1e-5)
    assert sd[0, 2] == 0.0   # structural zero stays zero
    rng = np.random.RandomState(0)
    indices = np.array([[0, 0, 0], [1, 2, 3], [0, 1, 2]])
    vals = rng.randn(3, 2).astype(np.float32)
    x = sp.sparse_coo_tensor(paddle.to_tensor(indices),
                             paddle.to_tensor(vals), (1, 4, 4, 2))
    y = snn.SubmConv2D(2, 5, 3, padding=1)(x)
    assert y.nnz() == 3   # submanifold keeps the active-site set
    np.testing.assert_array_equal(np.asarray(y.indices().numpy()),
                                  indices)


# ---------------------------------------------------------- distribution

def test_new_distributions_math():
    import paddle2_tpu.distribution as D
    paddle.seed(0)
    e = D.Exponential(paddle.to_tensor(np.array([2.0], np.float32)))
    np.testing.assert_allclose(
        float(e.log_prob(paddle.to_tensor(
            np.array([1.0], np.float32))).numpy()[0]),
        np.log(2) - 2, rtol=1e-5)
    g = D.Gamma(paddle.to_tensor(np.array([3.0], np.float32)),
                paddle.to_tensor(np.array([2.0], np.float32)))
    v = 1.7
    exp_lp = 3 * np.log(2) + 2 * np.log(v) - 2 * v - math.lgamma(3)
    np.testing.assert_allclose(
        float(g.log_prob(paddle.to_tensor(
            np.array([v], np.float32))).numpy()[0]), exp_lp, rtol=1e-3)
    c = D.Cauchy(paddle.to_tensor(np.array([1.0], np.float32)),
                 paddle.to_tensor(np.array([2.0], np.float32)))
    np.testing.assert_allclose(
        float(c.cdf(paddle.to_tensor(
            np.array([1.0], np.float32))).numpy()[0]), 0.5, atol=1e-6)
    b = D.Binomial(paddle.to_tensor(np.array([5.0], np.float32)),
                   paddle.to_tensor(np.array([0.3], np.float32)))
    tot = sum(float(np.exp(b.log_prob(paddle.to_tensor(
        np.array([float(k)], np.float32))).numpy()[0]))
        for k in range(6))
    np.testing.assert_allclose(tot, 1.0, rtol=1e-3)


def test_mvn_independent_lkj():
    import paddle2_tpu.distribution as D
    paddle.seed(0)
    cov = np.array([[2.0, 0.5], [0.5, 1.0]], np.float32)
    mvn = D.MultivariateNormal(paddle.to_tensor(np.zeros(2, np.float32)),
                               paddle.to_tensor(cov))
    x = np.array([0.3, -0.2], np.float32)
    exp = -0.5 * (x @ np.linalg.inv(cov) @ x) - 0.5 * np.log(
        (2 * np.pi) ** 2 * np.linalg.det(cov))
    np.testing.assert_allclose(
        float(mvn.log_prob(paddle.to_tensor(x)).numpy()), exp, rtol=1e-4)
    emp = np.cov(np.asarray(mvn.sample([20000]).numpy()).T)
    np.testing.assert_allclose(emp, cov, atol=0.08)
    n = D.Normal(paddle.to_tensor(np.zeros((3, 4), np.float32)),
                 paddle.to_tensor(np.ones((3, 4), np.float32)))
    lp = D.Independent(n, 1).log_prob(
        paddle.to_tensor(np.zeros((3, 4), np.float32)))
    np.testing.assert_allclose(lp.numpy(), 4 * -0.5 * np.log(2 * np.pi),
                               rtol=1e-5)
    L = np.asarray(D.LKJCholesky(3, 1.5).sample([50]).numpy())
    R = L @ np.swapaxes(L, -1, -2)
    np.testing.assert_allclose(np.diagonal(R, axis1=-2, axis2=-1), 1.0,
                               atol=1e-5)


# ------------------------------------------------------------ transforms

def test_transform_functionals_identities():
    import paddle2_tpu.vision.transforms as T
    from paddle2_tpu.vision.transforms import functional as F
    img = (np.random.RandomState(0).rand(16, 16, 3) * 255).astype(
        np.uint8)
    np.testing.assert_allclose(F.adjust_hue(img, 0.0).astype(float),
                               img.astype(float), atol=1.5)
    g = F.adjust_saturation(img, 0.0)
    assert np.allclose(g[..., 0], g[..., 1], atol=1.0)
    np.testing.assert_allclose(
        F.affine(img, 0.0, (0, 0), 1.0, (0.0, 0.0)).astype(float),
        img.astype(float), atol=1e-3)
    pts = [(0, 0), (15, 0), (15, 15), (0, 15)]
    np.testing.assert_allclose(
        F.perspective(img, pts, pts).astype(float), img.astype(float),
        atol=1e-3)
    r = F.affine(img[:, :, 0], 90.0, (0, 0), 1.0, (0.0, 0.0))
    np.testing.assert_allclose(r.astype(float),
                               np.rot90(img[:, :, 0], 3), atol=1e-2)
    er = T.RandomErasing(prob=1.0)._apply_image(img.copy())
    assert (er != img).any()
    assert T.ColorJitter(0.2, 0.2, 0.2, 0.1)._apply_image(img).shape \
        == img.shape


# ------------------------------------------------------------- fft/audio

def test_hfft_family_round_trips():
    y = np.random.RandomState(0).randn(4, 8).astype(np.float32)
    back = paddle.fft.hfft2(paddle.fft.ihfft2(paddle.to_tensor(y)))
    np.testing.assert_allclose(back.numpy(), y, rtol=1e-4, atol=1e-4)
    yn = np.random.RandomState(1).randn(3, 4, 8).astype(np.float32)
    bn = paddle.fft.hfftn(paddle.fft.ihfftn(paddle.to_tensor(yn),
                                            axes=(0, 1, 2)),
                          axes=(0, 1, 2))
    np.testing.assert_allclose(bn.numpy(), yn, rtol=1e-4, atol=1e-4)


def test_audio_wav_roundtrip(tmp_path):
    sr = 8000
    t = np.linspace(0, 1, sr, dtype=np.float32)
    wav = (0.5 * np.sin(2 * np.pi * 440 * t))[None]
    p = str(tmp_path / "a.wav")
    paddle.audio.save(p, paddle.to_tensor(wav), sr)
    info = paddle.audio.info(p)
    assert (info.sample_rate, info.num_channels,
            info.bits_per_sample) == (sr, 1, 16)
    back, sr2 = paddle.audio.load(p)
    assert sr2 == sr
    np.testing.assert_allclose(back.numpy(), wav, atol=1e-3)
    with pytest.raises(RuntimeError, match="egress"):
        paddle.audio.datasets.ESC50()


def test_text_local_datasets(tmp_path):
    import paddle2_tpu.text as text
    f = tmp_path / "ratings"
    f.write_text("1::10::4.5::99\n2::20::3.0::98\n")
    ml = text.Movielens(str(f))
    assert ml[0] == (1, 10, 4.5) and len(ml) == 2
    f2 = tmp_path / "corpus"
    f2.write_text("hello world foo\n")
    ng = text.Imikolov(str(f2), window_size=3)
    assert ng[0] == ("<s>", "hello", "world")
    f3 = tmp_path / "pairs"
    f3.write_text("the cat\tle chat\n")
    wmt = text.WMT14(str(f3))
    assert wmt[0] == (["the", "cat"], ["le", "chat"])


# ------------------------------------------------------- nn.utils / misc

def test_weight_and_spectral_norm():
    import paddle2_tpu.nn as nn
    from paddle2_tpu.nn.utils import (parameters_to_vector,
                                      remove_weight_norm,
                                      spectral_norm,
                                      vector_to_parameters, weight_norm)
    paddle.seed(0)
    lin = nn.Linear(4, 3)
    vec = parameters_to_vector(lin.parameters())
    assert tuple(vec.shape) == (15,)
    vector_to_parameters(vec * 0 + 1.0, lin.parameters())
    np.testing.assert_allclose(lin.weight.numpy(), 1.0)
    lin2 = nn.Linear(4, 4)
    weight_norm(lin2, dim=0)
    _ = lin2(paddle.randn([2, 4]))
    assert "weight_v" in dict(lin2.named_parameters())
    remove_weight_norm(lin2)
    lin3 = nn.Linear(4, 4)
    spectral_norm(lin3)
    _ = lin3(paddle.randn([2, 4]))
    s = np.linalg.svd(lin3.weight.numpy(), compute_uv=False)[0]
    assert abs(s - 1.0) < 0.25


def test_bilinear_initializer_and_device_helpers():
    from paddle2_tpu.nn.initializer import Bilinear
    p = paddle.zeros([2, 2, 4, 4])
    p.stop_gradient = False
    Bilinear()(p)
    w = p.numpy()
    assert w.max() <= 1.0 and w[0, 0, 1, 1] > 0.3
    # center-symmetric stencil
    np.testing.assert_allclose(w[0, 0], w[0, 0][::-1, ::-1], rtol=1e-5)
    import paddle2_tpu.device as dev
    assert dev.get_cudnn_version() is None
    assert dev.is_compiled_with_distribute()
    assert not dev.is_compiled_with_cinn()
    with dev.stream_guard(None):
        pass
    with pytest.raises(NotImplementedError):
        dev.XPUPlace(0)


def test_fleet_classes_and_data_generator():
    import paddle2_tpu.distributed.fleet as fleet
    rm = fleet.PaddleCloudRoleMaker()
    assert rm.is_worker() and not rm.is_server()
    assert fleet.UserDefinedRoleMaker(current_id=2,
                                      worker_num=4).worker_index() == 2

    class Gen(fleet.MultiSlotDataGenerator):
        def generate_sample(self, line):
            def g():
                yield [("slot1", [1, 2]), ("slot2", [3])]
            return g

    assert Gen().run_from_memory(["x"]) == ["2 1 2 1 3"]
    f = fleet.Fleet()
    assert f.is_worker() and f.util.get_file_shard(["a"]) == ["a"]


def test_inplace_index_ops_and_shufflenet_variant():
    x = paddle.to_tensor(np.zeros((3, 2), np.float32))
    paddle.index_add_(x, paddle.to_tensor(np.array([0, 2])), 0,
                      paddle.to_tensor(np.ones((2, 2), np.float32)))
    np.testing.assert_allclose(x.numpy(), [[1, 1], [0, 0], [1, 1]])
    paddle.index_fill_(x, paddle.to_tensor(np.array([1])), 0, 7.0)
    np.testing.assert_allclose(x.numpy()[1], [7, 7])
    m = paddle.vision.models.shufflenet_v2_x0_33()
    y = m(paddle.randn([1, 3, 64, 64]))
    assert tuple(y.shape) == (1, 1000)


def test_quantization_bases_and_quanter_registry():
    from paddle2_tpu.quantization import (BaseObserver, BaseQuanter,
                                          _QUANTER_REGISTRY, quanter)

    @quanter("R5TestQuanter")
    class TQ(BaseQuanter):
        pass

    assert _QUANTER_REGISTRY["R5TestQuanter"] is TQ
    assert issubclass(TQ, BaseQuanter)
    assert isinstance(paddle.quantization.AbsmaxObserver(), object)


def test_review_regressions_r5b():
    import jax.numpy as jnp
    import paddle2_tpu.distribution as D
    # Chi2 with INTEGER df keeps float math
    c2 = D.Chi2(paddle.to_tensor(np.array([4])))
    np.testing.assert_allclose(np.asarray(c2.mean.numpy()), [4.0])
    # LKJ dim=2, eta=1 is the uniform prior: diagonal exponent 0, so
    # log_prob is the (constant) -log(normalizer) for any valid L
    lkj = D.LKJCholesky(2, 1.0)
    def lp(theta):
        L = np.array([[1.0, 0.0],
                      [np.cos(theta), np.sin(theta)]], np.float32)
        return float(lkj.log_prob(paddle.to_tensor(L)).numpy())
    np.testing.assert_allclose(lp(0.3), lp(1.2), rtol=1e-5)
    # heter reindex with two edge types
    import paddle2_tpu.geometric as geo
    src, dst, nodes = geo.reindex_heter_graph(
        paddle.to_tensor(np.array([0, 1])),
        [paddle.to_tensor(np.array([5, 6])),
         paddle.to_tensor(np.array([7]))],
        [paddle.to_tensor(np.array([1, 1], np.int32)),
         paddle.to_tensor(np.array([1, 0], np.int32))])
    assert dst.numpy().tolist() == [0, 1, 0]
    assert nodes.numpy().tolist() == [0, 1, 5, 6, 7]
    # hfftn default covers ALL axes (3-D round trip already pinned; the
    # regression is that a 3-D array's axis 0 participates by default)
    y = np.random.RandomState(0).randn(3, 4, 8).astype(np.float32)
    b = paddle.fft.hfftn(paddle.fft.ihfftn(paddle.to_tensor(y)))
    np.testing.assert_allclose(b.numpy(), y, rtol=1e-4, atol=1e-4)
    # remove_weight_norm honors dim
    import paddle2_tpu.nn as nn
    from paddle2_tpu.nn.utils import remove_weight_norm, weight_norm
    lin = nn.Linear(4, 6)
    w0 = lin.weight.numpy().copy()
    weight_norm(lin, dim=1)
    _ = lin(paddle.randn([2, 4]))
    remove_weight_norm(lin)
    np.testing.assert_allclose(lin.weight.numpy(), w0, rtol=1e-5)
    # spectral_norm with zero power iterations uses the stored estimate
    from paddle2_tpu.nn.utils import spectral_norm
    lin2 = nn.Linear(4, 4)
    spectral_norm(lin2, n_power_iterations=0)
    _ = lin2(paddle.randn([2, 4]))   # must not raise
    # SubmConv without same-padding refuses instead of corrupting
    import paddle2_tpu.sparse as sp
    import paddle2_tpu.sparse.nn as snn
    x = sp.sparse_coo_tensor(
        paddle.to_tensor(np.array([[0], [3], [3]])),
        paddle.to_tensor(np.ones((1, 1), np.float32)), (1, 4, 4, 1))
    with pytest.raises(ValueError, match="preserve"):
        snn.SubmConv2D(1, 1, 3)(x)   # padding=0 shrinks the map


def test_review_regressions_r5c():
    import paddle2_tpu.nn as nn
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.nn.utils import (remove_weight_norm, spectral_norm,
                                      weight_norm)
    paddle.seed(0)
    # spectral_norm keeps TRAINING (weight_orig is the live parameter)
    lin = nn.Linear(6, 1)
    spectral_norm(lin)
    o = opt.Adam(learning_rate=0.05, parameters=lin.parameters())
    X = paddle.to_tensor(np.random.RandomState(0)
                         .randn(32, 6).astype(np.float32))
    Y = paddle.to_tensor(np.random.RandomState(1)
                         .randn(32, 1).astype(np.float32))
    first = last = None
    for _ in range(40):
        loss = ((lin(X) - Y) ** 2).mean()
        loss.backward()
        o.step()
        o.clear_grad()
        last = float(loss.numpy())
        first = first if first is not None else last
    assert last < 0.8 * first, (first, last)
    # remove_weight_norm de-registers the reparam params
    lin2 = nn.Linear(4, 4)
    weight_norm(lin2)
    remove_weight_norm(lin2)
    names = dict(lin2.named_parameters())
    assert "weight_v" not in names and "weight_g" not in names
    # sparse dense-conv output chains into SubmConv (site-indexed COO)
    import paddle2_tpu.sparse as sp
    import paddle2_tpu.sparse.nn as snn
    idx = np.array([[0, 0], [1, 2], [1, 3]])
    x = sp.sparse_coo_tensor(paddle.to_tensor(idx),
                             paddle.to_tensor(np.random.RandomState(2)
                                              .randn(2, 3)
                                              .astype(np.float32)),
                             (1, 4, 4, 3))
    y = snn.Conv2D(3, 5, 3, padding=1)(x)
    z = snn.SubmConv2D(5, 2, 3, padding=1)(y)   # must not corrupt
    assert np.asarray(z.values().numpy()).shape[-1] == 2
    # groups/dilation are honored (shape-level check)
    g = snn.Conv2D(4, 4, 3, padding=2, dilation=2, groups=2)
    xg = sp.sparse_coo_tensor(paddle.to_tensor(np.array([[0], [1], [1]])),
                              paddle.to_tensor(np.ones((1, 4), np.float32)),
                              (1, 4, 4, 4))
    assert g(xg).shape[-1] == 4
    # ColorJitter accepts (lo, hi) tuples; 4-element shear is honored
    import paddle2_tpu.vision.transforms as T
    img = (np.random.RandomState(0).rand(8, 8, 3) * 255).astype(np.uint8)
    cj = T.ColorJitter(brightness=(0.5, 1.5), hue=(-0.1, 0.1))
    assert cj._apply_image(img).shape == img.shape
    ra = T.RandomAffine(0, shear=(0, 0, 30, 30))
    out = ra._apply_image(img.astype(np.float32))
    assert (out != img).any()       # y-shear actually applied
    # Flowers validates label/image count at init
    import tempfile, os
    from PIL import Image
    d = tempfile.mkdtemp()
    for i in range(2):
        Image.fromarray(np.zeros((4, 4, 3), np.uint8)).save(
            os.path.join(d, f"im{i}.jpg"))
    lab = os.path.join(d, "labels.txt")
    open(lab, "w").write("1\n")
    with pytest.raises(ValueError, match="one entry per jpg"):
        paddle.vision.datasets.Flowers(data_file=d, label_file=lab)


def test_incubate_fused_functional_math():
    import paddle2_tpu.incubate.nn.functional as FF
    rng = np.random.RandomState(0)
    # swiglu single-input splits; fused LN matches manual
    y = FF.swiglu(paddle.to_tensor(rng.randn(2, 8).astype(np.float32)))
    assert tuple(y.shape) == (2, 4)
    x = paddle.to_tensor(rng.randn(2, 4, 8).astype(np.float32))
    w = paddle.to_tensor(np.ones(8, np.float32))
    b = paddle.to_tensor(np.zeros(8, np.float32))
    out = FF.fused_layer_norm(x, w, b, begin_norm_axis=2)
    a = np.asarray(x.numpy())
    mu = a.mean(-1, keepdims=True)
    var = a.var(-1, keepdims=True)
    np.testing.assert_allclose(out.numpy(), (a - mu) / np.sqrt(var + 1e-5),
                               rtol=1e-4, atol=1e-4)
    # residual form returns (out, residual_out)
    r = paddle.to_tensor(rng.randn(2, 4, 8).astype(np.float32))
    o2, res = FF.fused_layer_norm(x, w, b, begin_norm_axis=2, residual=r)
    np.testing.assert_allclose(res.numpy(), a + np.asarray(r.numpy()),
                               rtol=1e-5)
    # fused MHA runs; MultiTransformer stack finite
    qkvw = paddle.to_tensor(rng.randn(3, 2, 4, 8).astype(np.float32) * .1)
    lw = paddle.to_tensor(rng.randn(8, 8).astype(np.float32) * 0.1)
    o = FF.fused_multi_head_attention(x, qkvw, lw, pre_layer_norm=True,
                                      pre_ln_scale=w, pre_ln_bias=b,
                                      dropout_rate=0.0,
                                      attn_dropout_rate=0.0,
                                      training=False)
    assert tuple(o.shape) == (2, 4, 8)
    import paddle2_tpu.incubate.nn as inn
    mt = inn.FusedMultiTransformer(8, 2, 16, num_layers=2)
    mt.eval()
    assert np.isfinite(mt(x).numpy()).all()
    with pytest.raises(NotImplementedError, match="MoELayer"):
        FF.fused_moe(x, None, None, None)


def test_static_nn_builders():
    import paddle2_tpu.static as st
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(2, 6).astype(np.float32))
    y = st.nn.fc(x, 4, activation="relu")
    assert tuple(y.shape) == (2, 4) and (y.numpy() >= 0).all()
    img = paddle.to_tensor(rng.randn(1, 3, 8, 8).astype(np.float32))
    c = st.nn.conv2d(img, 6, 3, padding=1)
    assert tuple(c.shape) == (1, 6, 8, 8)
    assert tuple(st.nn.group_norm(c, 2).shape) == (1, 6, 8, 8)
    e = st.nn.embedding(paddle.to_tensor(np.array([[1, 2]])), (10, 4))
    assert tuple(e.shape) == (1, 2, 4)
    assert tuple(st.nn.bilinear_tensor_product(x, x, 3).shape) == (2, 3)
    # control flow evaluates the taken branch
    r = st.nn.cond(paddle.to_tensor(np.array([False])),
                   lambda: paddle.to_tensor(np.array([1.0])),
                   lambda: paddle.to_tensor(np.array([2.0])))
    assert float(r.numpy()[0]) == 2.0
    v = st.nn.while_loop(lambda t: t < 3, lambda t: t + 1,
                         [paddle.to_tensor(np.array([0.0]))])
    assert float(v[0].numpy()[0]) == 3.0
    with pytest.raises(NotImplementedError, match="LoD"):
        st.nn.sequence_pool(None)
    # fc under program_guard records and replays
    prog = st.Program()
    with st.program_guard(prog):
        ph = st.data("x", [2, 6], "float32")
        out = st.nn.fc(ph, 3)
    exe = st.Executor()
    res = exe.run(prog, feed={"x": rng.randn(2, 6).astype(np.float32)},
                  fetch_list=[out])
    assert res[0].shape == (2, 3)


def test_incubate_autograd_namespace():
    import paddle2_tpu.incubate as inc
    assert inc.autograd.prim_enabled()
    inc.autograd.disable_prim()
    assert not inc.autograd.prim_enabled()
    inc.autograd.enable_prim()
    out, jv = inc.autograd.jvp(
        lambda t: t * t,
        paddle.to_tensor(np.array([3.0], np.float32)),
        paddle.to_tensor(np.array([1.0], np.float32)))
    np.testing.assert_allclose(jv.numpy(), [6.0], rtol=1e-5)


def test_review_regressions_r5d():
    import paddle2_tpu.static as st
    import paddle2_tpu.incubate.nn.functional as FF
    rng = np.random.RandomState(0)
    # layer_norm handles multi-dim normalized shape
    x3 = paddle.to_tensor(rng.randn(2, 3, 4).astype(np.float32))
    ln = st.nn.layer_norm(x3)     # begin_norm_axis=1 over (3, 4)
    a = np.asarray(x3.numpy())
    mu = a.reshape(2, -1).mean(1).reshape(2, 1, 1)
    sd = a.reshape(2, -1).std(1).reshape(2, 1, 1)
    np.testing.assert_allclose(ln.numpy(), (a - mu) / sd, rtol=1e-3,
                               atol=1e-3)
    # conv2d_transpose derives filter_size from output_size
    img = paddle.to_tensor(rng.randn(1, 3, 8, 8).astype(np.float32))
    up = st.nn.conv2d_transpose(img, 4, output_size=[16, 16], stride=2)
    assert tuple(up.shape)[2:] == (16, 16)
    # unique builder param names
    st.nn._name_counter.clear()
    x = paddle.to_tensor(rng.randn(2, 6).astype(np.float32))
    prog = st.Program()
    with st.program_guard(prog):
        ph = st.data("x", [2, 6], "float32")
        a1 = st.nn.fc(ph, 4)
        a2 = st.nn.fc(a1, 4)
    names = [getattr(t, "name", "") for t in prog._live.values()
             if getattr(t, "stop_gradient", True) is False
             and getattr(t, "name", "")]   # params only (not activations)
    assert len(names) == len(set(names)), names
    # fused_bias_dropout_residual_layer_norm works with defaults
    h = paddle.to_tensor(rng.randn(2, 4, 8).astype(np.float32))
    r = paddle.to_tensor(rng.randn(2, 4, 8).astype(np.float32))
    out = FF.fused_bias_dropout_residual_layer_norm(h, r, training=False)
    assert np.isfinite(np.asarray(out[0].numpy()
                                  if isinstance(out, tuple)
                                  else out.numpy())).all()
    # varlen attention applies the additive mask
    q = paddle.to_tensor(rng.randn(1, 1, 4, 8).astype(np.float32))
    m0 = FF.variable_length_memory_efficient_attention(
        q, q, q, paddle.to_tensor(np.array([4])),
        paddle.to_tensor(np.array([4])))
    big = np.zeros((1, 1, 4, 4), np.float32)
    big[..., 0] = 100.0            # force all attention onto key 0
    m1 = FF.variable_length_memory_efficient_attention(
        q, q, q, paddle.to_tensor(np.array([4])),
        paddle.to_tensor(np.array([4])), mask=paddle.to_tensor(big))
    assert not np.allclose(m0.numpy(), m1.numpy())
    np.testing.assert_allclose(m1.numpy()[0, 0, 1],
                               np.asarray(q.numpy())[0, 0, 0], atol=1e-3)
    # cache_kv raises loudly
    with pytest.raises(NotImplementedError, match="cache"):
        FF.fused_multi_head_attention(
            paddle.to_tensor(rng.randn(1, 2, 8).astype(np.float32)),
            paddle.to_tensor(rng.randn(3, 2, 4, 8).astype(np.float32)),
            paddle.to_tensor(rng.randn(8, 8).astype(np.float32)),
            cache_kv=paddle.zeros([2]))
    # trans_qkvw=False layout accepted
    w_alt = paddle.to_tensor(rng.randn(8, 3, 2, 4).astype(np.float32)
                             * 0.1)
    lw = paddle.to_tensor(rng.randn(8, 8).astype(np.float32) * 0.1)
    ones = paddle.to_tensor(np.ones(8, np.float32))
    zeros = paddle.to_tensor(np.zeros(8, np.float32))
    h8 = paddle.to_tensor(rng.randn(1, 3, 8).astype(np.float32))
    out_alt = FF.fused_multi_transformer(
        h8, [ones], [zeros], [w_alt], None, [lw], None, [ones], [zeros],
        [paddle.to_tensor(rng.randn(8, 16).astype(np.float32) * 0.1)],
        None,
        [paddle.to_tensor(rng.randn(16, 8).astype(np.float32) * 0.1)],
        None, trans_qkvw=False, training=False)
    assert np.isfinite(out_alt.numpy()).all()
