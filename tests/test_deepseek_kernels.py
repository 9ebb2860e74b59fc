"""DeepSeek-V2: the router, the expert shares, the kernels (paged latent
decode, flash with a value width of its own, the head-major entry) and the
staged reference (moved from ``test_deepseek.py``; harness: ``served.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.incubate.moe import softmax_group_limited_route
from paddle2_tpu.kernels import attention, pallas_flash
from paddle2_tpu.kernels.attention import _sdpa_xla
from paddle2_tpu.models import DeepseekV2ForCausalLM, deepseek_v2_tiny
from paddle2_tpu.serving import paged_attention as pa
from served import shared_programs  # noqa: F401
from served import deepseek_bench as bench

pytestmark = pytest.mark.usefixtures("shared_programs")


def whole(bench):
    """The same tiny model UNCUT: all 8 experts held."""
    cfg = dict(bench["cfg"], n_routed_experts=8, num_experts=8,
               held_group=None)
    return cfg


# ------------------------------------------------------------ the router
def plain_group_limited(p, k, n_group, topk_group):
    """The published selection, row by row in numpy."""
    out = []
    for row in p:
        groups = row.reshape(n_group, -1)
        kept = np.argsort(-groups.max(-1), kind="stable")[:topk_group]
        masked = np.zeros_like(row)
        for g in kept:
            lo = g * groups.shape[1]
            masked[lo:lo + groups.shape[1]] = row[lo:lo + groups.shape[1]]
        out.append(np.argsort(-masked, kind="stable")[:k])
    return np.asarray(out)


def test_group_limited_route_is_the_published_selection():
    rng = np.random.default_rng(21)
    a = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)
    ids, w = softmax_group_limited_route(a, gate, 4, 6, 2, False, 16.0)
    p = np.asarray(jax.nn.softmax(a @ gate, -1))
    want = plain_group_limited(p, 4, 6, 2)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want, -1))
    # the weights are the probabilities themselves, times the scale
    np.testing.assert_allclose(
        w, 16.0 * np.take_along_axis(p, np.asarray(ids), -1), rtol=1e-6)
    # the limit binds: plain top 4 reaches more than two groups somewhere
    top = np.argsort(-p, -1)[:, :4]
    assert (np.sort(top, -1) != np.sort(want, -1)).any()
    assert all(len(set(r // 4)) <= 2 for r in np.asarray(ids))
    # normalised weights sum to the scale
    _, wn = softmax_group_limited_route(a, gate, 4, 6, 2, True, 2.0)
    np.testing.assert_allclose(wn.sum(-1), 2.0, rtol=1e-6)


def test_forced_experts_and_their_deficit(bench):
    """Handed its own choice the reference reads a deficit of 0; handed
    plain top-k (the group limit ignored) or a wrong expert it reads
    how far off that is."""
    cfg, ref = whole(bench), bench["ref"]
    rng = np.random.default_rng(23)
    a = jnp.asarray(rng.normal(size=(1, 50, 64)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(64, 8)) * 0.3, jnp.float32)
    from reference.common import matmul_f32
    idx, w, deficit = ref.route(a, gate, cfg, matmul_f32)
    assert float(deficit.max()) == 0.0
    _, w2, d2 = ref.route(a, gate, cfg, matmul_f32, forced=idx)
    assert float(d2.max()) == 0.0
    np.testing.assert_allclose(w2, w)
    p = np.asarray(jax.nn.softmax(a @ gate, -1))
    np.testing.assert_allclose(
        w, cfg["routed_scaling_factor"]
        * np.take_along_axis(p, np.asarray(idx), -1), rtol=1e-6)
    # plain top 2 of 8 where it reaches a group the limit drops
    top = jnp.asarray(np.argsort(-p, -1)[..., :2])
    _, _, d3 = ref.route(a, gate, dict(cfg, topk_group=1), matmul_f32,
                         forced=top)
    own, _, _ = ref.route(a, gate, dict(cfg, topk_group=1), matmul_f32)
    differs = (np.sort(top, -1) != np.sort(own, -1)).any(-1)
    assert differs.any()
    assert (np.asarray(d3)[differs] > 0).all()
    assert (np.asarray(d3)[~differs] == 0).all()
    # the worst expert in the best expert's place
    worst = jnp.asarray(np.argsort(p, -1)[..., :2])
    _, _, d4 = ref.route(a, gate, cfg, matmul_f32, forced=worst)
    assert float(d4.min()) > 0


def test_router_is_float32_under_bf16_parameters():
    rng = np.random.default_rng(24)
    a = jnp.asarray(rng.normal(size=(16, 32)), jnp.bfloat16)
    gate = jnp.asarray(rng.normal(size=(32, 8)), jnp.bfloat16)
    _, w = softmax_group_limited_route(a, gate, 2, 4, 2)
    assert w.dtype == jnp.float32


def test_expert_shares_add_up_to_the_whole_layer(bench):
    """The guide's tie of the share to the model: the parts that all
    ``n_group`` shares of one expert layer give, the shared experts
    counted once, add up to the uncut reference's layer — for the
    program's layer and for the reference's own share alike."""
    cfg, ref = whole(bench), bench["ref"]
    from reference.common import matmul_f32
    params = bench["make_weights"](ref.leaf_specs(cfg), 31, jnp.float32)
    a = jnp.asarray(np.random.default_rng(31).normal(size=(40, 64)),
                    jnp.float32)
    want, used, _ = ref.experts_ff(a[None], params, 1, cfg, matmul_f32)
    shared = ref.swiglu(a, params["l1_sw1"], params["l1_sw3"],
                        params["l1_sw2"], matmul_f32)
    total_prog = total_ref = 0.0
    assigned = 0
    for g in range(4):
        mcfg = deepseek_v2_tiny(held_group=g)
        layer = DeepseekV2ForCausalLM(mcfg).model.layers[1].mlp
        lo, n = mcfg.held_experts
        for name, leaf in (("shared_experts.w1.weight", "sw1"),
                           ("shared_experts.w3.weight", "sw3"),
                           ("shared_experts.w2.weight", "sw2"),
                           ("experts.gate_weight", "gate")):
            obj = layer
            for part in name.split("."):
                obj = getattr(obj, part)
            obj._replace_data(params[f"l1_{leaf}"])
        for name in ("w1", "w3", "w2"):
            getattr(layer.experts, name)._replace_data(
                params[f"l1_{name}"][lo:lo + n])
        out, record = layer.run(a, interpret=True)
        total_prog = total_prog + (out - shared)
        assigned += int(record[0])
        share_cfg = dict(bench["cfg"], held_group=g)
        share = {k: (v[lo:lo + n] if k in ("l1_w1", "l1_w3", "l1_w2")
                     else v) for k, v in params.items()}
        part, used_g, _ = ref.experts_ff(a[None], share, 1, share_cfg,
                                         matmul_f32)
        np.testing.assert_array_equal(used_g, used)
        total_ref = total_ref + (part[0] - shared)
    assert assigned == 40 * 2           # every assignment on some share
    np.testing.assert_allclose(total_prog + shared, want[0], atol=2e-5)
    np.testing.assert_allclose(total_ref + shared, want[0], atol=2e-5)


def test_sliced_vocabulary_is_the_whole_heads_rows(bench):
    """A chip's slice of the vocabulary is a smaller vocabulary: on ids
    of the slice its logits are the whole head's columns of the slice."""
    full_cfg = deepseek_v2_tiny(held_group=0)
    cut_cfg = deepseek_v2_tiny(held_group=0, vocab_size=128)
    full, cut = DeepseekV2ForCausalLM(full_cfg), DeepseekV2ForCausalLM(cut_cfg)
    state = dict(full.named_parameters())
    for name, p in cut.named_parameters():
        src = state[name]._data
        if name == "model.embed_tokens.weight":
            src = src[:128]
        elif name == "lm_head.weight":
            src = src[:, :128]
        p._replace_data(src)
    ids = np.random.default_rng(33).integers(0, 128, (1, 24))
    got = cut(paddle.to_tensor(ids))._data
    want = full(paddle.to_tensor(ids))._data[..., :128]
    np.testing.assert_allclose(got, want, atol=1e-6)


# ------------------------------------------------------------ the kernels
@pytest.mark.parametrize("ctx", [[1, 127, 128], [129, 300, 384],
                                 [0, 256, 257]])
def test_paged_mla_decode_against_dense_softmax(monkeypatch, ctx):
    """The streaming body (interpreted) against one dense softmax over
    the gathered latents, at contexts on both sides of a compute
    block's edge (blocks of 16 pages of 8: 128 tokens; 3 blocks)."""
    monkeypatch.setattr(pa, "_MLA_BLOCK_BYTES", 1)
    rng = np.random.default_rng(0)
    L, N, bs, rank, dr, H, B, P = 2, 160, 8, 32, 8, 4, 3, 48
    W = pa.mla_row_width(rank, dr)
    assert pa.mla_pages_per_block(P, bs, W, jnp.float32) == 16
    assert pa.mla_pages_per_copy(P, bs, W, jnp.float32) == 16
    pool = np.zeros((L, N, bs, W), np.float32)
    pool[..., :rank + dr] = rng.normal(size=(L, N, bs, rank + dr))
    pool = jnp.asarray(pool)
    qc = jnp.asarray(rng.normal(size=(B, H, rank)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(B, H, dr)), jnp.float32)
    bt = rng.permutation(np.arange(1, N))[:B * P].reshape(B, P)
    ctx = np.asarray(ctx, np.int32)
    got = pa.paged_mla_decode(qc, qr, pool, bt, ctx, 0.2, interpret=True,
                              layer=1)
    want = pa.paged_mla_reference(qc, qr, pool[1], bt, ctx, 0.2)
    np.testing.assert_allclose(got[ctx > 0], want[ctx > 0], atol=2e-6)
    assert not np.asarray(got[ctx == 0]).any()


def test_paged_mla_decode_bf16_pool():
    rng = np.random.default_rng(1)
    N, bs, rank, dr, H, B, P = 40, 16, 128, 64, 8, 2, 8
    W = pa.mla_row_width(rank, dr)
    pool = np.zeros((1, N, bs, W), np.float32)
    pool[..., :rank + dr] = rng.normal(size=(1, N, bs, rank + dr))
    pool = jnp.asarray(pool, jnp.bfloat16)
    qc = jnp.asarray(rng.normal(size=(B, H, rank)), jnp.bfloat16)
    qr = jnp.asarray(rng.normal(size=(B, H, dr)), jnp.bfloat16)
    bt = rng.permutation(np.arange(1, N))[:B * P].reshape(B, P)
    ctx = np.asarray([100, 37], np.int32)
    got = pa.paged_mla_decode(qc, qr, pool, bt, ctx, 0.1, interpret=True)
    want = pa.paged_mla_reference(qc, qr, pool[0], bt, ctx, 0.1)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


@pytest.mark.parametrize("walk", [True, False])
def test_flash_forward_value_width_differs(monkeypatch, walk):
    """Query/key heads of 24 lanes against value heads of 16, causal,
    with a scale of its own: both forward bodies against the dense
    computation; the backward says it is not there."""
    if not walk:
        monkeypatch.setattr(pallas_flash, "WALK_VMEM_BYTES", 0)
    monkeypatch.setattr(pallas_flash, "_JIT_CACHE", {})
    rng = np.random.default_rng(2)
    q, k = (jnp.asarray(rng.normal(size=(1, 1024, 2, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, 1024, 2, 16)), jnp.float32)
    assert (pallas_flash._walks(1024, 1024, 24, jnp.float32, 1, 512, 512,
                                False, 16) is not None) == walk

    def flash(q, k, v):
        return pallas_flash.flash_attention_bshd(
            q, k, v, causal=True, scale=0.3, block_q=256, block_k=256,
            interpret=True)

    got = flash(q, k, v)
    assert got.shape == (1, 1024, 2, 16)
    np.testing.assert_allclose(
        got, _sdpa_xla(q, k, v, causal=True, scale=0.3), atol=2e-6)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: flash(q, k, v).sum())(q)


def swapped(x):
    return jnp.swapaxes(x, 1, 2)


@pytest.mark.parametrize("walk", [True, False])
def test_head_major_flash_entry_is_the_bshd_one_on_swapped_operands(
        monkeypatch, walk):
    """``flash_attention_bhsd`` on (batch, heads, seq, dim) operands:
    the very values ``flash_attention_bshd`` gives on the swapped ones,
    value width != query width, both forward bodies; a mask block that
    is no power of two is refused as there."""
    if not walk:
        monkeypatch.setattr(pallas_flash, "WALK_VMEM_BYTES", 0)
    monkeypatch.setattr(pallas_flash, "_JIT_CACHE", {})
    rng = np.random.default_rng(4)
    q, k = (jnp.asarray(rng.normal(size=(2, 3, 512, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 3, 512, 16)), jnp.float32)
    for block in (1, 4):
        kw = dict(causal=True, scale=0.3, causal_block=block,
                  interpret=True)
        got = pallas_flash.flash_attention_bhsd(q, k, v, **kw)
        assert got.shape == (2, 3, 512, 16)
        np.testing.assert_array_equal(got, swapped(
            pallas_flash.flash_attention_bshd(*map(swapped, (q, k, v)),
                                              **kw)))
    assert sorted(key[0] for key in pallas_flash._JIT_CACHE) == \
        ["bhsd", "bhsd", "bshd", "bshd"]
    with pytest.raises(ValueError, match="power of two"):
        pallas_flash.flash_attention_bhsd(q, k, v, causal=True,
                                          causal_block=3, interpret=True)


@pytest.mark.parametrize("case", ["unsupported", "not_on_tpu", "on_tpu"])
def test_head_major_entry_takes_the_xla_path_where_bshd_does(monkeypatch,
                                                              case):
    """The two places attention leaves the kernel: a length no 8-row
    tile divides (``supported()`` false, inside the flash entry) and a
    host that is no TPU (``use_pallas``, in ``attention_bhsd`` as in
    ``scaled_dot_product_attention``); there the head-major entry is the
    XLA path on swapped operands, and on a TPU it is the kernel."""
    rng = np.random.default_rng(5)
    S = 1001 if case == "unsupported" else 1024
    q, k = (jnp.asarray(rng.normal(size=(1, 2, S, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, 2, S, 16)), jnp.float32)
    want = swapped(_sdpa_xla(*map(swapped, (q, k, v)), causal=True,
                             scale=0.3))
    monkeypatch.setattr(pallas_flash, "_JIT_CACHE", {})
    calls = []
    flash = pallas_flash._flash
    monkeypatch.setattr(pallas_flash, "_flash",
                        lambda *a: (calls.append(a[3:]), flash(*a))[1])
    bshd_shape = (1, S, 2, 24)
    if case == "unsupported":
        assert not pallas_flash.supported(bshd_shape, bshd_shape)
        got = pallas_flash.flash_attention_bhsd(q, k, v, causal=True,
                                                scale=0.3, interpret=True)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            swapped(got), pallas_flash.flash_attention_bshd(
                *map(swapped, (q, k, v)), causal=True, scale=0.3,
                interpret=True))
    else:
        assert not attention.use_pallas(bshd_shape)      # this host
        if case == "on_tpu":
            monkeypatch.setattr(attention, "on_tpu", lambda: True)
            assert attention.use_pallas(bshd_shape)
            assert not attention.use_pallas((1, 1023, 2, 24))
        got = attention.attention_bhsd(q, k, v, causal=True, scale=0.3)
        if case == "on_tpu":
            np.testing.assert_allclose(got, want, atol=2e-6)
        else:
            np.testing.assert_array_equal(got, want)
    assert len(calls) == (case == "on_tpu")


def test_walk_bytes_count_the_value_width():
    """What decides the forward body: at the published widths (192 / 128
    lanes, 128 heads) the 2,048- and 3,072-token prompts walk, the
    5,120-token one takes the grid; equal widths reckon as before."""
    for seq, walks in ((2048, True), (3072, True), (5120, False)):
        assert (pallas_flash._walks(seq, seq, 192, jnp.bfloat16, 1, 1024,
                                    1024, False, 128) is not None) == walks
    assert pallas_flash._walk_bytes(1024, 1024, 64, 2, 512, 512, False) == \
        pallas_flash._walk_bytes(1024, 1024, 64, 2, 512, 512, False, 64) == \
        2 * 4 * 1024 * 128 * 2 + 512 * 1024 * 10


# -------------------------------------------------- the staged reference
def test_staged_reference_draws_the_same_weights_and_result(bench):
    """``drivers/serve_routed_staged``: a stage's leaves drawn alone are
    ``weights.make_weights``' own values, and the model computed stage
    by stage, each with only its leaves at hand, is ``forward``."""
    staged = bench["load_module"]("drivers", "serve_routed_staged")
    cfg, ref = bench["cfg"], bench["ref"]
    specs = ref.leaf_specs(cfg)
    seed = 2 ** 31 + 5
    params = bench["make_weights"](specs, seed, jnp.float32)
    names = [n for _, leaves in ref.stage_leaves(cfg) for n in leaves]
    assert sorted(names) == sorted(specs)
    for _, leaves in ref.stage_leaves(cfg)[1:3]:
        some = staged.draw(specs, seed, leaves, jnp.float32)
        for n in leaves:
            np.testing.assert_array_equal(some[n], params[n])
    ids = jnp.asarray(np.random.default_rng(5).integers(1, 503, (1, 32)))
    want, used, deficit = ref.forward(params, ids, cfg)
    from reference.common import matmul_f32
    got = staged.staged_forward(ref, cfg, seed, [ids], matmul_f32)[0]
    np.testing.assert_allclose(got[0], want, atol=1e-6)
    np.testing.assert_array_equal(got[1], used)
    np.testing.assert_allclose(got[2], deficit, atol=1e-7)
