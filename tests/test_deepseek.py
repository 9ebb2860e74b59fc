"""DeepSeek-V2 (``models/deepseek.py``: latent attention, shared experts
beside group-limited softmax-routed experts) against the benchmark's
plain reference (``benchmark/reference/deepseek_v2.py``), tiny sizes
with every ratio kept, float32 on the CPU, Pallas kernels interpreted,
seeded random weights placed through the benchmark's own layout
(``benchmark/configs/deepseek-v2.json``).

Tolerances. Model and reference compute the same float32 mathematics in
another order (the ABSORBED association at decode against the expanded
one, grouped matmul over sorted rows against a scan over experts, an
online softmax per compute block against one row), so logits of scale
~1 agree to a few 1e-6; ``LOGIT_TOL`` = 5e-5 leaves an order of
magnitude of room and is two orders under what a bf16-for-f32
substitution gives (``test_tolerance_rejects_bf16``)."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.kernels import attention, pallas_flash
from paddle2_tpu.kernels.attention import _sdpa_xla
from paddle2_tpu.models import (DeepseekV2Config, DeepseekV2ForCausalLM,
                                deepseek_v2_tiny)
from paddle2_tpu.models._decoder import rope_tables, yarn_inv_freq, yarn_mscale
from served import LOGIT_TOL, build, shared_programs  # noqa: F401
from served import deepseek_bench as bench

pytestmark = pytest.mark.usefixtures("shared_programs")


# ------------------------------------------------------------- the model
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_forward_logits_match_reference(bench, seed):
    model, _, params = build(bench, seed)
    ids = np.random.default_rng(seed % 2 ** 32).integers(1, 503, (2, 40))
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    want = np.asarray(bench["ref"].logits(params, jnp.asarray(ids),
                                          bench["cfg"]))
    assert float(np.abs(got - want).max()) <= LOGIT_TOL


def test_tolerance_rejects_bf16(bench):
    """The reference with bf16 operands in the program's place is far
    outside the tolerance: the comparison would notice a precision
    below the one stated."""
    from reference.common import MATMULS
    _, _, params = build(bench, 4)
    ids = jnp.asarray(np.random.default_rng(4).integers(1, 503, (1, 40)))
    exact = bench["ref"].logits(params, ids, bench["cfg"])
    low = bench["ref"].logits(params, ids, bench["cfg"],
                              MATMULS["bfloat16"])
    assert float(jnp.abs(exact - low).max()) > 100 * LOGIT_TOL


def test_config_takes_published_keys_and_the_held_group(bench):
    _, mcfg, _ = build(bench, 1)
    assert isinstance(mcfg, DeepseekV2Config)
    # the router keeps its published width; the group says what is held
    assert (mcfg.n_routed_experts, mcfg.held_group) == (8, 0)
    assert mcfg.held_experts == (0, 2)
    assert deepseek_v2_tiny(held_group=3).held_experts == (6, 2)
    assert DeepseekV2Config().held_experts is None
    assert DeepseekV2Config(held_group=7).held_experts == (140, 20)
    with pytest.raises(ValueError):
        DeepseekV2Config(held_group=8)
    with pytest.raises(ValueError):
        DeepseekV2Config(topk_method="greedy")
    with pytest.raises(ValueError):
        DeepseekV2Config(q_lora_rank=None)


def test_yarn_tables_and_scale(bench):
    """The program's rotary tables are the reference's, and the softmax
    scale carries mscale(40, 0.707)^2 at the published settings."""
    cfg = bench["cfg"]
    ys = cfg["rope_scaling"]
    pos = jnp.arange(0, 200, 7)
    inv = yarn_inv_freq(cfg["qk_rope_head_dim"], cfg["rope_theta"],
                        ys["factor"], ys["original_max_position_embeddings"],
                        ys["beta_fast"], ys["beta_slow"])
    cos, sin = rope_tables(pos, cfg["qk_rope_head_dim"], cfg["rope_theta"],
                           inv)
    want_cos, want_sin = bench["ref"].rope_tables(pos, cfg)
    np.testing.assert_allclose(cos, want_cos, atol=1e-6)
    np.testing.assert_allclose(sin, want_sin, atol=1e-6)
    # 0.1 x 0.707 x ln 40 + 1 = 1.26081
    assert abs(yarn_mscale(40, 0.707) - 1.26081) < 1e-5
    published = DeepseekV2ForCausalLM(deepseek_v2_tiny(
        qk_nope_head_dim=16, qk_rope_head_dim=8,
        rope_scaling=DeepseekV2Config().rope_scaling))
    attn = published.model.layers[0].self_attn
    assert abs(attn.scale - 24 ** -0.5 * 1.26081 ** 2) < 1e-5
    # the published 64 rope lanes: the ramp runs between dimensions 10
    # (32 turns over 4,096 positions) and 23 (one turn)
    f = np.asarray(yarn_inv_freq(64, 10000.0, 40, 4096))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    assert (f[11:23] < plain[11:23]).all() \
        and (f[11:23] > plain[11:23] / 40).all()


def test_absorbed_attention_equals_expanded():
    """One layer's attention for the LAST position of a sequence: the
    absorbed association over the latents equals the expanded causal
    attention's last row."""
    model = DeepseekV2ForCausalLM(deepseek_v2_tiny())
    attn = model.model.layers[1].self_attn
    u = jnp.asarray(np.random.default_rng(0).normal(size=(1, 19, 64)),
                    jnp.float32)
    want, c, k_rope = attn.full(u)
    pos = jnp.asarray([18])
    q_nope, q_rope = attn.queries(u[0, -1:], pos)
    s = (jnp.einsum("thr,sr->ths", attn.absorb(q_nope), c[0])
         + jnp.einsum("thd,sd->ths", q_rope, k_rope[0])) * attn.scale
    o = jnp.einsum("ths,sr->thr", jax.nn.softmax(s, -1), c[0])
    got = attn.project(attn.unabsorb(o))
    np.testing.assert_allclose(got[0], want[0, -1], atol=2e-6)


def expanded_token_major(attn, u, attend=None):
    """The expanded association in the reference's own layout, as the
    program computed it until PR 38: ``[T, nh, dn + dr]`` queries sliced
    at the rope lanes and joined again, keys joined from ``W_kvb``'s
    product and the broadcast ``RoPE(k_rope)``, (batch, seq, heads, dim)
    attention, a token-major ``W_o``. Plain XLA unless ``attend`` is
    given."""
    B, S, H = u.shape
    pos = jnp.tile(jnp.arange(S), B)
    flat = u.reshape(B * S, H)
    q_nope, q_rope = attn.queries(flat, pos)
    c, k_rope = attn.latent(flat, pos)
    kv = (c @ attn.kv_b_proj.weight._data).reshape(B, S, attn.nh, -1)
    k = jnp.concatenate([
        kv[..., :attn.dn],
        jnp.broadcast_to(k_rope.reshape(B, S, 1, -1),
                         (B, S, attn.nh, attn.dr))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1).reshape(B, S, attn.nh, -1)
    a = (attend or _sdpa_xla)(q, k, kv[..., attn.dn:], causal=True,
                              scale=attn.scale)
    return attn.project(a.reshape(B, S, -1))


def force_flash(monkeypatch, body):
    """``attention_bhsd`` / ``scaled_dot_product_attention`` take the
    Pallas kernel (interpreted here) at any length; ``body``: the walk
    or the grid forward."""
    monkeypatch.setattr(attention, "use_pallas", lambda shape: True)
    monkeypatch.setattr(pallas_flash, "_JIT_CACHE", {})
    if body == "grid":
        monkeypatch.setattr(pallas_flash, "WALK_VMEM_BYTES", 0)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("path", ["xla", "walk", "grid"])
def test_head_major_expanded_attention(monkeypatch, path, dtype, tol):
    """``LatentAttention.full`` (head-major from the projections to
    ``W_o``, through ``attention_bhsd``) against the token-major form it
    replaced and, for the last position, the ABSORBED association over
    the latents it returns — on the XLA path and through both
    interpreted flash forward bodies."""
    S = 19 if path == "xla" else 1024
    if path != "xla":
        force_flash(monkeypatch, path)
        ran = []
        flash = pallas_flash._flash
        monkeypatch.setattr(pallas_flash, "_flash", lambda *a: (
            ran.append(pallas_flash._walks(
                S, S, 24, a[0].dtype, 1, a[5], a[6], False, 16) is not None),
            flash(*a))[1])
    model = DeepseekV2ForCausalLM(deepseek_v2_tiny(dtype=dtype))
    attn = model.model.layers[1].self_attn
    u = jnp.asarray(np.random.default_rng(0).normal(size=(1, S, 64)), dtype)
    got, c, k_rope = attn.full(u)
    assert got.dtype == u.dtype and got.shape == u.shape
    if path != "xla":
        assert ran == [path == "walk"]
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_allclose(f32(got), f32(expanded_token_major(attn, u)),
                               atol=tol)
    pos = jnp.asarray([S - 1])
    q_nope, q_rope = attn.queries(u[0, -1:], pos)
    s = (jnp.einsum("thr,sr->ths", attn.absorb(q_nope), c[0])
         + jnp.einsum("thd,sd->ths", q_rope, k_rope[0])) * attn.scale
    o = jnp.einsum("ths,sr->thr", jax.nn.softmax(s.astype(jnp.float32), -1),
                   c[0].astype(jnp.float32)).astype(u.dtype)
    np.testing.assert_allclose(f32(attn.project(attn.unabsorb(o))[0]),
                               f32(got[0, -1]), atol=tol)


def big_moves(fn, *args, elements, split):
    """The mechanism's counter, on the jaxpr of ``fn``: (transposes of
    arrays of at least ``elements`` elements — but for a ``dot_general``'s
    own result, whose order is the matmul's to write —, slices / joins /
    updates of an array whose last two axes are ``split`` = (heads, lanes a
    head), the operand shapes of the flash kernel's calls)."""
    turned, cut, calls = [], [], []

    def visit(jaxpr):
        made_by = {v: e.primitive.name for e in jaxpr.eqns
                   for v in e.outvars}
        for e in jaxpr.eqns:
            name = e.primitive.name
            shapes = [tuple(v.aval.shape) for v in (*e.invars, *e.outvars)
                      if hasattr(v.aval, "shape")]
            if name == "pallas_call":
                calls.append(shapes[:3])
                continue
            if name == "transpose" and math.prod(shapes[0]) >= elements \
                    and made_by.get(e.invars[0]) != "dot_general":
                turned.append(shapes[0])
            if name in ("slice", "dynamic_slice", "concatenate", "gather",
                        "scatter", "dynamic_update_slice") \
                    and any(s[-2:] == split for s in shapes):
                cut.append((name, shapes))
            for sub in jax.core.jaxprs_in_params(e.params):
                visit(sub)

    visit(jax.make_jaxpr(fn)(*args).jaxpr)
    return turned, cut, calls


def test_head_major_prefill_moves_no_activation(monkeypatch):
    """With the flash kernel forced, the expanded attention's program
    holds no transpose of an ``S x nh x dv`` array (the three head-major
    products aside: ``tests/test_chip_compile.py`` holds the compiled
    program to writing those in place) and no slice or join of a
    ``[.., nh, dn + dr]`` activation, and the kernel is handed ``[B, nh,
    S, d]`` operands; the token-major form trips both counts."""
    force_flash(monkeypatch, "walk")
    model = DeepseekV2ForCausalLM(deepseek_v2_tiny())
    attn = model.model.layers[1].self_attn
    S, nh, d = 1024, attn.nh, attn.dn + attn.dr
    u = jnp.zeros((1, S, 64), jnp.float32)
    count = functools.partial(big_moves, elements=S * nh * attn.dv,
                              split=(nh, d))
    turned, cut, calls = count(attn.full, u)
    assert (turned, cut) == ([], [])
    assert calls == [[(1, nh, S, d), (1, nh, S, d), (1, nh, S, attn.dv)]]

    def attend(q, k, v, **kw):
        return attention.scaled_dot_product_attention(
            paddle.Tensor(q), paddle.Tensor(k), paddle.Tensor(v),
            is_causal=kw["causal"], scale=kw["scale"])._data

    turned, cut, calls = count(
        lambda u: expanded_token_major(attn, u, attend), u)
    assert len(turned) == 4 and len(cut) >= 4 and len(calls) == 1
