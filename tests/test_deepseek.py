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
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu import inference
from paddle2_tpu.incubate.moe import (DroplessExperts,
                                      softmax_group_limited_route)
from paddle2_tpu.kernels import attention, pallas_flash
from paddle2_tpu.kernels.attention import _sdpa_xla
from paddle2_tpu.models import (DeepseekV2Config, DeepseekV2ForCausalLM,
                                deepseek_v2_tiny)
from paddle2_tpu.models._decoder import (rope_tables, yarn_inv_freq,
                                         yarn_mscale)
from paddle2_tpu.serving import EngineConfig, ServingEngine
from paddle2_tpu.serving import paged_attention as pa
from paddle2_tpu.serving.block_cache import audit_kv_ledger
from paddle2_tpu.serving.model_runner import PagedRunner

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
LOGIT_TOL = 5e-5


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules and the tiny (rehearsal) configuration:
    a dense layer and two expert layers, this chip holding routing
    group 0 of 4."""
    added = [p for p in (BENCH,) if p not in sys.path]
    sys.path[:0] = added
    import run as harness
    from common import load_module
    from drivers import program
    from weights import make_weights
    with open(os.path.join(BENCH, "configs", "deepseek-v2.json")) as f:
        cfg = json.load(f)
    cfg = harness.merge(cfg, cfg["rehearsal"])
    cfg.update(name="deepseek-v2", num_hidden_layers=3)
    ref = load_module("reference", cfg["reference"])
    yield {"cfg": cfg, "ref": ref, "program": program,
           "make_weights": make_weights, "load_module": load_module}
    for p in added:
        sys.path.remove(p)


def build(bench, seed, cfg=None, **overrides):
    """(model with the seed's weights, its config, the reference's
    float32 leaves of the same seed)."""
    cfg = cfg or bench["cfg"]
    model, mcfg = bench["program"].build_model(cfg, overrides)
    model.eval()
    bench["program"].set_weights(model, cfg, "per_layer", bench["ref"], seed)
    params = bench["make_weights"](bench["ref"].leaf_specs(cfg), seed,
                                   jnp.float32)
    return model, mcfg, params


def whole(bench):
    """The same tiny model UNCUT: all 8 experts held."""
    cfg = dict(bench["cfg"], n_routed_experts=8, num_experts=8,
               held_group=None)
    return cfg


def ref_logits(bench, params, seq, cfg=None):
    return np.asarray(bench["ref"].logits(
        params, jnp.asarray([seq], jnp.int32), cfg or bench["cfg"])[0])


@pytest.fixture
def logit_tap(monkeypatch):
    """Every logits array the runner's sampling wrapper is handed, in
    call order. An armed drop hook (which never fires) holds the engine
    to reading every step back in the call that enqueued it, so
    ``serve`` can pair a call with the logits of the step it ran."""
    from paddle2_tpu.distributed.fault_tolerance import chaos
    monkeypatch.setattr(chaos, "_ACTIVE",
                        chaos.ChaosInjector("drop_decode_step:1000000000"))
    store = []
    sample = PagedRunner._sample

    def tapped(logits, counts):
        jax.debug.callback(lambda lg: store.append(np.asarray(lg)), logits,
                           ordered=True)
        return sample(logits, counts)

    monkeypatch.setattr(PagedRunner, "_sample", staticmethod(tapped))
    return store


def serve(engine, prompts, max_new, store):
    """Drive the engine to idle; {request id: [logits row of each
    generated token, in order]} and the request ids."""
    rids = [engine.submit(p, max_new) for p in prompts]
    rows = {r: [] for r in rids}
    now = 0.0
    while not engine.idle():
        now += 1.0
        for info in engine.admit_and_prefill(now):
            jax.effects_barrier()
            rows[info["seq"].req_id].append(store.pop(0)[0])
        active = [s for s in engine.scheduler.running()
                  if getattr(s, "ready_at", 0.0) <= now]
        before = engine.scheduler.total_evictions
        if engine.decode_once(now):
            jax.effects_barrier()
            lg = store.pop(0)
            gone = engine.scheduler.total_evictions - before
            for i, s in enumerate(active[:len(active) - gone]):
                rows[s.req_id].append(lg[i])
    assert not store
    return rids, rows


def check_against_reference(bench, params, engine, rids, rows):
    worst = 0.0
    for rid in rids:
        seq = engine.sequence(rid)
        prompt, gen = seq.request.prompt, seq.generated
        assert len(rows[rid]) == len(gen)
        ref = ref_logits(bench, params, list(prompt) + list(gen))
        for j, row in enumerate(rows[rid]):
            worst = max(worst, float(np.abs(
                row - ref[len(prompt) - 1 + j]).max()))
    assert worst <= LOGIT_TOL, worst
    return worst


def tiny_engine(model, **kw):
    conf = dict(block_size=8, num_blocks=64, max_batch=4, max_model_len=96,
                kv_dtype="float32", interpret=True)
    conf.update(kw)
    return ServingEngine(model, config=EngineConfig(**conf))


def run_to_idle(engine):
    now = 0.0
    while not engine.idle():
        now += 1.0
        engine.tick(now)


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


# ----------------------------------------------------- the serving plane
def test_prefill_then_paged_decode_logits(bench, logit_tap):
    """Prompts that are no multiples of 16 (nor of the block size 8),
    three sequences in one batch: every step's logits — the expanded
    prefill's and the ABSORBED decode's through the latent cache —
    against the reference's full expanded forward over prompt +
    generated."""
    model, _, params = build(bench, 5)
    engine = tiny_engine(model)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 503, n).tolist() for n in (5, 21, 37)]
    rids, rows = serve(engine, prompts, 7, logit_tap)
    check_against_reference(bench, params, engine, rids, rows)
    assert engine.allocator.used_count == 0
    audit_kv_ledger(engine.allocator, [])


def test_eviction_and_readmission_give_same_logits(bench, logit_tap):
    model, _, params = build(bench, 6)
    engine = tiny_engine(model, num_blocks=10)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 503, n).tolist() for n in (27, 30)]
    rids, rows = serve(engine, prompts, 12, logit_tap)
    assert engine.scheduler.total_evictions >= 1
    for rid in rids:        # a re-prefill recomputes the evicted tail
        gen = engine.sequence(rid).generated
        rows[rid] = rows[rid][-len(gen):] if len(rows[rid]) > len(gen) \
            else rows[rid]
    for rid in rids:
        seq = engine.sequence(rid)
        ref = ref_logits(bench, params, seq.tokens)
        # the last row of every request was computed once, at decode
        got = rows[rid][-1]
        assert float(np.abs(got - ref[len(seq.tokens) - 2]).max()) \
            <= LOGIT_TOL


def test_cache_holds_one_latent_row_a_token(bench):
    """ONE pool of ``[c | RoPE(k_rope) | zeros]`` rows and no V pool:
    per-head keys and values are never stored; the allocator's bytes
    follow the row; the ledger closes with sequences live and gone."""
    model, mcfg, _ = build(bench, 7)
    engine = tiny_engine(model)
    cache, family = engine.cache, engine.runner.family
    rank, dr = mcfg.kv_lora_rank, mcfg.qk_rope_head_dim
    width = pa.mla_row_width(rank, dr)
    assert (family.kv_widths, family.num_kv_heads, family.head_dim) == \
        ((width, 0), 1, rank + dr)
    assert cache.v is None and cache.k.shape == (3, 64, 8, width)
    assert cache.block_bytes == 3 * 8 * width * 4
    assert pa.mla_row_width(512, 64) == 640          # 1,280 B in bf16
    rid = engine.submit(list(range(1, 20)), 3)
    engine.admit_and_prefill(0.0)
    seq = engine.sequence(rid)
    audit_kv_ledger(engine.allocator, [seq.table.blocks])
    # the rows written: the latent and the rotary key, then zeros
    rows = np.asarray(cache.k[:, seq.table.blocks[0]])
    assert np.abs(rows[..., :rank + dr]).min() > 0
    assert not rows[..., rank + dr:].any()
    run_to_idle(engine)
    assert engine.allocator.used_count == 0
    audit_kv_ledger(engine.allocator, [])


@pytest.mark.parametrize("feature", [
    {"weight_only_int8": True}, {"weight_only_lm_head": True},
    {"spec": "spec"}, {"enable_kv_spill": True, "enable_prefix_cache": True}])
def test_engine_refuses_what_the_family_lacks(bench, feature):
    from paddle2_tpu.serving.spec import SpeculativeConfig
    model, _, _ = build(bench, 8)
    if feature.get("spec"):
        feature = {"spec": SpeculativeConfig(num_draft_tokens=2)}
    with pytest.raises(ValueError, match="not served with"):
        tiny_engine(model, **feature)


def test_artifact_path_serves_the_family(bench, tmp_path):
    """jit.save -> inference.Config -> create_serving_engine(gpt_config=
    <DeepseekV2Config>): the tokens of the live-model engine, with
    run-ahead decode and the deferred first token on."""
    model, mcfg, _ = build(bench, 12)
    prompt = np.random.default_rng(12).integers(1, 503, 13).tolist()
    live = tiny_engine(model)
    rid = live.submit(prompt, 6)
    run_to_idle(live)
    path = str(tmp_path / "model")
    paddle.jit.save(model, path)
    conf = inference.Config(path)
    conf.enable_continuous_batching(block_size=8, num_blocks=64,
                                    max_batch=4, max_model_len=96,
                                    kv_dtype="float32", interpret=True)
    engine = conf.create_serving_engine(gpt_config=mcfg)
    assert isinstance(engine.model, DeepseekV2ForCausalLM)
    rid2 = engine.submit(prompt, 6)
    run_to_idle(engine)
    assert engine.sequence(rid2).generated == live.sequence(rid).generated
    assert engine.ahead_steps > 0 and engine.prefill_ahead > 0


def test_routing_counts_ride_behind_the_tokens(bench):
    model, _, _ = build(bench, 13)
    engine = tiny_engine(model)
    out = engine.runner.prefill_dispatch(list(range(1, 12)))
    tok, counts, chosen = engine.runner.split_counts(out[0], 1)
    assert tok.shape == (1,)
    # two expert layers, the experts chosen for every (padded) row
    assert chosen.shape == (16, 2, 2)
    assert ((0 <= chosen) & (chosen < 8)).all()
    assert set(counts) == set(DroplessExperts.COUNT_NAMES)
    # 11 real tokens routed (the padded tail is not); this chip holds
    # experts 0 and 1 of 8
    assert counts["moe_rows"] == [11, 11]
    here = [int(((chosen[:11, l] < 2).any(-1)).sum()) for l in range(2)]
    assert counts["moe_rows_routed_here"] == here
    assert counts["moe_assignments"] == \
        [int((chosen[:11, l] < 2).sum()) for l in range(2)]
    assert all(h <= 2 for h in counts["moe_experts_hit"])
    stats = engine._count_stats(counts)
    assert stats["moe_rows"] == 22 and \
        stats["moe_rows_routed_here"] == sum(here)


def test_engine_keeps_the_experts_the_served_path_chose(bench):
    """``routed_experts``: one row per token the model was FED, equal to
    the float32 reference's own choice on the same tokens — through the
    expanded prefill and the absorbed decode alike."""
    model, _, params = build(bench, 17)
    engine = tiny_engine(model)
    rng = np.random.default_rng(17)
    rids = [engine.submit(rng.integers(1, 503, n).tolist(), 9)
            for n in (11, 30)]
    run_to_idle(engine)
    for rid in rids:
        seq = engine.sequence(rid)
        chosen = engine.routed_experts(rid)
        assert chosen.shape == (len(seq.tokens) - 1, 2, 2)
        ids = jnp.asarray([seq.tokens[:-1]], jnp.int32)
        _, used, _ = bench["ref"].forward(params, ids, bench["cfg"])
        np.testing.assert_array_equal(np.sort(chosen, -1),
                                      np.sort(np.asarray(used[0]), -1))
        _, _, forced_deficit = bench["ref"].forward(
            params, ids, bench["cfg"], forced=jnp.asarray(chosen)[None])
        assert float(forced_deficit.max()) == 0.0


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
