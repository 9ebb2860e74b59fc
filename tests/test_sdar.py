"""SDAR-MoE (``models/sdar.py``: block diffusion over softmax-routed
dropless experts) against the benchmark's plain reference
(``benchmark/reference/sdar_moe.py``), tiny sizes, float32 on the CPU,
Pallas kernels interpreted, seeded random weights placed through the
benchmark's own layout (``benchmark/configs/sdar-30b-a3b-chat.json``).

Tolerances. Model and reference compute the same float32 mathematics in
another order (grouped matmul over sorted rows against a scan over
experts, paged softmax per page block against one row), so logits of
scale ~1 agree to a few 1e-6; ``LOGIT_TOL`` = 5e-5 leaves an order of
magnitude of room and is two orders under what a bf16-for-f32
substitution gives (``test_tolerance_rejects_*``)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu import inference
from paddle2_tpu.incubate.moe import softmax_topk_route
from paddle2_tpu.kernels import pallas_flash
from paddle2_tpu.kernels.attention import _sdpa_xla
from paddle2_tpu.models import SdarMoeConfig, SdarMoeForCausalLM
from paddle2_tpu.serving import EngineConfig, ServingEngine, blockdiff
from paddle2_tpu.serving.block_cache import GARBAGE_BLOCK, audit_kv_ledger
from paddle2_tpu.serving.paged_attention import (paged_attention_decode,
                                                 paged_attention_reference)
from paddle2_tpu.serving.spec import SpeculativeConfig

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
LOGIT_TOL = 5e-5
VOCAB = 503


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules and the tiny (rehearsal) configuration."""
    added = [p for p in (BENCH,) if p not in sys.path]
    sys.path[:0] = added
    import run as harness
    from common import load_module
    from drivers import program
    from weights import make_weights
    with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json")) as f:
        cfg = json.load(f)
    cfg = harness.merge(cfg, cfg["rehearsal"])
    # two of the four identical layers: half the interpreted kernels
    cfg.update(name="sdar-30b-a3b-chat", num_hidden_layers=2)
    ref = load_module("reference", cfg["reference"])
    yield {"cfg": cfg, "ref": ref, "program": program,
           "make_weights": make_weights}
    for p in added:
        sys.path.remove(p)


def build(bench, seed, **overrides):
    """(model with the seed's weights, the configuration as the
    reference reads it, the reference's float32 leaves of the seed)."""
    cfg = dict(bench["cfg"], **overrides)
    model, _ = bench["program"].build_model(cfg, {})
    model.eval()
    bench["program"].set_weights(model, cfg, "per_layer", bench["ref"], seed)
    params = bench["make_weights"](bench["ref"].leaf_specs(cfg), seed,
                                   jnp.float32)
    return model, cfg, params


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
    model, cfg, params = build(bench, seed)
    ids = np.random.default_rng(seed).integers(1, VOCAB, (2, 37))
    got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._data)
    want = np.asarray(bench["ref"].logits(params, jnp.asarray(ids), cfg))
    assert np.abs(got - want).max() <= LOGIT_TOL


@pytest.mark.parametrize("control", ["causal", "bfloat16", "int8"])
def test_tolerance_rejects(bench, control):
    """The controls of LOGIT_TOL: the reference itself under a plain
    causal mask (a position blind to the rest of its block), and with
    bf16 or int8 matmul operands, lies far outside it."""
    from reference import common as rc
    ref = bench["ref"]
    _, cfg, params = build(bench, 3)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, VOCAB, (1, 37)))
    want = ref.logits(params, ids, cfg)
    if control == "causal":
        low = ref.forward(params, ids, cfg,
                          mask=ref.block_causal_mask(37, 1))[0]
    else:
        low = ref.logits(params, ids, cfg, rc.MATMULS[control])
    assert float(jnp.abs(low - want).max()) > 20 * LOGIT_TOL


def test_config_takes_published_keys():
    cfg = SdarMoeConfig()           # the published 48-layer defaults
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.head_dim,
            cfg.num_key_value_heads, cfg.vocab_size) == (
        128, 8, 128, 4, 151936)
    with pytest.raises(ValueError):
        SdarMoeConfig(tie_word_embeddings=True)
    with pytest.raises(ValueError):
        SdarMoeConfig(mlp_only_layers=[0])
    with pytest.raises(ValueError):
        SdarMoeConfig(mask_token_id=151936)


def test_softmax_router_renormalises_its_top_k():
    a = jnp.eye(4, dtype=jnp.float32)[:1]           # picks row 0 of W_g
    gate = jnp.asarray([[2.0, 1.0, 0.0, -1.0, 3.0, -3.0]]
                       + [[0.0] * 6] * 3, jnp.float32)
    p = np.asarray(jax.nn.softmax(gate[0]))
    ids, w = softmax_topk_route(a, gate, 2)
    assert ids.tolist() == [[4, 0]]
    np.testing.assert_allclose(np.asarray(w[0]), p[[4, 0]] / p[[4, 0]].sum(),
                               rtol=1e-6)
    _, raw = softmax_topk_route(a, gate, 2, norm_topk=False)
    np.testing.assert_allclose(np.asarray(raw[0]), p[[4, 0]], rtol=1e-6)


def test_clean_noisy_mask_is_the_published_training_mask(bench):
    """[clean ; noisy]: clean rows block-causal and blind to the noisy
    copy; a noisy block sees the clean blocks BEFORE it and itself."""
    pos, sees = bench["ref"].clean_noisy(12, 4, 4)
    assert pos.tolist() == list(range(12)) + list(range(4, 12))
    np.testing.assert_array_equal(sees[:12, :12],
                                  bench["ref"].block_causal_mask(12, 4))
    assert not sees[:12, 12:].any()
    # noisy block of positions 8..11: clean 0..7, not clean 8..11
    assert sees[16:, :8].all() and not sees[16:, 8:12].any()
    assert sees[16:, 16:].all() and not sees[16:, 12:16].any()
    assert sees[12:16, :4].all() and not sees[12:16, 4:12].any()


# --------------------------------------------------------- the schedule
def test_fix_plan_is_known_without_reading_anything():
    assert blockdiff.fix_plan(4, 4, 4) == (1, 1, 1, 1)
    assert blockdiff.fix_plan(4, 2, 4) == (2, 2)
    assert blockdiff.fix_plan(4, 1, 4) == (4,)
    assert blockdiff.fix_plan(8, 2, 8) == (4, 4)
    # a block that opens with prompt tokens takes fewer, smaller passes
    assert blockdiff.fix_plan(4, 2, 3) == (2, 1)
    assert blockdiff.fix_plan(4, 4, 1) == (1,)
    assert blockdiff.fix_plan(8, 2, 3) == (3,)


def test_unmask_takes_the_most_confident_masked_positions():
    lg = np.full((2, 4, 6), -4.0, np.float32)
    # row 0: confidences rise with the position; position 3 is fixed
    for j, top in enumerate((1.0, 2.0, 3.0, 9.0)):
        lg[0, j, j + 1] = top
    lg[1, :, 5] = 2.0                               # row 1: all tie
    ids = jnp.asarray([[7, 7, 7, 42], [7, 7, 7, 7]], jnp.int32)
    masked = jnp.asarray([[1, 1, 1, 0], [1, 1, 1, 1]], bool)
    out, left = blockdiff.unmask_low_confidence(
        jnp.asarray(lg), ids, masked, jnp.asarray([2, 1], jnp.int32))
    assert out.tolist() == [[7, 2, 3, 42], [5, 7, 7, 7]]
    assert left.tolist() == [[True, False, False, False],
                             [False, True, True, True]]
    # a commit pass (nothing to fix) comes back as it went in
    out, left = blockdiff.unmask_low_confidence(
        jnp.asarray(lg), ids, masked, jnp.zeros(2, jnp.int32))
    assert out.tolist() == ids.tolist() and left.tolist() == masked.tolist()


# ------------------------------------------- passes through the paged cache
@pytest.fixture
def logit_tap(monkeypatch):
    """Every pass's logits ``[rows, B, V]`` as the decode program's
    ``unmask`` is handed them, in call order. The engine is held to
    reading every step back in the call that enqueued it — by its own
    rule: an armed drop hook (which never fires here) — so that a call
    of ``decode_once`` pairs with the logits of the step it ran."""
    from paddle2_tpu.distributed.fault_tolerance import chaos
    monkeypatch.setattr(chaos, "_ACTIVE",
                        chaos.ChaosInjector("drop_decode_step:1000000000"))
    store = []
    unmask = blockdiff.unmask_low_confidence

    def tapped(logits, ids, masked, n_fix):
        jax.debug.callback(lambda lg: store.append(np.asarray(lg)), logits,
                           ordered=True)
        return unmask(logits, ids, masked, n_fix)

    monkeypatch.setattr(blockdiff, "unmask_low_confidence", tapped)
    return store


def serve(engine, prompts, max_new, store):
    """Drive the engine to idle; {request id: {index of a pass in the
    request's record: its logits [B, V]}} and the request ids. (A block
    that an eviction throws away leaves the record, and the passes that
    recompute it take its indices.)"""
    rids = [engine.submit(p, max_new) for p in prompts]
    rows = {r: {} for r in rids}
    now = 0.0
    while not engine.idle():
        now += 1.0
        engine.admit_and_prefill(now)
        active = [s for s in engine.scheduler.running()
                  if getattr(s, "ready_at", 0.0) <= now]
        before = engine.scheduler.total_evictions
        if engine.decode_once(now):
            jax.effects_barrier()
            lg = store.pop(0)
            # an eviction inside the step drops rows from the END of
            # the running list (LIFO victims)
            gone = engine.scheduler.total_evictions - before
            for i, s in enumerate(active[:len(active) - gone]):
                rows[s.req_id][len(s.passes) - 1] = lg[i]
    assert not store
    return rids, rows


def passes_against_reference(bench, cfg, params, engine, rid, rows):
    """Every pass in the record of request ``rid`` against the
    reference's forward over [clean ; noisy], once per pass index;
    returns the widest logit difference."""
    ref, B = bench["ref"], cfg["block_length"]
    seq = engine.sequence(rid)
    first = len(seq.request.prompt) // B * B
    blocks = {}         # block start -> [(ids after the pass, its logits)]
    for i, (start, row, _, _) in enumerate(engine.block_passes(rid)):
        blocks.setdefault(start, []).append((row, rows[i]))
    starts = sorted(blocks)
    clean = list(seq.request.prompt[:first])
    for start in starts:
        clean += blocks[start][-1][0].tolist()      # the commit's ids
    pos, sees = ref.clean_noisy(len(clean), first, B)
    worst = 0.0
    for j in range(max(len(v) for v in blocks.values())):
        # the state every block is in BEFORE its pass j (a block with
        # fewer passes is fed its final ids and not compared)
        noisy = []
        for start in starts:
            passes = blocks[start]
            if j == 0:
                state = np.full(B, -1)
                left = seq.request.prompt[start:start + B]
                state[:len(left)] = left
            else:
                state = passes[min(j, len(passes)) - 1][0]
            noisy += np.where(state < 0, cfg["mask_token_id"],
                              state).tolist()
        want = np.asarray(ref.forward(
            params, jnp.asarray([clean + noisy], jnp.int32), cfg, mask=sees,
            positions=jnp.asarray(pos), head_from=len(clean))[0][0])
        for b, start in enumerate(starts):
            if j < len(blocks[start]):
                worst = max(worst, float(np.abs(
                    blocks[start][j][1] - want[b * B:(b + 1) * B]).max()))
    return worst


def check_passes(bench, cfg, params, engine, rids, rows):
    for rid in rids:
        assert sorted(rows[rid]) == list(range(len(
            engine.block_passes(rid))))
        worst = passes_against_reference(bench, cfg, params, engine, rid,
                                         rows[rid])
        assert worst <= LOGIT_TOL, (rid, worst)


def test_prefill_passes_and_commit_match_reference(bench, logit_tap):
    """Prompts with every remainder mod B, three sequences in a batch,
    S = 2: the logits of every denoise pass and of the commit, every
    block, against the reference's forward over [clean ; noisy]."""
    model, cfg, params = build(bench, 5)
    engine = tiny_engine(model, denoising_steps=2)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (5, 22, 36, 7)]
    rids, rows = serve(engine, prompts, 9, logit_tap)
    check_passes(bench, cfg, params, engine, rids, rows)
    assert engine.allocator.used_count == 0
    audit_kv_ledger(engine.allocator, [])


def test_prefix_hit_passes_match_reference(bench, logit_tap):
    """A prefix hit shares whole pages (block_size is a multiple of B, so
    a page holds whole blocks and depends on nothing behind it)."""
    model, cfg, params = build(bench, 10)
    engine = tiny_engine(model, enable_prefix_cache=True,
                         denoising_steps=4)
    rng = np.random.default_rng(10)
    shared = rng.integers(1, VOCAB, 24).tolist()
    prompts = [shared + rng.integers(1, VOCAB, n).tolist() for n in (3, 6)]
    rids, rows = serve(engine, prompts[:1], 6, logit_tap)
    rids2, rows2 = serve(engine, prompts[1:], 6, logit_tap)
    assert engine.sequence(rids2[0]).prefix_cached_tokens >= 16
    check_passes(bench, cfg, params, engine, rids + rids2,
                 {**rows, **rows2})


def test_eviction_passes_match_reference(bench, logit_tap):
    """A pool too small for the batch: a sequence is evicted inside a
    block and re-prefilled from its committed log; every pass kept,
    before and after, still matches."""
    model, cfg, params = build(bench, 7)
    engine = tiny_engine(model, num_blocks=12, max_batch=3,
                         denoising_steps=2)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (19, 23, 26)]
    rids, rows = serve(engine, prompts, 13, logit_tap)
    assert engine.scheduler.total_evictions > 0
    check_passes(bench, cfg, params, engine, rids, rows)


def generate_both(bench, cfg, params, engine, prompts, max_new, steps):
    """The engine's tokens and passes beside ``reference.generate``'s."""
    rids = [engine.submit(p, n) for p, n in zip(prompts, max_new)]
    run_to_idle(engine)
    for rid, prompt, n in zip(rids, prompts, max_new):
        seq = engine.sequence(rid)
        want, record = bench["ref"].generate(params, prompt, n, cfg, steps)
        assert seq.generated == want, rid
        got = [(s, row.tolist()) for s, row, _, commit
               in engine.block_passes(rid) if not commit]
        assert got == [(s, row.tolist()) for s, row in record], rid
    return rids


@pytest.mark.parametrize("block,steps", [(4, 4), (4, 2), (4, 1), (8, 2)])
def test_engine_generates_what_the_reference_generates(bench, block, steps):
    """Token for token AND pass for pass (which positions were fixed in
    which pass, at which token), run-ahead on: prompt lengths with every
    remainder mod B, ``max_new_tokens`` ending inside a block."""
    model, cfg, params = build(bench, 40 + block + steps,
                               block_length=block)
    engine = tiny_engine(model, denoising_steps=steps)
    rng = np.random.default_rng(block * 10 + steps)
    lens = [block * 2 + r for r in range(block)][:4] + [3]
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in lens]
    new = [block + 1, 2 * block, 3, 2 * block + block // 2, 5][:len(lens)]
    # a request of fewer tokens than fill its first block, too
    generate_both(bench, cfg, params, engine, prompts, new, steps)
    assert engine.ahead_steps > 0 and engine.ahead_dropped == 0
    assert engine.allocator.used_count == 0


def test_mask_id_in_a_prompt_is_a_token(bench):
    """Masked-ness is a bit, never ``id == mask_token_id``: a prompt may
    hold the id, also among the tokens that open its first block."""
    model, cfg, params = build(bench, 51)
    engine = tiny_engine(model, denoising_steps=2)
    m = cfg["mask_token_id"]
    prompts = [[9, m, 4, 4, 17, m], [m] * 7]
    generate_both(bench, cfg, params, engine, prompts, [6, 5], 2)


def test_eviction_mid_block_recomputes_exactly(bench):
    """A pool too small for the batch: sequences are evicted inside a
    block, the block is thrown away, and the re-prefill from the
    committed log recomputes it: the reference's tokens and passes."""
    model, cfg, params = build(bench, 7)
    engine = tiny_engine(model, num_blocks=12, max_batch=3,
                         denoising_steps=2)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (19, 23, 26)]
    generate_both(bench, cfg, params, engine, prompts, [14, 13, 12], 2)
    assert engine.scheduler.total_evictions > 0
    assert engine.allocator.used_count == 0


def test_skipped_commit_is_seen(bench, logit_tap, monkeypatch):
    """The damaged engine: a commit pass whose keys and values never
    reach the block's page (the row's page entry points at the garbage
    block), so the cache keeps what the LAST DENOISE pass wrote — the
    comparison of the passes' logits fails it."""
    model, cfg, params = build(bench, 5)
    engine = tiny_engine(model, denoising_steps=2)
    build_step = engine._build_block_step

    def no_commit(*a):
        step = build_step(*a)
        meta, tables = step.arrays
        for i in range(len(step.active)):
            if meta[i, 1] == 0:
                tables[i, meta[i, 2] // 8] = GARBAGE_BLOCK
        return step

    monkeypatch.setattr(engine, "_build_block_step", no_commit)
    prompt = np.random.default_rng(5).integers(1, VOCAB, 12).tolist()
    rids, rows = serve(engine, [prompt], 12, logit_tap)
    worst = passes_against_reference(bench, cfg, params, engine, rids[0],
                                     rows[rids[0]])
    assert worst > 100 * LOGIT_TOL


# ------------------------------------------------------ engine contract
@pytest.mark.parametrize("feature", [
    dict(weight_only_int8=True), dict(weight_only_lm_head=True),
    dict(spec=SpeculativeConfig(num_draft_tokens=2)),
    dict(enable_prefix_cache=True, enable_kv_spill=True)])
def test_engine_refuses_what_the_family_lacks(bench, feature):
    model, _, _ = build(bench, 11)
    with pytest.raises(ValueError, match="not served with"):
        tiny_engine(model, **feature)


def test_engine_refuses_schedules_it_cannot_run(bench):
    model, _, _ = build(bench, 11)
    with pytest.raises(ValueError, match="must divide the block length"):
        tiny_engine(model, denoising_steps=3)
    with pytest.raises(ValueError, match="is not served"):
        tiny_engine(model, unmask_strategy="low_confidence_dynamic")
    with pytest.raises(ValueError, match="must divide the cache's"):
        tiny_engine(model, block_size=6)
    from paddle2_tpu.models import GPTForCausalLM, gpt_tiny
    with pytest.raises(ValueError, match="denoising_steps does not apply"):
        ServingEngine(GPTForCausalLM(gpt_tiny(use_scan=False)),
                      config=EngineConfig(denoising_steps=2, interpret=True))
    # whole blocks must fit the model's length
    engine = tiny_engine(model, max_model_len=30)
    from paddle2_tpu.serving.reliability import PromptTooLongError
    with pytest.raises(PromptTooLongError, match="whole blocks"):
        engine.submit(list(range(1, 26)), 5)       # 30 tokens, 32 slots
    engine.submit(list(range(1, 26)), 3)


def test_artifact_path_serves_the_family(bench, tmp_path):
    """jit.save -> inference.Config -> create_serving_engine(gpt_config=
    <SdarMoeConfig>): the tokens of the live-model engine."""
    model, _, _ = build(bench, 12)
    prompt = np.random.default_rng(12).integers(1, VOCAB, 13).tolist()
    live = tiny_engine(model, denoising_steps=2)
    rid = live.submit(prompt, 6)
    run_to_idle(live)
    path = str(tmp_path / "model")
    paddle.jit.save(model, path)
    conf = inference.Config(path)
    conf.enable_continuous_batching(block_size=8, num_blocks=64,
                                    max_batch=4, max_model_len=96,
                                    kv_dtype="float32", interpret=True,
                                    denoising_steps=2)
    engine = conf.create_serving_engine(gpt_config=model.cfg)
    assert isinstance(engine.model, SdarMoeForCausalLM)
    rid2 = engine.submit(prompt, 6)
    run_to_idle(engine)
    assert engine.sequence(rid2).generated == live.sequence(rid).generated


def test_record_holds_the_experts_of_every_row(bench):
    """Prefill rows (``routed_experts``) and the B rows of every pass,
    commits included: the float32 reference's own choice on the same
    inputs."""
    model, cfg, params = build(bench, 17)
    engine = tiny_engine(model, denoising_steps=2)
    prompt = np.random.default_rng(17).integers(1, VOCAB, 14).tolist()
    rid = engine.submit(prompt, 8)
    run_to_idle(engine)
    seq = engine.sequence(rid)
    L, k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    assert engine.routed_experts(rid).shape == (12, L, k)
    record = engine.block_passes(rid)
    assert [r[3] for r in record] == [False, True] + [False, False,
                                                      True] * 2
    assert all(r[2].shape == (4, L, k) for r in record)
    # the commits' rows and the prefill's, against one clean forward
    ids = jnp.asarray([seq.tokens[:12] + sum(
        (r[1].tolist() for r in record if r[3]), [])], jnp.int32)
    _, used, _ = bench["ref"].forward(params, ids, cfg)
    chosen = np.concatenate([engine.routed_experts(rid)]
                            + [r[2] for r in record if r[3]])
    np.testing.assert_array_equal(np.sort(chosen, -1),
                                  np.sort(np.asarray(used[0]), -1))


# ------------------------------------------------------------- kernels
@pytest.mark.parametrize("block", [1, 4, 8])
def test_flash_block_causal_fwd_bwd(block):
    """The flash kernel under the block-causal mask, forward and
    backward, several tiles a side, against the dense masked softmax."""
    rng = np.random.default_rng(block)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 64, 2, 16)), jnp.float32)
               for _ in range(3))

    def flash(q, k, v):
        return pallas_flash.flash_attention_bshd(
            q, k, v, causal=True, causal_block=block, block_q=16,
            block_k=16, interpret=True)

    def dense(q, k, v):
        return _sdpa_xla(q, k, v, causal=True, causal_block=block)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)), atol=2e-5)
    if block > 1:
        # and it is not the plain causal mask
        assert float(jnp.abs(dense(q, k, v) - _sdpa_xla(
            q, k, v, causal=True)).max()) > 1e-2
    w = jnp.asarray(rng.standard_normal((2, 64, 2, 16)), jnp.float32)
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (dense(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for g, e in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), atol=5e-5)


def test_flash_refuses_a_block_it_cannot_mask():
    q = jnp.zeros((1, 64, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="power of two"):
        pallas_flash.flash_attention_bshd(q, q, q, causal=True,
                                          causal_block=6, interpret=True)


@pytest.mark.parametrize("Q", [1, 4, 8])
def test_paged_kernel_block_of_positions(Q):
    """Q query positions a sequence over ONE walk of its pages, 32 query
    heads over 4 key/value heads, every position's context ending at the
    block's end: each position against the reference's single row."""
    H, Hkv, D, bs, pages = 32, 4, 16, 16, 5
    rng = np.random.default_rng(Q)
    B, N = 3, 24
    kp, vp = (jnp.asarray(rng.standard_normal((2, N, bs, Hkv * D)),
                          jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, Q, H, D)), jnp.float32)
    bt = rng.permutation(np.arange(1, N))[:B * pages].reshape(
        B, pages).astype(np.int32)
    ctx = np.asarray([pages * bs - 8, 8, bs + 16], np.int32)
    got = paged_attention_decode(q, kp, vp, bt, ctx, layer=1,
                                 interpret=True)
    assert got.shape == (B, Q, H, D)
    for j in range(Q):
        want = paged_attention_reference(q[:, j:j + 1], kp[1], vp[1], bt,
                                         ctx)
        np.testing.assert_allclose(np.asarray(got[:, j:j + 1]),
                                   np.asarray(want), rtol=2e-6, atol=2e-6)
