"""Falcon-H1 (``models/falcon_h1.py``, ``kernels/ssd.py``,
``serving/falcon_h1_family.py``) against the benchmark's plain reference
(``benchmark/reference/falcon_h1.py``: the recurrence as a token-by-token
scan), tiny sizes, float32 on the CPU, Pallas kernels interpreted,
seeded random weights placed a leaf at a time through the benchmark's
own layout (``benchmark/configs/falcon-h1-34b-instruct.json``,
``drivers/serve_staged_dense.place_weights``). The tiny size keeps what
is awkward in the real one: 6 query heads over 2 key/value heads (a
group of 3, no power of two, as the model's 5), 4 mixer heads in 2
groups, a chunk of 8, 4 taps with bias, EVERY multiplier unlike 1.

Tolerances. Model and reference compute the same float32 mathematics in
another order (the chunked scan against the token-by-token recurrence,
paged softmax per page block against one row), so logits of scale ~1
agree to a few 1e-6; ``LOGIT_TOL`` = 5e-5 leaves an order of magnitude
of room and is two orders under what a bf16-for-f32 substitution gives
(``test_tolerance_rejects_bf16``). States are compared to ``STATE_TOL``
= 2e-5 (absolute, on states of scale ~1)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle2_tpu.distributed.fault_tolerance import chaos
from paddle2_tpu.kernels import ssd
from paddle2_tpu.models import FalconH1Config, falcon_h1_tiny
from paddle2_tpu.serving.block_cache import BlockFreeError, audit_kv_ledger
from paddle2_tpu.serving.model_runner import PagedRunner
from paddle2_tpu.serving.spec import SpeculativeConfig
# the logits tap, the drive to idle and the tiny engine are LFM2's
from test_lfm2_moe import logit_tap, serve, tiny_engine  # noqa: F401

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
LOGIT_TOL = 5e-5
STATE_TOL = 2e-5


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules and the tiny (rehearsal) configuration."""
    added = [p for p in (BENCH,) if p not in sys.path]
    sys.path[:0] = added
    import run as harness
    from common import load_module
    from drivers import program, serve_staged_dense
    from weights import make_weights
    with open(os.path.join(BENCH, "configs",
                           "falcon-h1-34b-instruct.json")) as f:
        cfg = json.load(f)
    cfg = harness.merge(cfg, cfg["rehearsal"])
    cfg["name"] = "falcon-h1-34b-instruct"
    ref = load_module("reference", cfg["reference"])
    yield {"cfg": cfg, "ref": ref, "program": program,
           "driver": serve_staged_dense, "make_weights": make_weights}
    for p in added:
        sys.path.remove(p)


def build(bench, seed, **overrides):
    """(model with the seed's weights, its config, the reference's
    float32 leaves of the same seed)."""
    cfg = bench["cfg"]
    model, mcfg = bench["program"].build_model(cfg, overrides)
    model.eval()
    bench["driver"].place_weights(model, cfg, "per_layer", bench["ref"],
                                  seed)
    params = bench["make_weights"](bench["ref"].leaf_specs(cfg), seed,
                                   jnp.float32)
    return model, mcfg, params


def ref_logits(bench, params, seq):
    with jax.default_matmul_precision("highest"):
        return np.asarray(bench["ref"].logits(
            params, jnp.asarray([seq], jnp.int32), bench["cfg"])[0])


def check_against_reference(bench, params, engine, rids, rows):
    worst = 0.0
    for rid in rids:
        seq = engine.sequence(rid)
        prompt, gen = seq.request.prompt, seq.generated
        # an evicted sequence's re-prefill yields its next token again
        assert len(rows[rid]) >= len(gen)
        ref = ref_logits(bench, params, list(prompt) + list(gen))
        for j, row in enumerate(rows[rid][:len(gen)]):
            worst = max(worst, float(np.abs(
                row - ref[len(prompt) - 1 + j]).max()))
    assert worst <= LOGIT_TOL, worst
    return worst


def run_to_idle(engine, prompts, max_new):
    rids = [engine.submit(p, max_new) for p in prompts]
    now = 0.0
    while not engine.idle():
        now += 1.0
        engine.tick(now)
    return [list(engine.sequence(r).generated) for r in rids]


def mixer_inputs(seed, T, nh=4, P=16, G=2, N=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(k[0], (T, nh, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (T, nh)) - 1.0),
        A=-jnp.exp(0.5 * jax.random.normal(k[2], (nh,))),
        B=jax.random.normal(k[3], (T, G, N)),
        C=jax.random.normal(k[4], (T, G, N)),
        D=1.0 + 0.3 * jax.random.normal(k[5], (nh,)))


# ------------------------------------------------------------- the kernels
@pytest.mark.parametrize("T", [1, 7, 8, 9, 16, 21])
def test_chunk_scan_is_the_recurrence(T):
    """Lengths on both sides of a chunk's edge (chunk 8)."""
    a = mixer_inputs(T, T)
    with jax.default_matmul_precision("highest"):
        y, H = ssd.ssd_chunk_scan(a["x"], a["dt"], a["A"], a["B"], a["C"],
                                  a["D"], 8)
        y0, H0 = ssd.ssm_recurrence(a["x"], a["dt"], a["A"], a["B"],
                                    a["C"], a["D"])
    assert float(jnp.abs(y - y0).max()) <= STATE_TOL
    assert float(jnp.abs(H - H0).max()) <= STATE_TOL


@pytest.mark.parametrize("last", [0, 6, 7, 8, 12])
def test_chunk_scan_stands_still_under_a_padded_tail(last):
    """``dt = 0`` past ``last``: the state returned over the PADDED
    length is the recurrence's state at ``last``."""
    a = mixer_inputs(40 + last, 21)
    dt = jnp.where(jnp.arange(21)[:, None] <= last, a["dt"], 0.0)
    with jax.default_matmul_precision("highest"):
        _, H = ssd.ssd_chunk_scan(a["x"], dt, a["A"], a["B"], a["C"],
                                  a["D"], 8)
        n = last + 1
        _, H0 = ssd.ssm_recurrence(a["x"][:n], a["dt"][:n], a["A"],
                                   a["B"][:n], a["C"][:n], a["D"])
    assert float(jnp.abs(H - H0).max()) <= STATE_TOL


def test_chunk_scan_continues_from_a_state():
    a = mixer_inputs(5, 21)
    cut = lambda lo, hi: (a["x"][lo:hi], a["dt"][lo:hi], a["A"],    # noqa
                          a["B"][lo:hi], a["C"][lo:hi], a["D"])
    with jax.default_matmul_precision("highest"):
        y0, H0 = ssd.ssm_recurrence(*cut(0, 21))
        _, H1 = ssd.ssd_chunk_scan(*cut(0, 13), 8)
        y2, H2 = ssd.ssd_chunk_scan(*cut(13, 21), 8, h0=H1)
    assert float(jnp.abs(y2 - y0[13:]).max()) <= STATE_TOL
    assert float(jnp.abs(H2 - H0).max()) <= STATE_TOL


def test_state_step_kernel_is_the_formula_in_place():
    """Interpreted kernel = the ``jnp`` formula; rows share no slot,
    the padded rows land in slot 0, other layers and slots untouched."""
    a = mixer_inputs(9, 5)
    pool = jax.random.normal(jax.random.PRNGKey(1), (3, 7, 4, 16, 16))
    slots = jnp.asarray([3, 0, 6, 0, 1])
    args = (a["x"], a["B"], a["C"], a["dt"], a["A"], a["D"])
    got_pool, got_y = ssd.ssm_state_step(pool, 1, slots, *args,
                                         interpret=True)
    want_pool, want_y = ssd.ssm_state_step_xla(pool, 1, slots, *args)
    live = np.asarray([0, 2, 4])
    assert got_y.dtype == jnp.float32 and got_pool.dtype == jnp.float32
    assert float(jnp.abs(got_y - want_y)[live].max()) <= 1e-5
    assert float(jnp.abs(got_pool - want_pool)[:, 1:].max()) <= 1e-6
    # layers 0 and 2 and the slots no row names are bit for bit as before
    assert bool((got_pool[0] == pool[0]).all())
    assert bool((got_pool[2] == pool[2]).all())
    assert bool((got_pool[1, jnp.asarray([2, 4, 5])]
                 == pool[1, jnp.asarray([2, 4, 5])]).all())
    # a step of the kernel = a step of the recurrence
    y1, H1 = ssd.ssm_recurrence(a["x"][:1], a["dt"][:1], a["A"],
                                a["B"][:1], a["C"][:1], a["D"],
                                h0=pool[1, 3])
    assert float(jnp.abs(got_pool[1, 3] - H1).max()) <= 1e-5
    assert float(jnp.abs(got_y[0] - y1[0]).max()) <= 1e-5


# ------------------------------------------------------------- the model
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_forward_logits_match_reference(bench, seed):
    import paddle2_tpu as paddle
    model, _, params = build(bench, seed)
    ids = np.random.default_rng(seed).integers(1, 503, (2, 37))
    got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._data)
    for b in range(2):
        want = ref_logits(bench, params, ids[b].tolist())
        assert np.abs(got[b] - want).max() <= LOGIT_TOL


def test_tolerance_rejects_bf16(bench):
    """The control of LOGIT_TOL: the reference itself with bf16 (and
    int8) matmul operands lies far outside it."""
    from reference import common as rc
    _, _, params = build(bench, 3)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 503, (1, 37)))
    with jax.default_matmul_precision("highest"):
        want = bench["ref"].logits(params, ids, bench["cfg"])
        for prec in ("bfloat16", "int8"):
            low = bench["ref"].logits(params, ids, bench["cfg"],
                                      rc.MATMULS[prec])
            assert float(jnp.abs(low - want).max()) > 20 * LOGIT_TOL, prec


def test_every_branch_weighs_in_the_stream(bench):
    """The seeded scales are not vacuous under the multipliers: with any
    one of the three branches' output matrices zeroed the logits move by
    far more than the tolerance."""
    _, _, params = build(bench, 4)
    ids = np.random.default_rng(4).integers(1, 503, 29).tolist()
    want = ref_logits(bench, params, ids)
    for leaf in ("l0_o", "l0_m_out", "l1_w_down"):
        damaged = dict(params, **{leaf: params[leaf] * 0})
        assert np.abs(ref_logits(bench, damaged, ids) - want).max() \
            > 100 * LOGIT_TOL, leaf


def test_config_takes_published_keys_and_refuses_variants():
    cfg = FalconH1Config()          # the published 72-layer defaults
    assert (cfg.num_hidden_layers, cfg.mamba_d_ssm, cfg.conv_dim) \
        == (72, 4096, 5120)
    assert cfg.num_attention_heads // cfg.num_key_value_heads == 5
    assert FalconH1Config(mamba_d_ssm=None, mamba_n_heads=80).mamba_d_ssm \
        == 2 * 5120
    tiny = falcon_h1_tiny()
    assert tiny.num_attention_heads // tiny.num_key_value_heads == 3
    for bad in (dict(attention_bias=True), dict(mamba_norm_before_gate=True),
                dict(mamba_rms_norm=False), dict(tie_word_embeddings=True),
                dict(rope_scaling={"type": "yarn"}),
                dict(mamba_d_ssm=4000), dict(mlp_multipliers=[1.0])):
        with pytest.raises(ValueError):
            FalconH1Config(**bad)


def test_leaf_at_a_time_placement_gives_make_weights_values(bench):
    """``place_weights`` = ``weights.make_weights``' values, leaf for
    leaf — but ``dt_bias``, which stands around its stated mean."""
    cfg, ref = bench["cfg"], bench["ref"]
    model, _, params = build(bench, 2 ** 31 + 5)
    where = bench["program"].leaf_of_param(cfg, "per_layer")
    seen = 0
    for name, p in model.named_parameters():
        leaf = where[name][0]
        want = params[leaf]
        if leaf.endswith("_m_dtb"):
            want = ref.dt_bias(want, cfg)
            assert abs(float(want.mean()) - cfg["dt_bias_mean"]) < 1.5
        assert np.array_equal(np.asarray(p._data, np.float32),
                              np.asarray(want)), name
        seen += 1
    assert seen == 3 + 17 * cfg["num_hidden_layers"]


# ------------------------------------------------- prefill + paged decode
def test_prefill_then_paged_decode_logits(bench, logit_tap):
    """Prompts that are no multiples of 16 (nor of the block size or
    the chunk, 8), three sequences in one batch: every step's logits
    against the reference's full forward over prompt + generated."""
    model, _, params = build(bench, 5)
    engine = tiny_engine(model)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 503, n).tolist() for n in (5, 21, 37)]
    rids, rows = serve(engine, prompts, 7, logit_tap)
    check_against_reference(bench, params, engine, rids, rows)
    # blocks and slots are back with the manager
    assert engine.allocator.used_count == 0
    assert engine.allocator.state_slots_used == 0
    audit_kv_ledger(engine.allocator, [], state_pools=engine.cache.states)


def test_single_token_prompt_state_is_zero_padded(bench, logit_tap):
    model, _, params = build(bench, 6)
    engine = tiny_engine(model)
    rids, rows = serve(engine, [[17]], 5, logit_tap)
    check_against_reference(bench, params, engine, rids, rows)


def test_prefill_state_is_the_state_at_the_last_real_position(bench):
    """A 21-token prompt is padded to 32: the states handed to the slot
    are those of an unpadded pass over the 21 tokens (the padded END
    would read otherwise: the control of the same name)."""
    model, _, _ = build(bench, 7)
    runner = PagedRunner(model, interpret=True)
    ids = np.random.default_rng(7).integers(1, 503, 21).tolist()
    _, _, _, conv, ssm_state = runner.prefill(ids)
    with runner.bound():
        _, _, states = model.model.full(jnp.asarray([ids], jnp.int32))
    for li, (xbc, H) in enumerate(states):
        assert float(jnp.abs(ssm_state[li] - H[0]).max()) <= STATE_TOL
        assert float(jnp.abs(conv[li] - xbc[0, -3:]).max()) <= STATE_TOL
    with runner.bound():
        padded = jnp.asarray([ids + [0] * 11], jnp.int32)
        _, _, at_end = model.model.full(padded)
    assert float(jnp.abs(at_end[0][1][0] - ssm_state[0]).max()) \
        > 100 * STATE_TOL


@pytest.mark.parametrize("n,m", [(5, 6), (16, 3), (23, 9)])
def test_prefill_plus_decode_is_a_longer_prefill(bench, n, m):
    """A prefill of n tokens + m decode steps leaves the slot's states,
    and yields the tokens, of a prefill of n + m tokens."""
    model, _, _ = build(bench, 8)
    prompt = np.random.default_rng(n).integers(1, 503, n).tolist()
    engine = tiny_engine(model, max_batch=1)
    rid = engine.submit(prompt, m + 1)
    now = 0.0
    while len(engine.sequence(rid).generated) < m + 1:
        now += 1.0
        engine.tick(now)
        if engine.sequence(rid).done:
            break
    gen = list(engine.sequence(rid).generated)
    # the slot after m decode steps (the last token is not fed)
    conv = np.asarray(engine.cache.states["conv"][:, 1])
    ssm_state = np.asarray(engine.cache.states["ssm"][:, 1])
    runner = PagedRunner(model, interpret=True)
    first, _, _, conv2, ssm2 = runner.prefill(prompt + gen[:m])
    assert first == gen[m]
    assert np.abs(conv - np.asarray(conv2)).max() <= STATE_TOL
    assert np.abs(ssm_state - np.asarray(ssm2)).max() <= STATE_TOL


def test_eviction_and_readmission_give_same_logits(bench, logit_tap):
    """A pool too small for the batch: sequences are evicted (blocks
    AND slot freed) and re-prefilled from their token logs; every
    logits row still matches the reference."""
    model, _, params = build(bench, 9)
    engine = tiny_engine(model, num_blocks=12, max_batch=3)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 503, n).tolist() for n in (19, 23, 27)]
    rids, rows = serve(engine, prompts, 12, logit_tap)
    assert engine.scheduler.total_evictions > 0
    check_against_reference(bench, params, engine, rids, rows)
    assert engine.allocator.state_slots_used == 0


@pytest.mark.parametrize("fault", ["drop_decode_step:2",
                                   "drop_decode_step:3,drop_decode_step:5"])
def test_dropped_step_leaves_the_served_tokens(bench, fault, monkeypatch):
    """ROADMAP D13: a discarded step has already moved the states its
    repeat would read. Its rows are re-prefilled, and the served tokens
    are those of an undisturbed run."""
    model, _, _ = build(bench, 10)
    rng = np.random.default_rng(10)
    prompts = [rng.integers(1, 503, n).tolist() for n in (9, 14, 20)]
    want = run_to_idle(tiny_engine(model), prompts, 10)
    monkeypatch.setattr(chaos, "_ACTIVE", chaos.ChaosInjector(fault))
    engine = tiny_engine(model)
    got = run_to_idle(engine, prompts, 10)
    assert engine.state_reprefills >= 3
    assert got == want
    assert engine.allocator.state_slots_used == 0


def test_repeating_a_step_on_a_moved_state_would_differ(bench):
    """The control of the test above: the same step run twice over the
    pools gives other logits the second time (the first moved the
    states), so a plain repeat is not a repair."""
    model, _, _ = build(bench, 10)
    engine = tiny_engine(model, max_batch=1)
    prompt = np.random.default_rng(10).integers(1, 503, 9).tolist()
    rid = engine.submit(prompt, 4)
    engine.admit_and_prefill(0.0)
    seq = engine.sequence(rid)
    fam, cache = engine.runner.family, engine.cache
    args = (jnp.asarray([[seq.tokens[-1]]], jnp.int32),
            jnp.asarray([len(prompt)], jnp.int32),
            jnp.asarray([seq.table.padded(2)], jnp.int32),
            jnp.asarray([seq.table.state_slot], jnp.int32), 8, True, None)
    with engine.runner.bound():
        lg1, k, v, pools, _ = fam.decode(
            cache.k, cache.v, tuple(cache.states.values()), *args)
        lg2, *_ = fam.decode(k, v, pools, *args)
    assert float(jnp.abs(lg1 - lg2).max()) > 100 * LOGIT_TOL


def test_prefix_cache_hit_still_fills_the_state(bench, logit_tap):
    """A prefix hit shares the K/V blocks but runs the whole prefill,
    which is where both states come from."""
    model, _, params = build(bench, 11)
    engine = tiny_engine(model, enable_prefix_cache=True)
    rng = np.random.default_rng(11)
    shared = rng.integers(1, 503, 24).tolist()
    prompts = [shared + rng.integers(1, 503, n).tolist() for n in (3, 6)]
    rids, rows = serve(engine, prompts[:1], 4, logit_tap)
    rids2, rows2 = serve(engine, prompts[1:], 4, logit_tap)
    assert engine.sequence(rids2[0]).prefix_cached_tokens >= 16
    check_against_reference(bench, params, engine, rids + rids2,
                            {**rows, **rows2})


def test_admissions_hold_a_bounded_number_of_states_in_flight(
        bench, monkeypatch):
    """A prefill's states live from its enqueueing to their write: past
    ``STATE_AHEAD_BYTES`` of them an admission reads its first token in
    place (which waits for every prefill before it); the tokens served
    are the same."""
    from paddle2_tpu.serving import engine as engine_module
    model, _, _ = build(bench, 15)
    rng = np.random.default_rng(15)
    prompts = [rng.integers(1, 503, 9).tolist() for _ in range(4)]
    want = run_to_idle(tiny_engine(model), prompts, 4)
    engine = tiny_engine(model)
    monkeypatch.setattr(engine_module, "STATE_AHEAD_BYTES",
                        2 * engine.cache.state_slot_bytes)
    for p in prompts:
        engine.submit(p, 4)
    assert len(engine.admit_and_prefill(0.0)) == 4
    # the third found two states in flight and read all three back
    assert engine.prefill_ahead == 3 and len(engine._firsts) == 1
    now = 0.0
    while not engine.idle():
        now += 1.0
        engine.tick(now)
    assert [list(engine.sequence(r).generated) for r in range(4)] == want


# ---------------------------------------------------- the two state kinds
@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16"])
def test_two_state_kinds_one_slot_id(bench, kv_dtype):
    """``conv`` in the cache's dtype, ``ssm`` float32 whatever it is;
    one allocator, one slot id for both; the ledger closes over them;
    the bytes a slot holds are counted."""
    model, mcfg, _ = build(bench, 12)
    engine = tiny_engine(model, max_batch=2, kv_dtype=kv_dtype)
    cache, alloc = engine.cache, engine.allocator
    L = mcfg.num_hidden_layers
    assert list(cache.states) == ["conv", "ssm"]
    assert cache.states["conv"].shape == (L, 3, 3, mcfg.conv_dim)
    assert cache.states["conv"].dtype == jnp.dtype(kv_dtype)
    assert cache.states["ssm"].shape == (L, 3, 4, 16, 16)
    assert cache.states["ssm"].dtype == jnp.float32
    assert cache.state_slot_bytes == L * (
        3 * mcfg.conv_dim * jnp.dtype(kv_dtype).itemsize + 4 * 16 * 16 * 4)
    assert alloc.state_slots == 2
    rid = engine.submit([5, 6, 7], 3)
    engine.admit_and_prefill(0.0)
    slot = engine.sequence(rid).table.state_slot
    assert slot in (1, 2)
    for pool in cache.states.values():          # the one id, every kind
        assert float(jnp.abs(pool[:, slot]).max()) > 0
        assert float(jnp.abs(pool[:, 3 - slot]).max()) == 0
    census = audit_kv_ledger(
        alloc, [engine.sequence(rid).table.blocks],
        live_state_slots=[slot], state_pools=cache.states)
    assert census["state_kinds"] == 2 and census["state_slots_claimed"] == 1
    with pytest.raises(BlockFreeError):
        audit_kv_ledger(alloc, [engine.sequence(rid).table.blocks],
                        live_state_slots=[slot],
                        state_pools={"ssm": cache.states["ssm"][:, :2]})


@pytest.mark.parametrize("feature", [
    dict(weight_only_int8=True), dict(weight_only_lm_head=True),
    dict(spec=SpeculativeConfig(num_draft_tokens=2)),
    dict(enable_prefix_cache=True, enable_kv_spill=True)])
def test_engine_refuses_what_the_family_lacks(bench, feature):
    model, _, _ = build(bench, 13)
    with pytest.raises(ValueError, match="not served with"):
        tiny_engine(model, **feature)


def test_artifact_path_serves_the_family(bench, tmp_path):
    """jit.save -> inference.Config -> create_serving_engine: the tokens
    of the live-model engine."""
    import paddle2_tpu as paddle
    from paddle2_tpu import inference
    from paddle2_tpu.models import FalconH1ForCausalLM
    model, mcfg, _ = build(bench, 14)
    prompt = np.random.default_rng(14).integers(1, 503, 13).tolist()
    want = run_to_idle(tiny_engine(model), [prompt], 5)
    path = str(tmp_path / "model")
    paddle.jit.save(model, path)
    conf = inference.Config(path)
    conf.enable_continuous_batching(block_size=8, num_blocks=64,
                                    max_batch=4, max_model_len=96,
                                    kv_dtype="float32", interpret=True)
    engine = conf.create_serving_engine(gpt_config=mcfg)
    assert isinstance(engine.model, FalconH1ForCausalLM)
    assert run_to_idle(engine, [prompt], 5) == want
