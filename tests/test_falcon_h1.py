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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle2_tpu.kernels import ssd
from paddle2_tpu.models import FalconH1Config, falcon_h1_tiny
from paddle2_tpu.serving.block_cache import BlockFreeError, audit_kv_ledger
from paddle2_tpu.serving.spec import SpeculativeConfig
from served import (TINY_ENGINE, LOGIT_TOL, STATE_TOL, build,  # noqa: F401
                    run_to_idle, shared_programs, ref_logits_highest as
                    ref_logits, tiny_engine)
from served import falcon_h1_bench as bench

pytestmark = pytest.mark.usefixtures("shared_programs")


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
    conf.enable_continuous_batching(**TINY_ENGINE)
    engine = conf.create_serving_engine(gpt_config=mcfg)
    assert isinstance(engine.model, FalconH1ForCausalLM)
    assert run_to_idle(engine, [prompt], 5) == want
