"""Serving subsystem, the engine and what stands on it: ServingEngine against
``generate``, eviction exactness, the program census, artifacts, the
deterministic simulation and its gates, the metrics and inference
satellites (moved from ``test_serving.py``, which keeps the paged-attention
kernel, the block cache and the scheduler)."""

import os
import threading

import numpy as np
import pytest
import jax.numpy as jnp

import paddle2_tpu as paddle
from paddle2_tpu.serving import (EngineConfig, ServingEngine, poisson_trace,
                                 simulate_predictor_baseline, simulate_serving)
from paddle2_tpu.serving.simulate import cost_seconds
from served import shared_programs  # noqa: F401

pytestmark = pytest.mark.usefixtures("shared_programs")


# ------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def tiny_model():
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(0)
    return GPTForCausalLM(gpt_tiny(use_scan=False))


def _engine(model, **over):
    kw = dict(block_size=8, num_blocks=32, max_batch=4,
              prefill_budget_tokens=64, max_model_len=64)
    kw.update(over)
    return ServingEngine(model, config=EngineConfig(**kw))


def _drain(eng, max_steps=300):
    steps = 0
    while not eng.idle() and steps < max_steps:
        eng.tick(now=float(steps))
        steps += 1
    assert eng.idle(), "engine did not drain"


def test_engine_matches_generate_greedy(tiny_model):
    eng = _engine(tiny_model)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, tiny_model.cfg.vocab_size, size=12).tolist()
    rid = eng.submit(prompt, max_new_tokens=4)
    _drain(eng)
    ref = tiny_model.generate(np.asarray(prompt, np.int32)[None],
                              max_new_tokens=4, temperature=0.0)
    ref = np.asarray(ref.numpy())[0][len(prompt):].tolist()
    assert eng.sequence(rid).generated == ref


def test_engine_eviction_exactness(tiny_model):
    """ACCEPTANCE: block exhaustion -> eviction -> requeue ->
    re-prefill, with final tokens identical to an uncontended run."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, tiny_model.cfg.vocab_size,
                            size=14).tolist() for _ in range(4)]

    def run(num_blocks):
        eng = _engine(tiny_model, num_blocks=num_blocks)
        rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
        _drain(eng)
        return eng, rids

    eng_big, rids_big = run(64)
    eng_tight, rids_tight = run(10)         # 9 usable blocks
    assert eng_tight.scheduler.total_evictions >= 1
    for a, b in zip(rids_big, rids_tight):
        assert (eng_big.sequence(a).generated
                == eng_tight.sequence(b).generated)


def test_engine_program_count_bounded(tiny_model):
    """ACCEPTANCE: compiled decode programs <= the fixed bucket count
    across shifting batch compositions (no per-composition recompile).
    """
    eng = _engine(tiny_model)
    rng = np.random.default_rng(5)
    for wave in ([6, 10], [8], [5, 7, 9]):  # varying compositions
        for n in wave:
            eng.submit(rng.integers(0, tiny_model.cfg.vocab_size,
                                    size=n).tolist(), max_new_tokens=4)
        _drain(eng)
    assert eng.num_decode_programs <= eng.program_budget
    # same bucket, different composition: the dict can't grow past the
    # grid even in principle
    assert set(eng.runner._decode_programs) <= {
        (b, p) for b in eng.scheduler.config.batch_buckets
        for p in eng.scheduler.config.page_buckets}


def test_engine_weight_only_int8(tiny_model):
    """Opt-in int8 weight-only quantization: projections swapped, the
    engine still serves, embeddings/head untouched."""
    import copy
    from paddle2_tpu.quantization import WeightOnlyLinear
    model = copy.deepcopy(tiny_model)
    eng = _engine(model, weight_only_int8=True)
    blk = model.gpt.h[0]
    assert isinstance(blk.attn.qkv, WeightOnlyLinear)
    assert isinstance(blk.mlp.up, WeightOnlyLinear)
    assert not isinstance(model.gpt.wte, WeightOnlyLinear)
    rid = eng.submit([3, 1, 4, 1, 5, 9, 2, 6], max_new_tokens=4)
    _drain(eng)
    gen = eng.sequence(rid).generated
    assert len(gen) == 4
    assert all(0 <= t < model.cfg.vocab_size for t in gen)


def test_engine_from_jit_save_artifact(tiny_model, tmp_path):
    """ServingEngine wraps a jit.save'd GPT artifact: weights round-
    trip into the rebuilt architecture and serving output matches the
    live-model engine."""
    from paddle2_tpu.jit.api import save
    from paddle2_tpu.models.gpt import gpt_tiny
    path = str(tmp_path / "gpt_artifact")
    save(tiny_model, path)                  # weights-only artifact
    eng = ServingEngine(
        artifact_path=path, gpt_config=gpt_tiny(use_scan=False),
        config=EngineConfig(block_size=8, num_blocks=32, max_batch=4,
                            max_model_len=64))
    live = _engine(tiny_model)
    prompt = [7, 8, 9, 10, 11, 12]
    r1 = eng.submit(prompt, max_new_tokens=4)
    r2 = live.submit(prompt, max_new_tokens=4)
    _drain(eng)
    _drain(live)
    assert eng.sequence(r1).generated == live.sequence(r2).generated
    # the Config route honors an explicit params file exactly like
    # create_predictor does (weights moved away from the prefix)
    from paddle2_tpu import inference
    moved = str(tmp_path / "weights_moved.bin")
    os.rename(path + ".pdiparams", moved)
    cfg = inference.Config()
    cfg.set_model(path + ".pdmodel", moved)
    cfg.enable_continuous_batching(block_size=8, num_blocks=32,
                                   max_batch=4, max_model_len=64)
    eng2 = cfg.create_serving_engine(gpt_config=gpt_tiny(use_scan=False))
    r3 = eng2.submit(prompt, max_new_tokens=4)
    _drain(eng2)
    assert eng2.sequence(r3).generated == live.sequence(r2).generated


def test_engine_serves_artifact_in_its_saved_dtypes(tmp_path):
    """A bf16 (amp O2) artifact is served in bf16 — not widened back to
    the rebuilt architecture's f32 defaults — with a bf16 KV pool, and
    still matches generate token-for-token."""
    from paddle2_tpu import inference
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(3)
    cfg = gpt_tiny(use_scan=False)
    model = paddle.amp.decorate(GPTForCausalLM(cfg), level="O2",
                                dtype="bfloat16")
    model.eval()
    path = str(tmp_path / "bf16_artifact")
    paddle.jit.save(model, path)
    conf = inference.Config(path)
    conf.enable_continuous_batching(block_size=8, num_blocks=32,
                                    max_batch=4, max_model_len=64,
                                    kv_dtype="bfloat16")
    eng = conf.create_serving_engine(gpt_config=cfg)
    want = {n: str(p._data.dtype) for n, p in model.named_parameters()}
    got = {n: str(p._data.dtype) for n, p in eng.model.named_parameters()}
    assert got == want and "bfloat16" in set(got.values())
    assert eng.cache.k.dtype == jnp.bfloat16
    prompt = list(range(3, 16))              # decode crosses a page
    rid = eng.submit(prompt, max_new_tokens=6)
    _drain(eng)
    ref = np.asarray(model.generate(
        np.asarray([prompt], np.int32), max_new_tokens=6,
        temperature=0.0)._data)[0, len(prompt):].tolist()
    assert eng.sequence(rid).generated == ref


def test_engine_rejects_stacked_blocks():
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    model = GPTForCausalLM(gpt_tiny(stacked_blocks=True))
    with pytest.raises(ValueError, match="stacked_blocks"):
        ServingEngine(model, config=EngineConfig())


# ------------------------------------------------- simulation + the gates
def test_sim_deterministic_and_disaggregated(tiny_model):
    trace = poisson_trace(6, rate_per_s=500.0, prompt_lens=[10, 14],
                          gen_tokens=[4, 6],
                          vocab=tiny_model.cfg.vocab_size, seed=11)
    reps = [simulate_serving(_engine(tiny_model), trace)
            for _ in range(2)]
    assert reps[0].tokens_per_s == reps[1].tokens_per_s
    assert reps[0].p99_ttft_s == reps[1].p99_ttft_s
    assert reps[0].total_tokens == 6 * 5    # mean gen = 5
    assert reps[0].kv_ratio <= 0.55


def test_sim_prefill_lane_does_not_starve_decode(tiny_model):
    """ACCEPTANCE (disaggregation): a huge prefill landing mid-stream
    must not stall the decode batch — running sequences keep producing
    a token per decode step while the prefill lane chews."""
    eng = _engine(tiny_model, prefill_budget_tokens=64)
    # request 0: long generation, admitted first
    r0 = eng.submit([1, 2, 3, 4, 5, 6, 7, 8], max_new_tokens=12)
    eng.admit_and_prefill(now=0.0)
    # request 1: a LONG prompt arrives; its prefill occupies the lane
    # far into the future
    r1 = eng.submit(list(range(1, 49)), max_new_tokens=2)
    eng.admit_and_prefill(now=0.0,
                          ready_at_fn=lambda info: 1e6)  # lane busy
    # decode steps keep running for r0 even though r1's prefill is
    # "in flight" on the lane
    produced = 0
    now = 0.0
    for _ in range(12):
        step = eng.decode_once(now=now)
        if step is None:
            break
        assert step["n_active"] == 1        # r1 never joins (held)
        produced += step["tokens"]
        now += 1e-3
    assert produced == 11                   # 12 total - 1 from prefill
    assert eng.sequence(r0).done
    assert not eng.sequence(r1).done        # still held by the lane


@pytest.mark.slow
def test_sim_beats_predictor_baseline(tiny_model):
    """Smoke-scale version of the bench's 3x gate: under saturating
    load, continuous batching beats one-at-a-time on the same trace
    and the same cost primitives. Marked slow — CI's serving-smoke
    job enforces the full gate via bench.py --serving."""
    probe = _engine(tiny_model)
    tr0 = poisson_trace(2, 100.0, [10], [4],
                        tiny_model.cfg.vocab_size, seed=1)
    simulate_serving(probe, tr0)
    b1 = min(probe.runner._decode_costs)
    decode_s = cost_seconds(probe.runner.decode_cost(b1))
    rate_req = 5.0 / decode_s / 6.0         # ~5x b1 token capacity
    trace = poisson_trace(16, rate_req, [10, 14], [4, 8],
                          tiny_model.cfg.vocab_size, seed=13)
    eng = _engine(tiny_model)
    rep = simulate_serving(eng, trace)
    base = simulate_predictor_baseline(eng, trace)
    assert rep.tokens_per_s > 1.5 * base.tokens_per_s
    assert rep.decode_programs <= rep.program_budget


# ----------------------------------------------------- metrics satellites
def test_serving_reports_tokens_explicitly(tiny_model, tmp_path):
    """Serving decode steps write step records with EXPLICIT token
    counts — never inferred from arg shapes (the engine's programs
    consume int32 block tables that a shape sniffer could misread)."""
    from paddle2_tpu.observability import metrics
    metrics.enable(str(tmp_path), rank=0, flush_steps=1)
    try:
        eng = _engine(tiny_model)
        eng.submit([5, 6, 7, 8, 9, 10], max_new_tokens=3)
        eng.submit([1, 2, 3, 4, 5, 6], max_new_tokens=3)
        _drain(eng)
        metrics.flush()
    finally:
        metrics.disable()
    import json
    recs = [json.loads(l) for l in
            open(os.path.join(str(tmp_path), "metrics_rank_0.jsonl"))]
    steps = [r for r in recs if r.get("type") == "step"
             and r.get("serving")]
    assert steps
    # explicit per-step token counts == active sequences, and the
    # deterministic modeled cost rides along for perf_doctor
    assert all(r["tokens"] == round(r["batch_occupancy"] * 4)
               for r in steps)
    assert all("modeled_step_s" in r for r in steps)
    snap = [r for r in recs if r.get("type") == "metrics"][-1]
    assert snap["counters"]["serving_decode_tokens_total"][""] == \
        sum(r["tokens"] for r in steps)


def test_train_step_token_heuristic_rejects_int8(tmp_path):
    """SATELLITE: an int8 2-D first arg (quantized KV / payload) must
    never be counted as tokens by the train-step heuristic; int32 ids
    still are."""
    from types import SimpleNamespace
    import json
    from paddle2_tpu.jit.train_step import TrainStepProgram
    from paddle2_tpu.observability.metrics import MetricsPlane
    fake = SimpleNamespace(_compiled={}, _scaler=None)
    pl = MetricsPlane(str(tmp_path), rank=0, flush_steps=10_000)
    int8_kv = np.zeros((4, 32), np.int8)
    TrainStepProgram._note_step_metrics(fake, pl, [int8_kv], False)
    ids32 = np.zeros((4, 32), np.int32)
    TrainStepProgram._note_step_metrics(fake, pl, [ids32], False)
    recs = [json.loads(l) for l in pl._buffer
            if '"type": "step"' in l]
    assert len(recs) == 2
    assert "tokens" not in recs[0]          # int8: NOT tokens
    assert recs[0]["samples"] == 4
    assert recs[1]["tokens"] == 4 * 32      # int32 ids: tokens


# --------------------------------------------------- inference satellites
def _save_tiny_artifact(tmp_path, name="m"):
    from paddle2_tpu import nn
    from paddle2_tpu.jit.api import InputSpec, save
    paddle.seed(1)
    layer = nn.Linear(4, 3)
    path = str(tmp_path / name)
    save(layer, path, input_spec=[InputSpec([None, 4], "float32")])
    return layer, path


def test_config_set_model_honors_params_file(tmp_path):
    """SATELLITE regression: the explicit params_file argument was
    accepted but ignored (prefix-derived path always won)."""
    from paddle2_tpu import inference
    layer, path = _save_tiny_artifact(tmp_path)
    moved = str(tmp_path / "weights_elsewhere.bin")
    os.rename(path + ".pdiparams", moved)
    cfg = inference.Config()
    cfg.set_model(path + ".pdmodel", moved)
    assert cfg.params_file() == moved
    pred = inference.create_predictor(cfg)
    x = np.ones((2, 4), np.float32)
    out = pred.run([x])[0]
    ref = layer(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-6)
    # constructor path honors it too
    cfg2 = inference.Config(path + ".pdmodel", moved)
    assert cfg2.params_file() == moved
    inference.create_predictor(cfg2)
    # prefix fallback still intact
    cfg3 = inference.Config()
    cfg3.set_model(path + ".pdmodel")
    assert cfg3.params_file() == path + ".pdiparams"


def test_predictor_pool_concurrent_handout(tmp_path):
    """SATELLITE: PredictorPool acquire/release is thread-safe."""
    from paddle2_tpu import inference
    layer, path = _save_tiny_artifact(tmp_path, "pool")
    pool = inference.PredictorPool(inference.Config(path), size=3)
    x = np.ones((1, 4), np.float32)
    ref = np.asarray(layer(paddle.to_tensor(x)).numpy())
    errors = []
    seen = set()
    mu = threading.Lock()

    def worker():
        try:
            for _ in range(5):
                p = pool.acquire(timeout=10.0)
                with mu:
                    seen.add(id(p))
                out = p.run([x])[0]
                np.testing.assert_allclose(out, ref, rtol=1e-5)
                pool.release(p)
        except Exception as e:              # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(pool._free) == 3             # every slot returned
    p = pool.acquire()
    pool.release(p)
    with pytest.raises(ValueError):
        pool.release(p)                     # double release
    assert pool.retrieve(0) is pool._preds[0]


def test_config_enable_continuous_batching_flag():
    from paddle2_tpu import inference
    cfg = inference.Config("some/model")
    assert not cfg.continuous_batching_enabled()
    cfg.enable_continuous_batching(block_size=16, max_batch=8)
    assert cfg.continuous_batching_enabled()


def test_config_create_serving_engine_requires_enable():
    from paddle2_tpu import inference
    with pytest.raises(ValueError, match="enable_continuous_batching"):
        inference.Config("x").create_serving_engine(gpt_config=None)
