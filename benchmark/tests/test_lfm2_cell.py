"""The LFM2-24B-A2B configuration and its cell: the configuration file
against the published keys, operation and byte counts against
hand-worked numbers, the traffic file's parameters, and the new
readers on hand-built spans (and on a program that wrote none)."""

import json
import os

import pytest

import moe_trace
import program_trace as P
import roofline
import run
import trafficgen
from roofline import lfm2_moe, paged_decode

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "lfm2moe-serve-doc3k-backlog"
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 64, "num_experts_per_tok": 4, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")) as f:
        return json.load(f)


def test_every_published_width_is_unchanged(cfg):
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    # one dense layer + one whole period, attention 1 in 4 of the period
    assert cfg["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                  "conv"]
    assert cfg["num_hidden_layers"] == 5 and cfg["num_dense_layers"] == 1
    assert {"tie_word_embeddings", "head_dim"} <= set(cfg["assumed"])
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]


def test_parameter_count_of_the_cut(cfg):
    from common import load_module
    specs = load_module("reference", cfg["reference"]).leaf_specs(cfg)
    total = 0
    for shape, _, _ in specs.values():
        n = 1
        for d in shape:
            n *= d
        total += n
    # embedding 134.2 M, dense layer 72.4 M, 4 conv operators 16.8 M,
    # attention 10.5 M, 4 x 64 experts of 9.44 M + routers: 2,700 M
    assert total == cfg["parameters"]
    assert 2.69e9 < total < 2.71e9
    # every parameter of the layout is a leaf and the other way round
    layout = cfg["program"]["layouts"]["per_layer"]
    assert set(layout) == set(specs)


def test_grouped_matmul_counts_only_what_is_hit():
    # one decode step of one layer: 256 assignments on 63 experts
    flops, nbytes = lfm2_moe.moe_gmm(256, 63, 2048, 1536)
    assert flops == 6 * 256 * 2048 * 1536 == 4_831_838_208
    weights = 3 * 63 * 2048 * 1536 * 2            # 1.189 GB
    rows = 256 * (3 * 2048 + 3 * 1536) * 2        # 5.5 MB
    assert nbytes == weights + rows == 1_194_590_208
    peaks = roofline.peaks_for("TPU v5 lite")
    t, bound = roofline.roofline_seconds(flops, nbytes, peaks)
    assert bound == "memory" and t == pytest.approx(1.4586e-3, rel=1e-3)
    # a 3072-token prefill, every expert hit: 192 rows an expert are
    # still under the ridge (240 operations a byte): 1.18 ms of MXU
    # against 1.79 ms of weights and rows; four such documents at once
    # would be bound by the MXU
    f2, b2 = lfm2_moe.moe_gmm(3072 * 4, 64, 2048, 1536)
    assert roofline.roofline_seconds(f2, b2, peaks)[1] == "memory"
    f3, b3 = lfm2_moe.moe_gmm(4 * 3072 * 4, 64, 2048, 1536)
    assert roofline.roofline_seconds(f3, b3, peaks)[1] == "compute"
    # an expert nobody is routed to adds nothing
    assert lfm2_moe.moe_gmm(256, 40, 2048, 1536)[1] < nbytes


def test_gqa_decode_reads_the_key_value_heads_of_the_attention_layers(cfg):
    # 64 rows of 2000 tokens, ONE attention layer, 8 heads x 64, bf16:
    # 128000 x 512 x 2 (K, V) x 2 B = 262 MB — an eighth of the 32 heads
    flops, nbytes = paged_decode.paged_decode(
        64 * 2000, cfg["layer_types"].count("full_attention"),
        cfg["num_key_value_heads"], cfg["head_dim"])
    assert nbytes == 128000 * 512 * 2 * 2 == 262_144_000


def test_operations_per_token(cfg):
    # forward per token at sequence 2048: head 268.4 M; 4 conv operators
    # of 33.6 M; attention 21.0 M + 2 x 2048 x 2048 scores; dense 144.7
    # M; 4 expert layers of 4 x 18.87 M + router 0.26 M
    fwd = (2 * 2048 * 65536 + 4 * (8 * 2048 * 2048 + 6 * 2048)
           + 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 2 * 2048 * 2048
           + 6 * 2048 * 11776
           + 4 * (6 * 2048 * 1536 * 4 + 2 * 2048 * 64))
    assert lfm2_moe.ops_per_token(cfg, 2048) == 3.0 * fwd
    assert 0.87e9 < fwd < 0.89e9          # ~0.44 B active parameters x 2


def test_traffic_is_the_issue_s(cfg):
    traffic = trafficgen.load_traffic("doc3k-backlog")
    assert traffic["arrival"]["process"] == "at-once"
    assert traffic["prompt_len"] == {
        "law": "lognormal", "median": 1536, "sigma": 0.5, "min": 512,
        "max": 3072, "round_up_to": [1024, 2048, 3072]}
    assert traffic["output_len"] == {
        "law": "lognormal", "median": 192, "sigma": 0.5, "min": 32,
        "max": 512}
    pop = trafficgen.population(traffic, 45.0)
    assert pop["n"] == round(traffic["arrival"]["rate_per_s"] * 45)
    assert set(pop["prompt_len"]) == {1024, 2048, 3072}
    assert pop["prompt_len"].max() + pop["output_len"].max() <= 4096
    reqs = trafficgen.requests(traffic, 2 ** 31 + 5, 45.0, 65536)
    assert all(r["due_s"] == 0.0 for r in reqs)
    again = trafficgen.requests(traffic, 2 ** 31 + 5, 45.0, 65536)
    assert reqs[3]["prompt"] == again[3]["prompt"]


def test_cell_in_the_manifest_and_its_files():
    cell = run.load_cell(CELL, False)
    assert cell["chips"] == 1 and cell["config"]["name"] == "lfm2-24b-a2b"
    eng = cell["workload"]["engine"]
    assert (eng["block_size"], eng["num_blocks"], eng["max_batch"],
            eng["max_model_len"]) == (16, 16384, 64, 4096)
    assert eng["num_blocks"] * eng["block_size"] \
        == eng["max_batch"] * eng["max_model_len"]
    names = {m["name"] for m in run.metrics_of(cell, "per_layer")}
    assert {"moe_gmm_roofline_pct.serve",
            "paged_decode_gqa_roofline_pct.serve", "moe_device_pct.serve",
            "conv_device_pct.serve", "moe_load_max_over_mean.serve",
            "device_idle_pct.serve", "decode_tick_ms.serve"} <= names
    assert "paged_decode_roofline_pct.serve" not in names
    assert {m["name"] for m in run.metrics_of(cell, "end_to_end")} \
        == {"serve_tokens_per_s", "setup_s"}
    for name in names:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name


# ------------------------------------------------------------ the readers
class _Trace:
    window = (0.0, 1000.0)
    devices = {"d": []}


def _ctx(monkeypatch, spans, ops):
    pt = P.ProgramTrace(spans=spans, ops={"d": ops})
    monkeypatch.setattr(P, "of", lambda ctx: pt)
    return {"trace": _Trace(), "cell": {"config": {
        "num_experts": 64, "num_dense_layers": 1,
        "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
        "hidden_size": 2048, "moe_intermediate_size": 1536}}}


def test_load_ratio_by_hand(monkeypatch):
    counts = {"moe_assignments": 1024, "moe_experts_hit": 250,
              "moe_load_max": 10}
    ctx = _ctx(monkeypatch, [
        ("decode.dispatch", 10.0, 20.0, dict(counts, rows=64)),
        ("decode.dispatch", 30.0, 40.0, dict(counts, moe_load_max=6)),
        ("prefill", 50.0, 60.0, dict(counts, moe_load_max=400)),
        ("decode.dispatch", 70.0, 80.0, {"rows": 64})], [])
    assert [n for n, _ in moe_trace.routing_counts(ctx)] == [
        "decode.dispatch", "decode.dispatch", "prefill"]
    # mean load = 1024 / 4 layers / 64 experts = 4: ratios 2.5 and 1.5
    assert run.read_layer_metric("moe_load_max_over_mean.serve", ctx) \
        == pytest.approx(2.0)


def test_scope_share_by_hand(monkeypatch):
    ops = [("fusion.1", 0.0, 100.0, "jit(p2t_decode)/moe/experts/dot"),
           ("moe_gmm", 100.0, 400.0,
            "jit(p2t_decode)/moe/experts/jit(_gmm)/moe_gmm/pallas_call"),
           ("fusion.2", 400.0, 450.0, "jit(p2t_decode)/conv/state_write/x"),
           ("fusion.3", 450.0, 500.0, "jit(p2t_decode)/attn/remove"),
           ("fusion.4", 600.0, 700.0, "jit(p2t_decode)/mlp/dot")]
    ctx = _ctx(monkeypatch, [], ops)
    assert run.read_layer_metric("moe_device_pct.serve", ctx) \
        == pytest.approx(100.0 * 400 / 600)
    assert run.read_layer_metric("conv_device_pct.serve", ctx) \
        == pytest.approx(100.0 * 50 / 600)


@pytest.mark.parametrize("metric", [
    "moe_load_max_over_mean.serve", "moe_device_pct.serve",
    "conv_device_pct.serve"])
def test_readers_find_nothing_in_an_older_programs_trace(monkeypatch,
                                                         metric):
    """A program without the counts or the scopes (the parent commit; a
    stale executable): None, never a number and never an error."""
    ops = [("fusion.1", 0.0, 100.0, "jit(p2t_decode)/attn/dot")]
    ctx = _ctx(monkeypatch, [("decode.dispatch", 1.0, 2.0, {"rows": 64})],
               ops)
    assert run.read_layer_metric(metric, ctx) is None


# ------------------------------------------- the routed comparison, tiny
@pytest.fixture(scope="module")
def routed():
    """The tiny configuration, and one request as a SOUND program would
    have served it: the float32 reference's own tokens and experts."""
    import jax.numpy as jnp
    import numpy as np
    from common import load_module
    from weights import make_weights
    cell = run.load_cell(CELL, True)
    # weights large enough that, at hidden 64, the experts move the
    # argmax of a 503-word vocabulary within a dozen tokens
    cfg = dict(cell["config"], initializer_range=0.2)
    ref = load_module("reference", cfg["reference"])
    params = make_weights(ref.leaf_specs(cfg), 11, jnp.float32)
    prompt = np.random.default_rng(11).integers(1, 503, 21).tolist()
    seq = list(prompt)
    import jax
    step = jax.jit(lambda ids: ref.forward(params, ids, cfg))
    for n in range(9):          # greedy, one token at a time
        ids = np.zeros((1, 32), np.int32)
        ids[0, :len(seq)] = seq
        lg, used, _ = step(jnp.asarray(ids))
        seq.append(int(lg[0, len(seq) - 1].argmax()))
    used = used[:, :len(seq) - 1]
    sound = {"prompt": prompt, "tokens": seq[len(prompt):],
             "routed": np.asarray(used[0])}
    return cfg, ref, sound


def _numbers(routed, sample, **how):
    from drivers import serve_routed
    cfg, ref, _ = routed
    return serve_routed.routed_numbers(serve_routed.routed_token_gaps(
        ref, cfg, 11, [sample], 32, 16, **how))


def test_sound_tokens_with_their_experts_read_zero(routed):
    got = _numbers(routed, routed[2])
    assert got == {"token_logit_gap": 0.0, "routing_score_gap": 0.0}


def test_another_choice_of_experts_is_seen_and_followed(routed):
    """Experts the reference would not have taken: the deficit says so,
    and the logits the tokens are judged by are those of THAT choice."""
    import numpy as np
    sample = dict(routed[2])
    sample["routed"] = (sample["routed"] + 1) % 8
    got = _numbers(routed, sample)
    assert got["routing_score_gap"] > 0.01
    # one flip at one position moves only what follows it
    sample["routed"] = np.array(routed[2]["routed"])
    sample["routed"][-1, 0] = (sample["routed"][-1, 0] + 1) % 8
    got = _numbers(routed, sample)
    assert got["routing_score_gap"] > 0.0


@pytest.mark.parametrize("damage,number", [
    ("experts_removed", "token_logit_gap"),
    ("bias_dropped", "routing_score_gap")])
def test_a_damaged_model_in_the_programs_place_reads_above_zero(
        routed, damage, number):
    import control_freed
    got = _numbers(routed, routed[2], damage=control_freed.DAMAGES[damage])
    assert got[number] > 0.0, got


def test_int8_damage_touches_the_experts_alone(routed):
    """(a dozen tiny tokens do not show it in the argmax: the chip's
    readings at the cell's size are in PERF.md)"""
    import control_freed
    import jax.numpy as jnp
    import numpy as np
    from weights import make_weights
    cfg, ref, _ = routed
    specs = ref.leaf_specs(cfg)
    clean = make_weights(specs, 11, jnp.float32)
    hurt = control_freed.DAMAGES["experts_int8"](
        make_weights(specs, 11, jnp.float32))
    for name, w in clean.items():
        moved = np.abs(np.asarray(hurt[name]) - np.asarray(w))
        if name.startswith("moe") and name[-3:] in ("_w1", "_w3", "_w2"):
            step = np.abs(np.asarray(w)).max(-2, keepdims=True) / 127
            assert 0 < moved.max() and (moved <= step * 0.501).all()
        else:
            assert moved.max() == 0.0, name


def test_rehearsal_limits_name_both_numbers():
    for rehearse in (False, True):
        wl = run.load_cell(CELL, rehearse)["workload"]
        assert wl["driver"] == "serve_routed"
        assert set(wl["check"]["limits"]) == {
            "token_logit_gap", "routing_score_gap", "window_compiles"}
