"""The SDAR-30B-A3B-Chat configuration and its cell: the configuration
file against the published keys, the byte counts against hand-worked
numbers, the traffic file's parameters, and the check itself at the
rehearsal's tiny size — it reads (all but) nothing on a sound run, and
every damaged engine and stand-in precision moves a number."""

import json
import os

import pytest

import control_blockdiff
import run
import trafficgen
from roofline import sdar_moe

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "sdar-serve-gen512-backlog"
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json")) as f:
        return json.load(f)


def test_every_published_width_is_unchanged(cfg):
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 4
    assert {"block_length", "mask_token_id", "logit_shift",
            "unmask_strategy", "initializer_range"} <= set(cfg["assumed"])


def test_parameter_count_of_the_cut(cfg):
    from common import load_module
    specs = load_module("reference", cfg["reference"]).leaf_specs(cfg)
    total = 0
    for shape, _, _ in specs.values():
        n = 1
        for d in shape:
            n *= d
        total += n
    assert total == cfg["parameters"] == 3_114_814_464
    layer = 18_874_368 + 4_352 + 262_144 + 128 * 4_718_592
    assert total == 4 * layer + 2 * 311_164_928 + 2_048


def test_paged_block_counts_a_context_once_a_pass():
    # 64 sequences of 2,500 cached positions, one pass, 4 layers
    flops, nbytes = sdar_moe.paged_block(64 * 2500, 4, 32, 4, 128, 4)
    assert nbytes == 2 * 64 * 2500 * 4 * 128 * 4 * 2       # 1.31 GB
    assert flops == 4 * 64 * 2500 * 4 * 32 * 128 * 4
    # the block length moves the operations, never the bytes
    assert sdar_moe.paged_block(64 * 2500, 4, 32, 4, 128, 8)[1] == nbytes


def test_traffic_is_the_issues(cfg):
    traffic = trafficgen.load_traffic("gen512-backlog")
    pop = trafficgen.population(traffic, 45.0)
    assert set(pop["prompt_len"]) <= {512, 1024, 2048}
    assert set(pop["output_len"]) == {256, 512, 1024}
    share = [(pop["output_len"] == n).mean() for n in (256, 512, 1024)]
    assert [round(s, 1) for s in share] == [0.4, 0.4, 0.2]
    assert (pop["prompt_len"] + pop["output_len"]).max() <= 3072
    assert pop["gaps"].max() == 0.0                  # a backlog
    cell = run.load_cell(CELL, False)
    eng = cell["workload"]["engine"]
    assert (eng["denoising_steps"], cfg["block_length"]) == (2, 4)
    assert eng["num_blocks"] == 64 * 3072 // 16 and eng["max_batch"] == 64


def test_check_reads_nothing_sound_and_every_damage_moves_a_number():
    cell = run.load_cell(CELL, True)
    out = control_blockdiff.readings(
        cell, 3, 4.0, 8, 3, list(control_blockdiff.DAMAGES),
        ["int8", "fp8"])
    limits = cell["workload"]["check"]["limits"]
    # (a float32 program still parts from the reference at a near tie of
    # two experts' probabilities: 1e-4 here)
    assert all(out["sound"][k] <= limits[k] for k in limits
               if k in out["sound"])
    moved = {"causal_in_block": "token_logit_gap",
             "commit_skipped": "routing_score_gap",
             "left_to_right": "unmask_choice_gap",
             "router_unnormalised": "token_logit_gap",
             "int8": "unmask_choice_gap", "fp8": "token_logit_gap"}
    for name, number in moved.items():
        assert out["control_" + name][number] \
            > 10 * max(out["sound"][number], 1e-4), (name, out)
