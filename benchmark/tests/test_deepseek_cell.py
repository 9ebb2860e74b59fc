"""The DeepSeek-V2 configuration and its cell: the harness finds the
cell's files by the manifest's names, the configuration file against
the published keys, the byte and operation counts against hand-worked
numbers, the traffic and engine parameters, and the cell's rehearsal at
the tiny size, which must print ``correct: true``."""

import json
import os
import subprocess
import sys

import pytest

import run
import trafficgen
from roofline import deepseek_v2

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "dsv2-serve-doc5k-backlog"
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_group": 8, "n_shared_experts": 2,
    "norm_topk_prob": False, "num_attention_heads": 128,
    "num_experts_per_tok": 6, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 128}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "deepseek-v2.json")) as f:
        return json.load(f)


def test_the_harness_finds_the_cells_files():
    cell = run.load_cell(CELL, False)
    assert cell["chips"] == 1 and cell["config"]["name"] == "deepseek-v2"
    assert cell["workload"]["driver"] == "serve_routed_staged"
    names = {m["name"] for m in run.metrics_of(cell, "per_layer")}
    assert {"paged_mla_roofline_pct.serve",
            "flash_mla_prefill_roofline_pct.serve",
            "moe_shared_device_pct.serve", "moe_rows_here_pct.serve",
            "attn_device_pct.serve", "moe_device_pct.serve",
            "moe_gmm_roofline_pct.serve", "moe_load_max_over_mean.serve",
            "kv_pool_live_pct.serve", "device_idle_pct.serve"} <= names
    for name in names:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
    assert {m["name"] for m in run.metrics_of(cell, "end_to_end")} == \
        {"serve_tokens_per_s", "setup_s"}


def test_every_published_width_is_unchanged(cfg):
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    # the cut, and the published values beside it
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 20, 12800)
    assert (cfg["router_experts"], cfg["held_group"]) == (160, 0)
    assert "8 chips" in cfg["deployment"]
    for published in ("60", "160", "102,400"):
        assert any(published in why for why in cfg["reduced_why"].values())
    assert {"rotary_lane_pairing", "initializer_range", "latent_row"} \
        <= set(cfg["assumed"])
    # what the accepted reader moe_load_max_over_mean.serve takes
    assert len(cfg["layer_types"]) - cfg["num_dense_layers"] == 4
    assert cfg["num_experts"] == cfg["n_routed_experts"]


def test_parameter_count_of_the_cut(cfg):
    from common import load_module
    specs = load_module("reference", cfg["reference"]).leaf_specs(cfg)
    total = 0
    for shape, _, _ in specs.values():
        n = 1
        for d in shape:
            n *= d
        total += n
    assert total == cfg["parameters"] == 3_145_466_880
    attn = 5120 * 1536 + 1536 + 1536 * 24576 + 5120 * 576 + 512 \
        + 512 * 32768 + 16384 * 5120 + 2 * 5120
    assert attn == 149_237_760
    expert_layer = attn + 3 * 5120 * 3072 + 5120 * 160 + 20 * 23_592_960
    assert expert_layer == 669_102_080
    assert total == attn + 188_743_680 + 4 * expert_layer \
        + 2 * 12800 * 5120 + 5120


def test_paged_mla_counts_one_vector_a_token():
    # 128 rows of 3,100 positions, one step, 5 layers
    flops, nbytes = deepseek_v2.paged_mla(128 * 3100, 5, 128, 512, 64)
    assert nbytes == 128 * 3100 * 5 * 1152           # 2.29 GB
    assert flops == 128 * 3100 * 5 * 278_528         # 0.55 TFLOP
    # 242 operations a byte: on a v5e's ridge (197e12 / 819e9 = 240.5)
    assert round(flops / nbytes) == 242
    from roofline import roofline_seconds
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert roofline_seconds(flops, nbytes, peaks)[1] == "compute"


def test_flash_mla_prefill_counts_the_triangle():
    flops, nbytes = deepseek_v2.flash_mla_prefill(3072, 5, 128, 192, 128)
    assert flops == 3072 * 3073 / 2 * 128 * 640 * 5  # 1.93 TFLOP
    assert nbytes == 2 * 3072 * 128 * 320 * 2 * 5
    # the value width moves the PV half only
    wide = deepseek_v2.flash_mla_prefill(3072, 5, 128, 192, 192)[0]
    assert wide / flops == pytest.approx(768 / 640)


def test_ops_per_token_of_the_share(cfg):
    attn = 2 * (5120 * 1536 + 1536 * 24576 + 5120 * 576 + 512 * 32768
                + 16384 * 5120) + 2048 * 128 * 320
    moe = 6 * 5120 * 1536 * 2 + 2 * 5120 * 160 + 6 * 5120 * 1536 * 6 / 8
    want = 3 * (5 * attn + 6 * 5120 * 12288 + 4 * moe + 2 * 5120 * 12800)
    assert deepseek_v2.ops_per_token(cfg, 2048) == want


def test_traffic_and_engine_are_the_issues():
    traffic = trafficgen.load_traffic("doc5k-backlog")
    pop = trafficgen.population(traffic, 45.0)
    assert set(pop["prompt_len"]) == {2048, 3072, 5120}
    assert pop["output_len"].min() >= 128 and pop["output_len"].max() <= 1024
    assert (pop["prompt_len"] + pop["output_len"]).max() <= 6144
    assert pop["gaps"].max() == 0.0                  # a backlog
    assert traffic["prefix_sharing"]["groups"] == 0
    law = traffic["prompt_len"]
    assert (law["median"], law["sigma"], law["min"], law["max"]) == \
        (2560, 0.5, 1024, 5120)
    law = traffic["output_len"]
    assert (law["median"], law["sigma"], law["min"], law["max"]) == \
        (512, 0.5, 128, 1024)
    eng = run.load_cell(CELL, False)["workload"]["engine"]
    assert eng["num_blocks"] == 128 * 6144 // 16 == 49152
    assert (eng["block_size"], eng["max_batch"], eng["max_model_len"],
            eng["prefill_budget_tokens"], eng["kv_dtype"]) == \
        (16, 128, 6144, 5120, "bfloat16")
    assert eng["batch_buckets"] == [128] and eng["page_buckets"] == [384]


def test_the_cells_rehearsal_is_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3400000007", "--seconds", "5", "--trace", "0",
         "--rehearse"],
        capture_output=True, text=True, timeout=1500,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PADDLE2_TPU_CACHE_DIR=""))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
