"""The Falcon-H1 configuration and its cell: the harness finds the
cell's files by the manifest's names, the configuration file against
the published keys, the kernel's byte count and the model's operation
count against hand-worked numbers, the traffic and engine parameters,
the driver's leaf-at-a-time placement against ``weights.make_weights``,
the three new readers on a hand-built trace, and the cell's rehearsal at
the tiny size, which must print ``correct: true``."""

import json
import os
import subprocess
import sys

import pytest

import program_split as S
import run
import trafficgen
from roofline import falcon_h1, roofline_seconds
from test_program_split import KERNEL, Plane, ctx_of

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "falconh1-serve-gen1k-backlog"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs",
                           "falcon-h1-34b-instruct.json")) as f:
        return json.load(f)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")


def test_the_harness_finds_the_cells_files():
    cell = run.load_cell(CELL, False)
    assert cell["chips"] == 1
    assert cell["config"]["name"] == "falcon-h1-34b-instruct"
    assert cell["workload"]["driver"] == "serve_staged_dense"
    names = {m["name"] for m in run.metrics_of(cell, "per_layer")}
    assert {"ssm_step_roofline_pct.serve", "ssm_device_pct.serve",
            "prefill_ssm_device_pct.serve", "attn_device_pct.serve",
            "prefill_attn_device_pct.serve",
            "paged_decode_gqa_roofline_pct.serve", "kv_pool_live_pct.serve",
            "device_idle_pct.serve", "decode_device_ms.serve"} <= names
    assert not any(n.startswith(("moe_", "prefill_moe_")) for n in names)
    for name in names:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
    assert {m["name"] for m in run.metrics_of(cell, "end_to_end")} == \
        {"serve_tokens_per_s", "setup_s"}


def test_every_published_key_is_unchanged_but_the_depth(cfg):
    row = catalog_row()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert cfg[key] == value, key
    assert (row["config"]["num_hidden_layers"],
            cfg["num_hidden_layers"]) == (72, 4)
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert "72" in cfg["reduced_why"]["num_hidden_layers"]
    assert "68 layers" in cfg["deployment"]
    assert {"init_scales", "dt_bias_mean", "rotary_lane_pairing",
            "mamba_d_ssm"} <= set(cfg["assumed"])
    assert "float32 recurrent state" in cfg["precision"]
    # what the accepted reader paged_decode_gqa_roofline_pct.serve takes
    assert cfg["layer_types"] == ["full_attention"] * 4
    assert (cfg["num_key_value_heads"], cfg["head_dim"]) == (4, 128)


def test_parameter_count_of_the_cut(cfg):
    from common import load_module
    specs = load_module("reference", cfg["reference"]).leaf_specs(cfg)
    total = 0
    for shape, _, _ in specs.values():
        n = 1
        for d in shape:
            n *= d
        total += n
    attn = 5120 * 2560 + 2 * 5120 * 512 + 2560 * 5120
    mixer = 5120 * 9248 + 5120 * 4 + 5120 + 3 * 32 + 4096 + 4096 * 5120
    layer = attn + mixer + 3 * 5120 * 21504 + 2 * 5120
    assert (attn, mixer, layer) == (31_457_280, 68_351_072, 430_120_032)
    assert total == cfg["parameters"] == 4 * layer + 2 * 261120 * 5120 + 5120
    assert total == 4_394_354_048


def test_state_step_counts_a_state_read_and_written_once():
    # one step of 128 real rows, 4 layers
    flops, nbytes = falcon_h1.ssm_state_step(128, 4, 32, 128, 2, 256)
    state = 128 * 4 * 2 * 32 * 128 * 256 * 4            # 4.29 GB
    operands = 128 * 4 * (2 * 32 * 128 + 2 * 2 * 256 + 32) * 4
    assert state == 4_294_967_296 and operands == 18_939_904
    assert nbytes == state + operands
    assert flops == 2 * state
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = roofline_seconds(flops, nbytes, peaks)
    assert bound == "memory"
    assert seconds == pytest.approx(5.267e-3, rel=1e-3)


def test_ops_per_token_counts_both_mixers(cfg):
    attn = 2 * (2 * 5120 * 2560 + 2 * 5120 * 512) + 2 * 1024 * 2560
    mixer = 2 * 5120 * 9248 + 2 * 4 * 5120 + 6 * 4096 * 256 + 2 * 4096 * 5120
    want = 3 * (4 * (attn + mixer + 6 * 5120 * 21504) + 2 * 5120 * 261120)
    assert falcon_h1.ops_per_token(cfg, 1024) == want
    # the recurrence is under 1 % of a layer's operations
    assert 6 * 4096 * 256 / (attn + mixer + 6 * 5120 * 21504) < 0.01


def test_traffic_and_engine_are_the_issues():
    traffic = trafficgen.load_traffic("gen1k-backlog")
    pop = trafficgen.population(traffic, 45.0)
    assert set(pop["prompt_len"]) == {256, 512, 1024}
    assert pop["output_len"].min() >= 256 and pop["output_len"].max() <= 1536
    assert (pop["prompt_len"] + pop["output_len"]).max() <= 2560
    assert pop["gaps"].max() == 0.0                  # a backlog
    assert traffic["prefix_sharing"]["groups"] == 0
    assert traffic["sampling"] == "greedy"
    law = traffic["prompt_len"]
    assert (law["law"], law["median"], law["sigma"], law["min"],
            law["max"]) == ("lognormal", 384, 0.6, 128, 1024)
    law = traffic["output_len"]
    assert (law["law"], law["median"], law["sigma"], law["min"],
            law["max"]) == ("lognormal", 768, 0.5, 256, 1536)
    wl = run.load_cell(CELL, False)["workload"]
    eng = wl["engine"]
    assert eng["num_blocks"] == 128 * 2560 // 16 == 20480
    assert (eng["block_size"], eng["max_batch"], eng["max_model_len"],
            eng["prefill_budget_tokens"], eng["kv_dtype"]) == \
        (16, 128, 2560, 1024, "bfloat16")
    assert eng["batch_buckets"] == [128] and eng["page_buckets"] == [160]
    assert wl["warmup"]["prompt_lengths"] == [256, 512, 1024]
    assert (wl["trace"]["from_s"], wl["trace"]["for_s"]) == (14.0, 6.0)
    assert wl["check"]["limits"]["window_compiles"] == 0


# -- the three new readers on a hand-built trace --------------------------
# device: D [100, 300): ssm_state_step.1 80 under ssm/step, fusion.s 20
# under ssm/norm, paged_decode.1 60 under attn, fusion.m 40 under mlp;
# P [400, 800): fusion.p 100 under ssm/in_proj, fusion.c 60 under
# ssm/scan/intra, flash_fwd.1 140 under attn, fusion.f 100 under mlp.
# busy 600; ssm 100 + 160 = 260; the prefill's ssm 160 of 400.
DEC, PRE = "jit(p2t_decode)/", "jit(p2t_prefill)/"
OPS = [("%ssm_state_step.1 = f32[8]{0}" + KERNEL, 100, 80,
        DEC + "ssm/step/jit(_state_step)/ssm_state_step/pallas_call"),
       ("%fusion.s = bf16[8]{0} fusion(%p), kind=kLoop", 180, 20,
        DEC + "ssm/norm/mul"),
       ("%paged_decode.1 = bf16[8]{0}" + KERNEL, 200, 60,
        DEC + "attn/jit(paged)/paged_decode/pallas_call"),
       ("%fusion.m = bf16[8]{0} fusion(%p), kind=kLoop", 260, 40,
        DEC + "mlp/dot_general"),
       ("%fusion.p = bf16[8]{0} fusion(%p), kind=kOutput", 400, 100,
        PRE + "ssm/in_proj/dot_general"),
       ("%fusion.c = f32[8]{0} fusion(%p), kind=kOutput", 500, 60,
        PRE + "ssm/scan/intra/dot_general"),
       ("%flash_fwd.1 = bf16[8]{0}" + KERNEL, 560, 140,
        PRE + "attn/jit(flash_bshd)/flash_fwd/pallas_call"),
       ("%fusion.f = bf16[8]{0} fusion(%p), kind=kLoop", 700, 100,
        PRE + "mlp/dot_general")]
MODULES = [("jit_p2t_decode(7)", 100, 200, ""),
           ("jit_p2t_prefill(5)", 400, 400, "")]
SLOT_BYTES = 4 * (32 * 128 * 256 * 4 + 3 * 5120 * 2)


def host(with_state=True):
    step = {"rows": 100, "row_bucket": 128, "page_bucket": 160,
            "ctx_tokens": 90000, "program": S.DECODE, "launch": 40}
    if with_state:
        step.update(state_bytes=2 * 100 * SLOT_BYTES, state_reprefills=0)
    return [("bench:traced_window", 0, 1000, {}),
            ("p2t:decode.dispatch", 10, 20, step),
            ("p2t:prefill", 300, 60, {"req": 0, "tokens": 500, "padded": 512,
                                      "ahead": 1, "scan_chunks": 4}),
            ("p2t:prefill.dispatch", 302, 18,
             {"program": S.PREFILL, "launch": 5, "launches": 1})]


def traced(monkeypatch, ops=OPS, with_state=True):
    from jax.profiler import ProfileData
    dev = Plane(1, "/device:TPU:0")
    dev.line(1, "XLA Ops", ops)
    dev.line(2, "XLA Modules", MODULES)
    plane = Plane(2, "/host:CPU")
    plane.line(1, "python", host(with_state))
    raw = ProfileData.text_proto_to_serialized_xspace(
        dev.text() + plane.text())
    ctx = ctx_of(monkeypatch, raw, cell=CELL)
    cell = run.load_cell(CELL, False)
    ctx["cell"].update(workload=cell["workload"], config=cell["config"],
                       peaks={"bf16_flops_per_s": 197e12,
                              "hbm_bytes_per_s": 819e9})
    return ctx


def test_new_readers_by_hand_arithmetic(monkeypatch):
    ctx = traced(monkeypatch)
    assert run.read_layer_metric("ssm_device_pct.serve", ctx) \
        == pytest.approx(100 * 260 / 600)
    assert run.read_layer_metric("prefill_ssm_device_pct.serve", ctx) \
        == pytest.approx(100 * 160 / 400)
    # 100 real rows of one step over the kernel's 80 ns
    need_s = roofline_seconds(*falcon_h1.ssm_state_step(
        100, 4, 32, 128, 2, 256), ctx["cell"]["peaks"])[0]
    assert run.read_layer_metric("ssm_step_roofline_pct.serve", ctx) \
        == pytest.approx(100 * need_s / 80e-9)


def test_new_readers_say_nothing_of_a_program_without_the_pieces(
        monkeypatch):
    """The parent has no ``ssm`` scope, no kernel and no ``state_bytes``:
    every new reader returns None and none raises."""
    plain = [(n, a, d, p.replace("ssm/", "attn/")) for n, a, d, p in OPS
             if "ssm_state_step" not in n]
    ctx = traced(monkeypatch, ops=plain, with_state=False)
    for name in ("ssm_device_pct.serve", "prefill_ssm_device_pct.serve",
                 "ssm_step_roofline_pct.serve"):
        assert run.read_layer_metric(name, ctx) is None, name


def test_the_cells_rehearsal_is_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3900000007", "--seconds", "5", "--trace", "0",
         "--rehearse"],
        capture_output=True, text=True, timeout=1500,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PADDLE2_TPU_CACHE_DIR=""))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
