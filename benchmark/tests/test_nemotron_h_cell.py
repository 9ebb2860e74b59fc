"""The Nemotron-H configuration and its cell: the harness finds the
cell's files by the manifest's names, the configuration file against the
published keys, the two kernels' byte counts and the model's operation
count against hand-worked numbers, the traffic and engine parameters,
the stage-by-kind reference against the whole forward, the two new
readers on a hand-built trace, and the cell's rehearsal at the tiny
size, which must print ``correct: true``."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import program_split as S
import run
import trafficgen
from roofline import nemotron_h, roofline_seconds
from test_program_split import KERNEL, Plane, ctx_of

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "nemotron3n-serve-reason2k-backlog"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows
                if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")


def test_the_harness_finds_the_cells_files():
    cell = run.load_cell(CELL, False)
    assert cell["chips"] == 1
    assert cell["config"]["name"] == "nemotron-3-nano-30b-a3b"
    assert cell["workload"]["driver"] == "serve_routed_kinds"
    names = {m["name"] for m in run.metrics_of(cell, "per_layer")}
    assert {"moe_gmm2_roofline_pct.serve",
            "ssm_mixer_step_roofline_pct.serve", "ssm_device_pct.serve",
            "prefill_ssm_device_pct.serve", "moe_device_pct.serve",
            "prefill_moe_device_pct.serve", "moe_shared_device_pct.serve",
            "moe_load_max_over_mean.serve", "moe_rows_here_pct.serve",
            "moe_gmm_tile_fill_pct.serve", "attn_device_pct.serve",
            "paged_decode_gqa_roofline_pct.serve", "kv_pool_live_pct.serve",
            "device_idle_pct.serve", "decode_device_ms.serve"} <= names
    # the accepted readers that would over-count here, and the one whose
    # scope list does not know ``ssm``
    assert not {"moe_gmm_roofline_pct.serve", "ssm_step_roofline_pct.serve",
                "unscoped_device_pct.serve"} & names
    for name in names:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
    assert {m["name"] for m in run.metrics_of(cell, "end_to_end")} == \
        {"serve_tokens_per_s", "setup_s"}


def test_every_published_key_is_unchanged_but_the_four_reduced(cfg):
    row = catalog_row()
    assert cfg["source"] == row["source_url"]
    reduced = ["num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert cfg[key] == value, key
    assert cfg["reduced"] == reduced
    assert set(cfg["reduced_why"]) == set(reduced)
    pub = row["config"]
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"]) == \
        (9, pub["hybrid_override_pattern"][:9]) == (9, "MEMEM*EME")
    assert (cfg["n_routed_experts"], cfg["router_experts"],
            cfg["held_experts"]) == (64, pub["n_routed_experts"], [0, 64])
    assert (cfg["vocab_size"], cfg["published_vocab_size"]) == \
        (65536, pub["vocab_size"])
    assert cfg["published_hybrid_override_pattern"] \
        == pub["hybrid_override_pattern"]
    assert "2 chips share each layer" in cfg["deployment"]
    assert "43 layers" in cfg["deployment"]
    assert {"no_rotary", "unused_keys", "init_scales", "dt_bias_mean",
            "selection_bias"} <= set(cfg["assumed"])
    assert "float32 recurrent state" in cfg["precision"]
    # what the accepted readers take
    assert cfg["layer_types"].count("full_attention") == 1
    assert len(cfg["layer_types"]) - cfg["num_dense_layers"] == 4
    assert cfg["num_experts"] == 64
    assert "mamba_d_state" not in cfg


def test_parameter_count_of_the_cut(cfg):
    from common import load_module
    specs = load_module("reference", cfg["reference"]).leaf_specs(cfg)
    total = sum(int(np.prod(shape)) for shape, _, _ in specs.values())
    ssm = 2688 * 10304 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 4096 * 2688 + 2688
    attn = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688 + 2688
    expert = 2 * 2688 * 1856
    moe = 64 * expert + 2 * 2688 * 3712 + 2688 * 128 + 128 + 2688
    assert (ssm, attn, expert, moe) == \
        (38_744_896, 23_399_040, 9_977_856, 658_885_376)
    assert total == cfg["parameters"] \
        == 4 * ssm + 4 * moe + attn + 2 * 65536 * 2688 + 2688
    assert total == 3_166_244_352


def test_two_matrix_experts_count_the_published_width():
    # a decode step's layer: 256 rows x 6, half of them held, 64 hit
    flops, nbytes = nemotron_h.moe_gmm2(768, 64, 2688, 1856)
    weights = 64 * 2 * 2688 * 1856 * 2                   # 1.28 GB
    rows = 2 * 768 * (2688 + 1856) * 2
    assert weights == 1_277_165_568
    assert nbytes == weights + rows and flops == 4 * 768 * 2688 * 1856
    seconds, bound = roofline_seconds(flops, nbytes, PEAKS)
    assert bound == "memory"
    assert seconds == pytest.approx(1.5765e-3, rel=1e-3)
    # a 2,048-token prefill's layer is bound by its products' rows? no:
    # 6,144 assignments are 122.6 GFLOP = 0.62 ms against 1.70 ms of bytes
    assert roofline_seconds(*nemotron_h.moe_gmm2(6144, 64, 2688, 1856),
                            PEAKS)[1] == "memory"


def test_state_step_counts_the_mixers_shape(cfg):
    flops, nbytes = nemotron_h.ssm_state_step(256, 4, cfg)
    state = 256 * 4 * 2 * 64 * 64 * 128 * 4              # 4.29 GB
    operands = 256 * 4 * (2 * 64 * 64 + 2 * 8 * 128 + 64) * 4
    assert state == 4_294_967_296
    assert nbytes == state + operands and flops == 2 * state
    assert roofline_seconds(flops, nbytes, PEAKS)[1] == "memory"


def test_ops_per_token_follows_the_pattern(cfg):
    ssm = 2 * 2688 * 10304 + 2 * 4 * 6144 + 6 * 4096 * 128 + 2 * 4096 * 2688
    attn = 2 * (2 * 2688 * 4096 + 2 * 2688 * 256) + 2 * 1024 * 4096
    moe = 4 * 2688 * 3712 + 2 * 2688 * 128 + 4 * 2688 * 1856 * 6 * 64 / 128
    want = 3 * (4 * ssm + 4 * moe + attn + 2 * 2688 * 65536)
    assert nemotron_h.ops_per_token(cfg, 1024) == want


def test_traffic_and_engine_are_the_issues():
    traffic = trafficgen.load_traffic("reason2k-backlog")
    pop = trafficgen.population(traffic, 45.0)
    assert set(pop["prompt_len"]) == {512, 1024, 2048}
    assert pop["output_len"].min() >= 256 and pop["output_len"].max() <= 2048
    assert (pop["prompt_len"] + pop["output_len"]).max() <= 4096
    assert pop["gaps"].max() == 0.0                  # a backlog
    assert traffic["prefix_sharing"]["groups"] == 0
    assert traffic["sampling"] == "greedy"
    law = traffic["prompt_len"]
    assert (law["law"], law["median"], law["sigma"], law["min"],
            law["max"]) == ("lognormal", 768, 0.6, 256, 2048)
    law = traffic["output_len"]
    assert (law["law"], law["median"], law["sigma"], law["min"],
            law["max"]) == ("lognormal", 1024, 0.5, 256, 2048)
    wl = run.load_cell(CELL, False)["workload"]
    eng = wl["engine"]
    assert eng["num_blocks"] == 256 * 4096 // 16 == 65536
    assert (eng["block_size"], eng["max_batch"], eng["max_model_len"],
            eng["prefill_budget_tokens"], eng["kv_dtype"]) == \
        (16, 256, 4096, 2048, "bfloat16")
    assert eng["batch_buckets"] == [256] and eng["page_buckets"] == [256]
    assert wl["warmup"]["prompt_lengths"] == [512, 1024, 2048]
    assert set(wl["kernels"]) == {"moe_gmm", "ssm_state_step",
                                  "paged_decode", "flash_fwd"}
    assert wl["check"]["limits"]["window_compiles"] == 0


def test_stages_by_kind_are_the_whole_forward():
    """``serve_routed_kinds.kinds_forward`` (one compiled program a
    layer kind, leaves drawn a stage at a time) = ``reference.forward``
    over ``weights.make_weights``' leaves; the experts handed in come
    back, and a forced choice shows in the deficit."""
    import jax
    import jax.numpy as jnp
    from common import load_module
    from drivers import serve_routed_kinds as kinds
    from reference.common import matmul_f32
    from weights import make_weights
    cfg = run.load_cell(CELL, True)["config"]
    ref = load_module("reference", cfg["reference"])
    ids = jnp.asarray(np.random.default_rng(1).integers(1, 503, (1, 24)),
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        params = make_weights(ref.leaf_specs(cfg), 5, jnp.float32)
        want, used, deficit = ref.forward(params, ids, cfg)
        (got, got_used, got_deficit), = kinds.kinds_forward(
            ref, cfg, 5, [ids], matmul_f32)
        # jitted stage by stage against one eager pass: logits of scale 5
        assert float(jnp.abs(got - want).max()) <= 5e-5
        assert bool((got_used == used).all())
        assert float(got_deficit.max()) == float(deficit.max()) == 0.0
        assert used.shape == (1, 24, 4, 2)
        # hand in a worse choice for one row of the second expert layer
        forced = np.asarray(used).copy()
        taken = set(forced[0, 3, 1].tolist())
        forced[0, 3, 1, 0] = next(e for e in range(8) if e not in taken)
        (_, back, worse), = kinds.kinds_forward(ref, cfg, 5, [ids],
                                                matmul_f32, [forced])
        assert bool((np.asarray(back)[0, 3, 1] == forced[0, 3, 1]).all())
        assert float(worse[0, 3, 1]) > 0 and float(worse[0, 2].max()) == 0


# -- the two new readers on a hand-built trace ----------------------------
# device: D [100, 300): ssm_state_step.1 80 under ssm/step, moe_gmm.1 60
# under moe/experts, fusion.m 40 under moe/shared, paged_decode.1 20 under
# attn; P [400, 800): moe_gmm.2 200 under moe/experts, fusion.c 100 under
# ssm/scan, flash_fwd.1 100 under attn.
DEC, PRE = "jit(p2t_decode)/", "jit(p2t_prefill)/"
OPS = [("%ssm_state_step.1 = f32[8]{0}" + KERNEL, 100, 80,
        DEC + "ssm/step/jit(_state_step)/ssm_state_step/pallas_call"),
       ("%moe_gmm.1 = bf16[8]{0}" + KERNEL, 180, 60,
        DEC + "moe/experts/jit(_gmm)/moe_gmm/pallas_call"),
       ("%fusion.m = bf16[8]{0} fusion(%p), kind=kLoop", 240, 40,
        DEC + "moe/shared/dot_general"),
       ("%paged_decode.1 = bf16[8]{0}" + KERNEL, 280, 20,
        DEC + "attn/jit(paged)/paged_decode/pallas_call"),
       ("%moe_gmm.2 = bf16[8]{0}" + KERNEL, 400, 200,
        PRE + "moe/experts/jit(_gmm)/moe_gmm/pallas_call"),
       ("%fusion.c = f32[8]{0} fusion(%p), kind=kOutput", 600, 100,
        PRE + "ssm/scan/intra/dot_general"),
       ("%flash_fwd.1 = bf16[8]{0}" + KERNEL, 700, 100,
        PRE + "attn/jit(flash_bshd)/flash_fwd/pallas_call")]
MODULES = [("jit_p2t_decode(7)", 100, 200, ""),
           ("jit_p2t_prefill(5)", 400, 400, "")]
ROUTING = {"moe_assignments": 3000, "moe_experts_hit": 250,
           "moe_load_max": 30, "moe_rows_routed_here": 990,
           "moe_rows": 1000, "moe_tile_rows": 32000}
LAYERS = {"ssm_layers": 4, "attn_layers": 1, "moe_layers": 4}


def host(with_counts=True):
    step = {"rows": 250, "row_bucket": 256, "page_bucket": 256,
            "ctx_tokens": 400000, "program": S.DECODE, "launch": 40,
            "state_bytes": 1, "state_reprefills": 0}
    pre = {"req": 0, "tokens": 500, "padded": 512, "ahead": 1,
           "scan_chunks": 4}
    if with_counts:
        step.update(LAYERS, **ROUTING)
        pre.update(LAYERS, **dict(ROUTING, moe_assignments=6000))
    return [("bench:traced_window", 0, 1000, {}),
            ("p2t:decode.dispatch", 10, 20, step),
            ("p2t:prefill", 300, 60, pre),
            ("p2t:prefill.dispatch", 302, 18,
             {"program": S.PREFILL, "launch": 5, "launches": 1})]


def traced(monkeypatch, with_counts=True):
    from jax.profiler import ProfileData
    dev = Plane(1, "/device:TPU:0")
    dev.line(1, "XLA Ops", OPS)
    dev.line(2, "XLA Modules", MODULES)
    plane = Plane(2, "/host:CPU")
    plane.line(1, "python", host(with_counts))
    raw = ProfileData.text_proto_to_serialized_xspace(
        dev.text() + plane.text())
    ctx = ctx_of(monkeypatch, raw, cell=CELL)
    cell = run.load_cell(CELL, False)
    ctx["cell"].update(workload=cell["workload"], config=cell["config"],
                       peaks=PEAKS)
    return ctx


def test_new_readers_by_hand_arithmetic(monkeypatch):
    ctx = traced(monkeypatch)
    cfg = ctx["cell"]["config"]
    need = sum(roofline_seconds(*nemotron_h.moe_gmm2(a, 250, 2688, 1856),
                                PEAKS)[0] for a in (3000, 6000))
    assert run.read_layer_metric("moe_gmm2_roofline_pct.serve", ctx) \
        == pytest.approx(100 * need / 260e-9)
    need = roofline_seconds(*nemotron_h.ssm_state_step(250, 4, cfg),
                            PEAKS)[0]
    assert run.read_layer_metric("ssm_mixer_step_roofline_pct.serve", ctx) \
        == pytest.approx(100 * need / 80e-9)
    # the accepted readers the cell is listed under read the same trace
    assert run.read_layer_metric("moe_rows_here_pct.serve", ctx) \
        == pytest.approx(99.0)
    assert run.read_layer_metric("moe_shared_device_pct.serve", ctx) \
        == pytest.approx(100 * 40 / 600)
    assert run.read_layer_metric("ssm_device_pct.serve", ctx) \
        == pytest.approx(100 * 180 / 600)
    assert run.read_layer_metric("moe_load_max_over_mean.serve", ctx) \
        == pytest.approx(30 * 4 * 64 / 3000)
    assert run.read_layer_metric("ssm_step_roofline_pct.serve", ctx) is None


def test_new_readers_say_nothing_of_a_program_without_the_counts(
        monkeypatch):
    """The parent's spans carry neither ``ssm_layers`` nor the routing
    counts of this family: both new readers return None, neither
    raises."""
    ctx = traced(monkeypatch, with_counts=False)
    for name in ("moe_gmm2_roofline_pct.serve",
                 "ssm_mixer_step_roofline_pct.serve"):
        assert run.read_layer_metric(name, ctx) is None, name


def test_the_cells_rehearsal_is_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4100000007", "--seconds", "5", "--trace", "0",
         "--rehearse"],
        capture_output=True, text=True, timeout=1500,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PADDLE2_TPU_CACHE_DIR=""))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
