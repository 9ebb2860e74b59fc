"""The K-EXAONE configuration and its cell: the harness finds the cell's
files by the manifest's names, the configuration file against the
published keys, the ring walk's byte count, the band's operation count
(linear in the prompt) and the model's operations against hand-worked
numbers, the traffic and engine parameters, the stage-by-kind reference
against the whole forward (the two attentions told apart by their
leaves), the three new readers on a hand-built trace, and the cell's
rehearsal at the tiny size, which must print ``correct: true``."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import program_split as S
import run
import trafficgen
from roofline import exaone_moe, roofline_seconds
from test_program_split import KERNEL, Plane, ctx_of

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "kexaone-serve-mixed8k-backlog"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["window_decode_roofline_pct.serve", "window_device_pct.serve",
       "prefill_window_device_pct.serve"]
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "sliding_windows", "num_experts", "vocab_size",
           "num_nextn_predict_layers"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs",
                           "k-exaone-236b-a23b.json")) as f:
        return json.load(f)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "K-EXAONE-236B-A23B")


def test_the_harness_finds_the_cells_files():
    cell = run.load_cell(CELL, False)
    assert cell["chips"] == 1
    assert cell["config"]["name"] == "k-exaone-236b-a23b"
    assert cell["workload"]["driver"] == "serve_routed_kinds"
    names = {m["name"] for m in run.metrics_of(cell, "per_layer")}
    assert set(NEW) | {
        "moe_gmm_roofline_pct.serve", "moe_gmm_tile_fill_pct.serve",
        "moe_device_pct.serve", "prefill_moe_device_pct.serve",
        "moe_load_max_over_mean.serve", "moe_shared_device_pct.serve",
        "moe_rows_here_pct.serve", "attn_device_pct.serve",
        "prefill_attn_device_pct.serve",
        "paged_decode_gqa_roofline_pct.serve", "unscoped_device_pct.serve",
        "paged_pages_coalesced_pct.serve",    # 0: the split body runs none
        "kv_pool_live_pct.serve", "device_idle_pct.serve",
        "decode_device_ms.serve"} <= names
    # the readers of other bodies and other mixers stay off this cell
    assert not {"paged_decode_roofline_pct.serve",
                "moe_gmm2_roofline_pct.serve", "ssm_device_pct.serve",
                "conv_device_pct.serve"} & names
    for name in names:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
    assert {m["name"] for m in run.metrics_of(cell, "end_to_end")} == \
        {"serve_tokens_per_s", "setup_s"}
    # the new readers list this cell alone
    for m in cell["manifest"]["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] \
                and m["moves"] == "serve_tokens_per_s"


def test_every_published_key_is_unchanged_but_the_reduced(cfg):
    row = catalog_row()
    pub = row["config"]
    assert cfg["source"] == row["source_url"]
    for key, value in pub.items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert cfg["reduced"] == REDUCED
    assert set(cfg["reduced_why"]) == set(REDUCED)
    # every published width
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["moe_intermediate_size"],
            cfg["intermediate_size"], cfg["router_experts"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == \
        (6144, 64, 8, 128, 128, 2048, 18432, 128, 8, 2.5)
    # the cut: the first five layers, an eighth of the experts and of
    # the vocabulary, the drafter off
    assert cfg["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert cfg[key] == pub[key][:5], key
    assert cfg["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention", "sliding_attention"]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert (cfg["num_experts"], cfg["held_experts"]) == (16, [0, 16])
    assert cfg["router_experts"] == pub["num_experts"] == 128
    assert cfg["vocab_size"] * 8 == pub["vocab_size"] \
        == cfg["published_vocab_size"]
    assert (cfg["num_nextn_predict_layers"],
            cfg["published_num_nextn_predict_layers"]) == (0, 1)
    assert cfg["published_num_hidden_layers"] == 48
    assert "8 chips share each layer" in cfg["deployment"]
    assert {"norm_placement", "qk_norm", "rotary_on_sliding_layers_only",
            "selection_bias", "init_scales"} <= set(cfg["assumed"])
    assert "ring" in cfg["precision"]
    # what the accepted readers take
    assert cfg["layer_types"].count("full_attention") == 1
    assert len(cfg["layer_types"]) - cfg["num_dense_layers"] == 4


def test_parameter_count_of_the_cut(cfg):
    from common import load_module
    specs = load_module("reference", cfg["reference"]).leaf_specs(cfg)
    total = sum(int(np.prod(shape)) for shape, _, _ in specs.values())
    attn = 2 * 6144 * 8192 + 2 * 6144 * 1024 + 2 * 128 + 2 * 6144
    dense = 3 * 6144 * 18432
    expert = 3 * 6144 * 2048
    moe = 16 * expert + expert + 6144 * 128 + 128
    assert (attn, dense, expert) == (113_258_752, 339_738_624, 37_748_736)
    assert attn + moe == 755_773_824
    assert total == cfg["parameters"] \
        == (attn + dense) + 4 * (attn + moe) + 2 * 19200 * 6144 + 6144
    assert total == 3_712_028_416                 # 7.42 GB in bfloat16


def test_ring_walk_bytes_and_band_operations():
    # a decode step of 128 rows past the window: 128 x 128 ring rows in
    # each of 4 sliding layers, 4,096 B a row (K and V, 8 x 128 lanes)
    flops, nbytes = exaone_moe.window_decode(128 * 128, 4, 8, 128)
    assert nbytes == 128 * 128 * 4 * 4096 == 268_435_456
    seconds, bound = roofline_seconds(flops, nbytes, PEAKS)
    assert bound == "memory" and seconds == pytest.approx(0.3278e-3,
                                                          rel=1e-3)
    # the band: min(i + 1, window) keys a query -- LINEAR past the window
    assert exaone_moe.band_keys(5, 128) == 15
    assert exaone_moe.band_keys(128, 128) == 128 * 129 // 2
    k2, k8 = (exaone_moe.band_keys(n, 128) for n in (2048, 8192))
    assert k8 - k2 == (8192 - 2048) * 128
    assert k8 / k2 == pytest.approx(4.0, rel=0.03)     # a square: 16


def test_ops_per_token_follows_the_two_attentions(cfg):
    proj = 2 * (2 * 6144 * 8192 + 2 * 6144 * 1024)
    sliding = proj + 4 * 128 * 8192             # 128 keys whatever the seq
    full = proj + 4 * 2048 * 8192               # causal: half of 4,096
    dense = 6 * 6144 * 18432
    moe = 6 * 6144 * 2048 + 2 * 6144 * 128 + 6 * 6144 * 2048 * 8 * 16 / 128
    want = 3 * (4 * sliding + full + dense + 4 * moe + 2 * 6144 * 19200)
    assert exaone_moe.ops_per_token(cfg, 4096) == want


def test_traffic_and_engine_are_the_issues():
    traffic = trafficgen.load_traffic("mixed8k-backlog")
    pop = trafficgen.population(traffic, 45.0)
    lens = np.asarray(pop["prompt_len"])
    assert set(lens) == {512, 2048, 8192}
    shares = [float(np.mean(lens == n)) for n in (512, 2048, 8192)]
    assert shares == pytest.approx([0.5, 0.3, 0.2], abs=0.01)
    assert pop["output_len"].min() >= 128 and pop["output_len"].max() <= 1024
    assert (pop["prompt_len"] + pop["output_len"]).max() <= 9216
    assert pop["gaps"].max() == 0.0                  # a backlog
    assert traffic["arrival"]["process"] == "at-once"
    assert traffic["prefix_sharing"]["groups"] == 0
    assert traffic["sampling"] == "greedy"
    law = traffic["prompt_len"]
    assert (law["law"], law["values"], law["weights"]) == \
        ("choice", [512, 2048, 8192], [0.5, 0.3, 0.2])
    law = traffic["output_len"]
    assert (law["law"], law["median"], law["sigma"], law["min"],
            law["max"]) == ("lognormal", 512, 0.5, 128, 1024)
    assert "3.0 x" in traffic["arrival"]["_rate_why"]
    wl = run.load_cell(CELL, False)["workload"]
    eng = wl["engine"]
    assert eng["num_blocks"] == eng["max_batch"] * 9216 // 16
    assert (eng["block_size"], eng["max_model_len"],
            eng["prefill_budget_tokens"], eng["kv_dtype"]) == \
        (16, 9216, 8192, "bfloat16")
    assert eng["batch_buckets"] == [eng["max_batch"]]
    assert eng["page_buckets"] == [576]
    assert wl["warmup"]["prompt_lengths"] == [512, 2048, 8192]
    assert set(wl["kernels"]) == {"moe_gmm", "paged_decode",
                                  "window_decode", "flash_fwd"}
    assert wl["check"]["limits"]["window_compiles"] == 0


def test_the_traffic_gives_the_same_work_at_every_seed():
    """The lengths are the laws at evenly spaced quantiles: seeds shuffle
    which request gets which, never how much work there is."""
    traffic = trafficgen.load_traffic("mixed8k-backlog")
    work = []
    for seed in (1, 7, 4500000101):
        reqs = trafficgen.requests(traffic, seed, 45.0, 19200)
        work.append((len(reqs), sum(len(r["prompt"]) for r in reqs),
                     sum(r["max_new"] for r in reqs)))
        assert max(max(r["prompt"]) for r in reqs) < 19200
    assert work[0] == work[1] == work[2]


def test_stages_by_kind_are_the_whole_forward():
    """``serve_routed_kinds.kinds_forward`` (one compiled program a layer
    kind — and, inside the kind ``moe``, one per attention, told apart by
    the leaves' names —, leaves drawn a stage at a time) =
    ``reference.forward`` over ``weights.make_weights``' leaves; the
    experts handed in come back, and a forced choice shows in the
    deficit."""
    import jax
    import jax.numpy as jnp
    from common import load_module
    from drivers import serve_routed_kinds as kinds
    from reference.common import matmul_f32
    from weights import make_weights
    cfg = run.load_cell(CELL, True)["config"]
    ref = load_module("reference", cfg["reference"])
    assert [ref.kind(cfg, i) for i in range(5)] == ["dense"] + ["moe"] * 4
    ids = jnp.asarray(np.random.default_rng(1).integers(1, 503, (1, 24)),
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        params = make_weights(ref.leaf_specs(cfg), 5, jnp.float32)
        want, used, deficit = ref.forward(params, ids, cfg)
        (got, got_used, got_deficit), = kinds.kinds_forward(
            ref, cfg, 5, [ids], matmul_f32)
        assert float(jnp.abs(got - want).max()) <= 5e-5
        assert bool((got_used == used).all())
        assert float(got_deficit.max()) == float(deficit.max()) == 0.0
        assert used.shape == (1, 24, 4, 2)
        # the global layer (3) under the sliding layers' program would
        # see 8 keys and rotate: the staged pass must not
        whole = dict(cfg, sliding_window=256)
        far = ref.forward(params, ids, whole)[0]
        assert float(jnp.abs(far - want).max()) > 1e-2
        # hand in a worse choice for one row of the second expert layer
        forced = np.asarray(used).copy()
        taken = set(forced[0, 3, 1].tolist())
        forced[0, 3, 1, 0] = next(e for e in range(8) if e not in taken)
        (_, back, worse), = kinds.kinds_forward(ref, cfg, 5, [ids],
                                                matmul_f32, [forced])
        assert bool((np.asarray(back)[0, 3, 1] == forced[0, 3, 1]).all())
        assert float(worse[0, 3, 1]) > 0 and float(worse[0, 2].max()) == 0


# -- the three new readers on a hand-built trace -----------------------------
# device: D [100, 300): window_decode.1 40 under attn/window, fusion.w 20
# under attn/window/out, paged_decode_split.1 80 under attn, moe_gmm.1 60
# under moe/experts; P [400, 800): fusion.b 100 under attn/window,
# fusion.q 60 under attn/window (projections), flash_fwd.1 40 under attn,
# moe_gmm.2 200 under moe/experts.
DEC, PRE = "jit(p2t_decode)/", "jit(p2t_prefill)/"
OPS = [("%window_decode.1 = bf16[8]{0}" + KERNEL, 100, 40,
        DEC + "attn/window/jit(_decode_single)/window_decode/pallas_call"),
       ("%fusion.w = bf16[8]{0} fusion(%p), kind=kLoop", 140, 20,
        DEC + "attn/window/out/dot_general"),
       ("%paged_decode_split.1 = f32[8]{0}" + KERNEL, 160, 80,
        DEC + "attn/paged_decode_split/pallas_call"),
       ("%moe_gmm.1 = bf16[8]{0}" + KERNEL, 240, 60,
        DEC + "moe/experts/jit(_gmm)/moe_gmm/pallas_call"),
       ("%fusion.b = f32[8]{0} fusion(%p), kind=kOutput", 400, 100,
        PRE + "attn/window/dot_general"),
       ("%fusion.q = bf16[8]{0} fusion(%p), kind=kOutput", 500, 60,
        PRE + "attn/window/dot_general"),
       ("%flash_fwd.1 = bf16[8]{0}" + KERNEL, 560, 40,
        PRE + "attn/jit(flash_bshd)/flash_fwd/pallas_call"),
       ("%moe_gmm.2 = bf16[8]{0}" + KERNEL, 600, 200,
        PRE + "moe/experts/jit(_gmm)/moe_gmm/pallas_call")]
MODULES = [("jit_p2t_decode(7)", 100, 200, ""),
           ("jit_p2t_prefill(5)", 400, 400, "")]
ROUTING = {"moe_assignments": 3000, "moe_experts_hit": 60,
           "moe_load_max": 90, "moe_rows_routed_here": 330,
           "moe_rows": 500, "moe_tile_rows": 32000}
LAYERS = {"window_layers": 4}


def host(with_counts=True):
    step = {"rows": 125, "row_bucket": 128, "page_bucket": 576,
            "ctx_tokens": 350000, "program": S.DECODE, "launch": 40,
            "state_bytes": 1, "state_reprefills": 0}
    pre = {"req": 0, "tokens": 2000, "padded": 2048, "ahead": 1}
    if with_counts:
        step.update(LAYERS, window_tokens=125 * 128, **ROUTING)
        pre.update(dict(ROUTING, moe_assignments=6000))
    return [("bench:traced_window", 0, 1000, {}),
            ("p2t:decode.dispatch", 10, 20, step),
            ("p2t:prefill", 300, 60, pre),
            ("p2t:prefill.dispatch", 302, 18,
             {"program": S.PREFILL, "launch": 5, "launches": 1})]


def traced(monkeypatch, with_counts=True, cell_name=CELL, ops=OPS):
    from jax.profiler import ProfileData
    dev = Plane(1, "/device:TPU:0")
    dev.line(1, "XLA Ops", ops)
    dev.line(2, "XLA Modules", MODULES)
    plane = Plane(2, "/host:CPU")
    plane.line(1, "python", host(with_counts))
    raw = ProfileData.text_proto_to_serialized_xspace(
        dev.text() + plane.text())
    ctx = ctx_of(monkeypatch, raw, cell=cell_name)
    cell = run.load_cell(cell_name, False)
    ctx["cell"].update(workload=cell["workload"], config=cell["config"],
                       peaks=PEAKS)
    # the benchmark's own count of the keys its decode steps saw
    ctx["spans"] = types.SimpleNamespace(
        counters={"context_tokens": 350000.0})
    return ctx


def test_new_readers_by_hand_arithmetic(monkeypatch):
    ctx = traced(monkeypatch)
    need = roofline_seconds(*exaone_moe.window_decode(125 * 128, 4, 8, 128),
                            PEAKS)[0]
    assert run.read_layer_metric("window_decode_roofline_pct.serve", ctx) \
        == pytest.approx(100 * need / 40e-9)
    assert run.read_layer_metric("window_device_pct.serve", ctx) \
        == pytest.approx(100 * 60 / 200)
    assert run.read_layer_metric("prefill_window_device_pct.serve", ctx) \
        == pytest.approx(100 * 160 / 400)
    # the accepted readers the cell is listed under read the same trace:
    # ``attn`` holds both kinds, the pattern ``paged_decode`` the global
    # layer's kernel alone (80 ns, not the ring walk's 40)
    assert run.read_layer_metric("attn_device_pct.serve", ctx) \
        == pytest.approx(100 * 340 / 600)
    assert run.read_layer_metric("prefill_attn_device_pct.serve", ctx) \
        == pytest.approx(100 * 200 / 400)
    from roofline import paged_decode
    need = roofline_seconds(*paged_decode.paged_decode(350000, 1, 8, 128),
                            PEAKS)[0]
    assert run.read_layer_metric("paged_decode_gqa_roofline_pct.serve",
                                 ctx) == pytest.approx(100 * need / 80e-9)
    assert run.read_layer_metric("moe_rows_here_pct.serve", ctx) \
        == pytest.approx(66.0)
    assert run.read_layer_metric("moe_load_max_over_mean.serve", ctx) \
        == pytest.approx(90 * 4 * 16 / 3000)


def test_new_readers_say_nothing_of_a_program_without_the_counts(
        monkeypatch):
    """The parent's spans carry no ``window_layers`` and its ops no
    ``window`` scope (another cell's trace stands in for it): all three
    new readers return None, none raises."""
    other = [(text, a, d, path.replace("attn/window", "attn"))
             for text, a, d, path in OPS if "window_decode" not in text]
    ctx = traced(monkeypatch, with_counts=False, ops=other)
    for name in NEW:
        assert run.read_layer_metric(name, ctx) is None, name
    # and on a cell without the scope or the keys
    ctx = traced(monkeypatch, with_counts=False,
                 cell_name="lfm2moe-serve-doc3k-backlog", ops=other)
    for name in NEW:
        assert run.read_layer_metric(name, ctx) is None, name


def test_the_cells_rehearsal_is_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4500000007", "--seconds", "5", "--trace", "0",
         "--rehearse"],
        capture_output=True, text=True, timeout=1500,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PADDLE2_TPU_CACHE_DIR=""))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
