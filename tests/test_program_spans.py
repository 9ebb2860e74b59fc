"""The program's host spans (``profiler.span``), its build log and its
names on the device: one tiny train step and one tiny engine tick under
a ``jax.profiler`` session on the CPU backend write every span of the
contract (PERF.md section 3 lists them), nested and counted; with no
session nothing is written; scopes and names change no arithmetic."""

import contextlib
import os

import jax
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu import profiler
from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle2_tpu.serving import block_cache
from paddle2_tpu.serving.engine import EngineConfig, ServingEngine
from served import (PARENT, PROMPTS, read_spans, scope_in, tiny_gpt_engine,
                    tiny_trainer, trace_session)

TRAIN_SPANS = ["train.step", "train.prepare", "train.dispatch",
               "train.rebind"]
SERVE_SPANS = ["submit", "admit", "admit.schedule", "prefill",
               "prefill.dispatch", "prefill.readback", "prefill.scatter",
               "decode", "decode.select", "decode.build_batch",
               "decode.dispatch", "decode.readback", "decode.emit"]
BUILD_SPANS = ["build", "build.cost"]


def run_both():
    """Two train steps, then two requests served to the end. Returns
    (losses, served tokens, the first tick's info dict)."""
    step, ids = tiny_trainer()
    losses = [float(step(ids, ids)) for _ in range(2)]
    engine = tiny_gpt_engine()
    rids = [engine.submit(PROMPTS[0], 4, trace_id=77),
            engine.submit(PROMPTS[1], 3)]
    first = engine.tick(0.0)
    now = 0.0
    while not engine.idle():
        now += 1.0
        engine.tick(now)
    tokens = [list(engine.sequence(r).generated) for r in rids]
    return losses, tokens, first


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    trace_dir = str(tmp_path_factory.mktemp("p2t_trace"))
    profiler._builds.clear()
    # the scatter programs are cached per process: a test file that ran
    # first in this worker with the same engine would leave nothing to
    # build here, and the build log's test below counts builds
    block_cache._PREFILL_SCATTER_CACHE.clear()
    with trace_session(trace_dir):
        losses, tokens, first = run_both()
    return {"spans": read_spans(trace_dir), "losses": losses,
            "tokens": tokens, "first_tick": first,
            "builds": profiler.builds()}


@pytest.mark.parametrize("name", TRAIN_SPANS + SERVE_SPANS + BUILD_SPANS)
def test_span_of_the_contract_is_written(traced, name):
    assert any(s[0] == name for s in traced["spans"])


@pytest.mark.parametrize("child", sorted(PARENT))
def test_child_lies_inside_its_parent(traced, child):
    names = PARENT[child] if isinstance(PARENT[child], tuple) \
        else (PARENT[child],)
    parents = [s for s in traced["spans"] if s[0] in names]
    kids = [s for s in traced["spans"] if s[0] == child]
    assert kids
    for _, a, b, _ in kids:
        assert any(pa <= a and b <= pb for _, pa, pb, _ in parents)


def test_build_spans_lie_under_the_call_that_built(traced):
    holders = {"train_step": "train.dispatch", "prefill": "prefill.dispatch",
               "decode": "decode.dispatch",
               "kv_scatter_prefill": "prefill.scatter"}
    for name, a, b, counts in traced["spans"]:
        if name == "build":
            outer = [s for s in traced["spans"]
                     if s[0] == holders[counts["program"]]]
            assert any(pa <= a and b <= pb for _, pa, pb, _ in outer)


def test_request_spans_share_req(traced):
    submits = [s[3] for s in traced["spans"] if s[0] == "submit"]
    prefills = [s[3] for s in traced["spans"] if s[0] == "prefill"]
    assert [c["req"] for c in submits] == [0, 1]
    # one span at the admission, one around the first token's read-back
    assert sorted(c["req"] for c in prefills) == [0, 0, 1, 1]
    by_req = {c["req"]: c for c in prefills if "tokens" in c}
    assert by_req[0]["tokens"] == 5 and by_req[0]["padded"] == 16
    # only counts that something reads (PERF.md section 3 names the
    # reader of each): the caller's trace id stays on the request plane
    assert set(submits[0]) == {"req"}
    assert set(by_req[0]) == {"req", "tokens", "padded", "ahead"}
    assert [set(c) for c in prefills if "tokens" not in c] == [{"req"}] * 2


def test_counts_equal_the_engines_own_info(traced):
    info = traced["first_tick"]
    dispatch = [s[3] for s in traced["spans"] if s[0] == "decode.dispatch"]
    first = dispatch[0]
    assert first["rows"] == info["n_active"] == 2
    assert (first["row_bucket"], first["page_bucket"]) == info["bucket"]
    assert first["evicted"] == info["evictions"]
    # cached positions of the two rows: their prompts
    assert first["ctx_tokens"] == sum(len(p) for p in PROMPTS)
    assert first["blocks_total"] == 32
    assert 0 < first["blocks_in_use"] <= 32
    assert set(first) == {"rows", "row_bucket", "page_bucket", "ctx_tokens",
                          "live_pages", "kernel_pages_per_block",
                          "coalesced_pages",
                          "blocks_in_use", "blocks_total", "evicted",
                          "ahead", "dropped_ahead", "program", "launch"}
    steps = [s[3]["built"] for s in traced["spans"] if s[0] == "train.step"]
    assert steps == [1, 0]


def test_dispatch_counts_what_the_paged_kernel_moves(traced):
    """``live_pages``: pages that hold a key of some row. The two rows
    sit at positions 5 and 3 (their prompts are cached), so they attend
    over 6 and 4 keys = 2 + 1 pages of 4 slots, out of the rows x
    page_bucket table the kernel is handed. ``kernel_pages_per_block``:
    the compute block the kernel chose — with 4-slot pages, the 32
    pages that fill one 128-lane score row."""
    dispatch = [s[3] for s in traced["spans"] if s[0] == "decode.dispatch"]
    first = dispatch[0]
    assert first["live_pages"] == 2 + 1
    assert first["live_pages"] <= first["row_bucket"] * first["page_bucket"]
    assert first["kernel_pages_per_block"] == 128 // 4
    # a 32-block pool holds no two copies of 32 pages: a page a copy
    assert first["coalesced_pages"] == 0
    # one more key a row each tick: 7 and 5 keys, 2 + 2 pages
    assert dispatch[1]["live_pages"] == 2 + 2


def test_coalesced_pages_of_a_table_by_hand(monkeypatch):
    """``coalesced_pages``: of the live pages, those the paged kernel
    fetches a run of consecutive pages at a time — counted on the host
    for the step's tables (``_step_counts`` -> runner -> family) with
    the kernel's own plan: here (a copy's byte budget set to 32 of
    these 1 KB pages) the table is read in aligned groups of 32
    entries, in a pool with room for two such copies."""
    import importlib.util
    import sys
    import types
    from paddle2_tpu.serving import paged_attention as pa
    monkeypatch.setattr(pa, "_COPY_BYTES", 32 * 1024)
    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    engine = ServingEngine(model, config=EngineConfig(
        block_size=4, num_blocks=256, max_batch=4, max_model_len=64))
    family = engine.runner.family
    shape = (64, 4, family.num_heads, family.head_dim, engine.cache.dtype,
             None, family.num_kv_heads)
    assert pa.kernel_pages_per_copy(*shape, 256) == 32
    assert pa.kernel_pages_per_copy(*shape, 63) == 1
    tables = np.zeros((4, 64), np.int32)
    tables[0] = np.arange(1, 65)             # one run, 40 pages live
    tables[1] = np.arange(200, 136, -1)      # a run that descends
    tables[2, :32] = np.arange(100, 132)     # a run, then scattered ids
    tables[2, 32:] = np.arange(66, 130, 2)
    live = [40, 64, 64]                      # row 3 is batch padding
    counts = engine._step_counts(3, tables, 0, live, [])
    assert counts["live_pages"] == 168
    assert counts["coalesced_pages"] == 32 + 0 + 32
    assert counts["kernel_pages_per_block"] == pa.kernel_pages_per_block(
        *shape)
    # the benchmark's reader: the steps' own ratio, nothing where no
    # span carries the count (a program from before it)
    spec = importlib.util.spec_from_file_location(
        "reader_paged_pages_coalesced", os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "layer_metrics",
            "paged_pages_coalesced_pct.serve.py"))
    reader = importlib.util.module_from_spec(spec)
    spans = [("decode.dispatch", 0, 1, dict(counts)),
             ("decode.dispatch", 1, 2, dict(counts, coalesced_pages=0))]
    # the reader's one import, stood in for by the spans themselves
    monkeypatch.setitem(sys.modules, "program_trace", types.SimpleNamespace(
        of=lambda ctx: ctx["spans"],
        spans_named=lambda spans, name, window: [
            s for s in spans if s[0] == name]))
    spec.loader.exec_module(reader)
    ctx = {"spans": spans, "trace": types.SimpleNamespace(window=None)}
    assert reader.read(ctx) == pytest.approx(100.0 * 64 / (2 * 168))
    for s in spans:
        del s[3]["coalesced_pages"]
    assert reader.read(ctx) is None
    # and its entry: appended to the manifest, on the five cells whose
    # decode program runs ``paged_decode``
    import json
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"]
              if m["name"] == "paged_pages_coalesced_pct.serve"]
    assert manifest["per_layer"].index(entry) == 53
    assert entry["source"] == "program_counter"
    assert sorted(entry["workloads"]) == sorted(
        w["name"] for w in manifest["workloads"]
        if "-serve-" in w["name"] and not w["name"].startswith("dsv2"))


def test_build_log_one_record_per_program(traced):
    programs = [b["program"] for b in traced["builds"]]
    assert programs.count("train_step") == 1
    assert programs.count("prefill") == 1        # both prompts pad to 16
    assert programs.count("decode") >= 1
    assert programs.count("kv_scatter_prefill") == 2     # 5 and 3 tokens
    n_spans = sum(1 for s in traced["spans"] if s[0] == "build")
    assert n_spans == len(traced["builds"])
    for b in traced["builds"]:
        assert b["trace_s"] > 0 and b["lower_s"] > 0 and b["compile_s"] > 0
        assert b["trace_s"] + b["lower_s"] + b["compile_s"] <= b["total_s"]
        assert b["cost_s"] <= b["total_s"]
        assert (b["cost_s"] > 0) == (b["program"] in ("prefill", "decode"))


def test_build_log_books_the_calls_tracing_not_the_cost_lowering():
    """With ``collect_cost`` the step is lowered a second time for
    ``cost_analysis`` AFTER the call that built it, so the record
    splits tracing, lowering and compiling from that second lowering
    (cost first would hit JAX's caches and read ``trace_s`` 0)."""
    step, ids = tiny_trainer()
    step.collect_cost = True
    profiler._builds.clear()
    step(ids, ids)
    rec, = profiler.builds()
    assert rec["program"] == "train_step" and rec["sig"] == "2x16"
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0
    assert rec["compile_s"] > 0 and rec["cost_s"] > 0
    assert rec["trace_s"] + rec["lower_s"] + rec["compile_s"] \
        + rec["cost_s"] <= rec["total_s"]
    assert step.last_cost_flops > 0


def test_build_log_is_bounded(monkeypatch):
    """A server that meets new shapes for weeks keeps the newest
    records only."""
    import collections
    monkeypatch.setattr(profiler, "_builds", collections.deque(maxlen=3))
    for i in range(5):
        with profiler.build("kv_scatter_prefill", str(i)):
            pass
    assert [b["sig"] for b in profiler.builds()] == ["2", "3", "4"]
    assert isinstance(profiler._builds.maxlen, int)


def test_builds_of_two_threads_do_not_mix():
    """The open build is the THREAD's: one that compiles inside its
    build while another thread's build is open fills its own record
    only, and neither is left installed afterwards."""
    import threading
    import jax.numpy as jnp
    profiler._builds.clear()
    opened, compiled = threading.Event(), threading.Event()

    def compiles():
        opened.wait(30)
        with profiler.build("decode", "compiles"):
            jax.jit(lambda x: x * 3 + 1)(jnp.ones((3,))).block_until_ready()
        compiled.set()

    def idles():
        with profiler.build("prefill", "idles"):
            opened.set()
            compiled.wait(30)

    threads = [threading.Thread(target=f) for f in (compiles, idles)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    by_sig = {b["sig"]: b for b in profiler.builds()}
    assert by_sig["compiles"]["trace_s"] > 0
    assert by_sig["compiles"]["compile_s"] > 0
    assert by_sig["idles"]["trace_s"] == 0 == by_sig["idles"]["compile_s"]
    assert getattr(profiler._open, "build", None) is None
    # a build of this thread afterwards is filled as ever
    with profiler.build("decode", "after"):
        jax.jit(lambda x: x * 5 - 2)(jnp.ones((3,))).block_until_ready()
    assert profiler.builds()[-1]["trace_s"] > 0


def test_no_session_nothing_recorded_nothing_written(tmp_path, monkeypatch):
    """The same calls with no profiler session: no record in the
    in-process sink, no file in the working directory, and a warm
    program adds no build record."""
    monkeypatch.chdir(tmp_path)
    step, ids = tiny_trainer()
    step(ids, ids)
    engine = tiny_gpt_engine()
    engine.submit(PROMPTS[0], 3)
    engine.tick(0.0)
    n_builds = len(profiler.builds())
    n_events = len(profiler._collector.events)
    for now in (1.0, 2.0):
        step(ids, ids)
        engine.tick(now)
    assert len(profiler.builds()) == n_builds
    assert len(profiler._collector.events) == n_events
    assert step.program_cache_size == 1
    assert not os.listdir(tmp_path)


@contextlib.contextmanager
def no_scopes(monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    yield
    monkeypatch.undo()


def test_scopes_and_spans_change_no_arithmetic(traced, monkeypatch):
    """Losses and served tokens with every ``jax.named_scope`` turned
    into a no-op are bitwise those of the traced run."""
    with no_scopes(monkeypatch):
        losses, tokens, _ = run_both()
    assert losses == traced["losses"]
    assert tokens == traced["tokens"]


@pytest.fixture(scope="module")
def lowered_train_text():
    step, ids = tiny_trainer(use_recompute=True,
                             recompute_granularity="dots",
                             fused_head_loss=True)
    step.collect_cost = True
    step(ids, ids)
    lowered = step.last_entry.lower(*step.last_abstract_args)
    return lowered.as_text(dialect="hlo", debug_info=True)


@pytest.mark.parametrize("scope", ["embed", "norm", "attn", "mlp",
                                   "head_ce", "optimizer"])
def test_lowered_train_step_carries_scope(lowered_train_text, scope):
    assert scope_in(lowered_train_text, scope)


def test_lowered_train_step_is_named(lowered_train_text):
    assert lowered_train_text.startswith("HloModule jit_p2t_train_step")
    assert "_lambda_" not in lowered_train_text


@pytest.fixture(scope="module")
def lowered_serving_texts():
    import jax.numpy as jnp
    engine = tiny_gpt_engine()
    runner, cache = engine.runner, engine.cache
    decode = runner._build_decode(2, 2, cache.block_size)
    dec = decode.lower(*runner._decode_args(
        cache, jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 2), jnp.int32), None))
    prefill = runner._build_prefill(16)
    pre = prefill.lower(runner._weights(), jnp.zeros((1, 16), jnp.int32),
                        jnp.asarray(4, jnp.int32))
    return {"decode": dec.as_text(dialect="hlo", debug_info=True),
            "prefill": pre.as_text(dialect="hlo", debug_info=True)}


@pytest.mark.parametrize("program,scope", [
    ("decode", "embed"), ("decode", "norm"), ("decode", "attn"),
    ("decode", "kv_write"), ("decode", "mlp"), ("decode", "head_ce"),
    ("decode", "sample"), ("prefill", "attn"), ("prefill", "mlp"),
    ("prefill", "head_ce"), ("prefill", "sample"), ("prefill", "kv_write")])
def test_lowered_serving_program_carries_scope(lowered_serving_texts,
                                               program, scope):
    text = lowered_serving_texts[program]
    assert scope_in(text, scope)
    assert "jit_p2t_" + program in text
    assert "_lambda_" not in text
