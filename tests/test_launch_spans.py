"""Launch ordinals (``profiler.launch``; PERF.md section 3): the four
spans that enqueue an execution of a jitted entry say WHICH execution —
``program`` (the HLO module's name), ``launch`` (the ordinal of the
first one enqueued inside the span), ``launches`` (how many) — and
``decode.dispatch`` also names the execution it read back
(``read_launch``). The ordinal is advanced where the jitted entry is
called, so the tests below hold the spans to the calls that really
happened. Then the benchmark's join (``benchmark/program_split.py``) on
synthetic spans and module events."""

import contextlib
import importlib
import os
import sys
import types

import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu import profiler
from paddle2_tpu.models import (DeepseekV2ForCausalLM, GPTForCausalLM,
                                deepseek_v2_tiny, gpt_tiny)
from paddle2_tpu.serving import EngineConfig, ServingEngine, model_runner
from paddle2_tpu.serving.block_cache import SCATTER_MODULE
from paddle2_tpu.serving.model_runner import (DECODE_MODULE, PREFILL_MODULE,
                                              PagedRunner)
from served import (PROMPTS, armed, engine_of, prompts_of, read_spans,
                    step_by_step, tiny_gpt_engine, tiny_trainer,
                    trace_session)

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
train_step_mod = importlib.import_module("paddle2_tpu.jit.train_step")
STEP_MODULE = train_step_mod.STEP_MODULE
ENQUEUING = {"prefill.dispatch": PREFILL_MODULE,
             "prefill.scatter": SCATTER_MODULE,
             "decode.dispatch": DECODE_MODULE,
             "train.dispatch": STEP_MODULE}


def slots(spans, name):
    """[ordinal] of every execution that the spans called ``name`` say
    they enqueued, in span order."""
    out = []
    for s in spans:
        if s[0] == name and "launch" in s[3]:
            out += range(s[3]["launch"],
                         s[3]["launch"] + s[3].get("launches", 1))
    return out


def called(monkeypatch):
    """Record the ordinal each REAL call of a prefill or decode program
    takes: {module name: [ordinals]}, filled as the engine runs."""
    seen = {PREFILL_MODULE: [], DECODE_MODULE: []}

    def recording(build, module):
        def wrapper(self, *key):
            fn = build(self, *key)

            def call(*args):
                seen[module].append(profiler.launched(module))
                return fn(*args)
            call.lower = fn.lower
            return call
        return wrapper

    monkeypatch.setattr(PagedRunner, "_build_prefill", recording(
        PagedRunner._build_prefill, PREFILL_MODULE))
    monkeypatch.setattr(PagedRunner, "_build_decode", recording(
        PagedRunner._build_decode, DECODE_MODULE))
    return seen


def serve(engine, arrivals, trace_dir=None, max_ticks=400):
    """Submit ``arrivals`` ([(tick, prompt, max new)]) and tick until the
    engine is idle, under a profiler session when ``trace_dir`` is
    given; (the program's spans or None, the served tokens)."""
    with trace_session(trace_dir) if trace_dir is not None \
            else contextlib.nullcontext():
        todo, rids, tick = sorted(arrivals, key=lambda a: a[0]), [], 0
        while todo or not engine.idle():
            while todo and todo[0][0] <= tick:
                _, prompt, max_new = todo.pop(0)
                rids.append(engine.submit(prompt, max_new))
            engine.tick(float(tick))
            tick += 1
            assert tick < max_ticks, "engine did not drain"
    tokens = [list(engine.sequence(r).generated) for r in rids]
    return (read_spans(str(trace_dir)) if trace_dir else None), tokens


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three train steps, then two requests served to the end on the
    GPT engine (a K and a V pool), one profiler session."""
    trace_dir = tmp_path_factory.mktemp("p2t_launch")
    before = {m: profiler.launched(m) for m in ENQUEUING.values()}
    with trace_session(trace_dir):
        step, ids = tiny_trainer()
        for _ in range(3):
            step(ids, ids)
        engine = tiny_gpt_engine()
        _, tokens = serve(engine, [(0, PROMPTS[0], 5), (1, PROMPTS[1], 4)])
    after = {m: profiler.launched(m) for m in ENQUEUING.values()}
    return {"spans": read_spans(str(trace_dir)), "tokens": tokens,
            "before": before, "after": after}


# -- the four spans ------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(ENQUEUING))
def test_ordinals_rise_by_launches_and_never_repeat(traced, name):
    module = ENQUEUING[name]
    spans = [s for s in traced["spans"]
             if s[0] == name and "launch" in s[3]]
    assert spans and all(s[3]["program"] == module for s in spans)
    # every execution enqueued in the session is on exactly one span, in
    # order: the counter's own range
    assert slots(traced["spans"], name) == list(
        range(traced["before"][module], traced["after"][module]))
    expect = traced["before"][module]
    for s in spans:
        assert s[3]["launch"] == expect
        expect += s[3].get("launches", 1)


def test_how_many_executions_a_span_enqueued(traced):
    by = {name: [s[3] for s in traced["spans"] if s[0] == name]
          for name in ENQUEUING}
    assert [c["launches"] for c in by["prefill.dispatch"]] == [1, 1]
    # a K and a V pool: two scatters a prefill
    assert [c["launches"] for c in by["prefill.scatter"]] == [2, 2]
    assert len(by["train.dispatch"]) == 3
    # the step's own counts stay beside the new ones
    first = by["decode.dispatch"][0]
    assert first["rows"] == 1 and first["program"] == DECODE_MODULE


def test_read_launch_is_the_step_before_when_ahead(traced):
    disp = [s[3] for s in traced["spans"] if s[0] == "decode.dispatch"]
    assert "read_launch" not in disp[0]         # nothing in flight yet
    assert "launch" not in disp[-1]             # the last only delivers
    for before, c in zip(disp, disp[1:]):
        assert c["read_launch"] == before["launch"]
        if "launch" in c:
            assert c["ahead"] == 1 and c["launch"] == c["read_launch"] + 1
    # every step enqueued is read back exactly once
    assert [c["read_launch"] for c in disp[1:]] == \
        [c["launch"] for c in disp[:-1]]


def test_read_launch_is_the_same_call_under_sync(tmp_path):
    engine = tiny_gpt_engine()
    with step_by_step():
        spans, _ = serve(engine, [(0, PROMPTS[0], 4)], tmp_path)
    disp = [s[3] for s in spans if s[0] == "decode.dispatch"]
    assert len(disp) == 3
    for c in disp:
        assert c["ahead"] == 0 and c["read_launch"] == c["launch"]


# -- the ordinals name real executions -------------------------------------------
SCENARIOS = {
    # nine usable blocks under two sequences that outgrow them: the
    # newer is evicted with a token in flight and prefilled again
    "eviction": dict(lengths=(27, 30), new=12, seed=4,
                     over=dict(num_blocks=10)),
    # a scribbled table requeues its sequence with a token in flight
    "requeue": dict(lengths=(10, 12), new=8, seed=5,
                    chaos="corrupt_block_table:3"),
    # the hook discards a step that was read back: it is computed again
    "drop_decode_step": dict(lengths=(10, 13), new=7, seed=9,
                             chaos="drop_decode_step:2"),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_ordinals_name_real_executions(tmp_path, monkeypatch, scenario):
    sc = SCENARIOS[scenario]
    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny(use_scan=False))
    model.eval()
    seen = called(monkeypatch)
    engine = engine_of(model, **sc.get("over", {}))
    p = prompts_of(model, sc["lengths"], seed=sc["seed"])
    with armed(sc["chaos"]) if "chaos" in sc else contextlib.nullcontext():
        spans, tokens = serve(engine, [(0, q, sc["new"]) for q in p],
                              tmp_path)
    assert [len(t) for t in tokens] == [sc["new"]] * 2
    if scenario == "eviction":
        assert engine.scheduler.total_evictions >= 1
    if scenario == "requeue":
        assert sum(s.recoveries for s in engine.scheduler.finished) == 1
    if scenario != "drop_decode_step":
        assert engine.ahead_dropped >= 1
        # an evicted or requeued sequence is prefilled a second time
        assert len(seen[PREFILL_MODULE]) == 3
    # span by span, the ordinal is the one the real call took
    assert slots(spans, "prefill.dispatch") == seen[PREFILL_MODULE]
    assert slots(spans, "decode.dispatch") == seen[DECODE_MODULE]
    assert len(set(seen[DECODE_MODULE])) == len(seen[DECODE_MODULE])
    disp = [s[3] for s in spans if s[0] == "decode.dispatch"]
    reads = [c["read_launch"] for c in disp if "read_launch" in c]
    # every execution is read back once, in order, whatever was dropped
    # from it on the way; none is read that was not enqueued
    assert reads == seen[DECODE_MODULE]
    if scenario == "drop_decode_step":
        # the engine reads in place while the hook is armed; the dropped
        # step's repeat is a new execution with an ordinal of its own
        assert all(c["read_launch"] == c["launch"] for c in disp)
        assert engine.decode_steps == len(disp) == sc["new"]


# -- how many scatters a prefill enqueues ----------------------------------------
def test_the_latent_family_scatters_one_pool(tmp_path):
    paddle.seed(0)
    model = DeepseekV2ForCausalLM(deepseek_v2_tiny())
    model.eval()
    engine = ServingEngine(model, config=EngineConfig(
        block_size=8, num_blocks=64, max_batch=4, max_model_len=96,
        kv_dtype="float32", interpret=True))
    rng = np.random.default_rng(0)
    spans, _ = serve(engine, [(0, rng.integers(1, 503, 11).tolist(), 2)],
                     tmp_path)
    scatter, = [s[3] for s in spans if s[0] == "prefill.scatter"]
    assert scatter["program"] == SCATTER_MODULE
    # by_layer: the layer loop is inside ONE execution
    assert scatter["launches"] == 1


def test_a_prefix_hit_that_covers_the_prompt_scatters_nothing(tmp_path):
    engine = ServingEngine(GPTForCausalLM(gpt_tiny()), config=EngineConfig(
        block_size=4, num_blocks=32, max_batch=4, max_model_len=64,
        enable_prefix_cache=True))
    prompt = list(range(1, 9))          # two whole blocks
    spans, tokens = serve(engine, [(0, prompt, 3), (6, prompt, 3)],
                          tmp_path)
    assert engine.prefix_cache.hits >= 1 and tokens[0] == tokens[1]
    first, second = [s[3] for s in spans if s[0] == "prefill.scatter"]
    assert first["launches"] == 2
    assert second["launches"] == 0
    # the ordinal a scatter WOULD have taken: the next span starts there
    assert second["launch"] == first["launch"] + 2
    assert slots(spans, "prefill.scatter") == [first["launch"],
                                               first["launch"] + 1]
    # the prefill itself ran whole both times
    assert [s[3]["launches"] for s in spans
            if s[0] == "prefill.dispatch"] == [1, 1]


# -- always on, and free of consequences --------------------------------------
def test_no_session_nothing_written_and_the_counter_changes_no_token(
        tmp_path, monkeypatch, traced):
    monkeypatch.chdir(tmp_path)
    n_events = len(profiler._collector.events)
    before = profiler.launched(DECODE_MODULE)
    _, counted = serve(tiny_gpt_engine(),
                       [(0, PROMPTS[0], 5), (1, PROMPTS[1], 4)])
    # five tokens: the prefill's and four decode steps
    assert profiler.launched(DECODE_MODULE) == before + 4
    # the counter patched out at its three call sites
    monkeypatch.setattr(model_runner, "_launch", lambda program: 0)
    monkeypatch.setattr(profiler, "launch", lambda program: 0)
    monkeypatch.setattr(train_step_mod, "_launch", lambda program: 0)
    frozen = {m: profiler.launched(m) for m in ENQUEUING.values()}
    _, plain = serve(tiny_gpt_engine(),
                     [(0, PROMPTS[0], 5), (1, PROMPTS[1], 4)])
    step, ids = tiny_trainer()
    step(ids, ids)
    assert frozen == {m: profiler.launched(m) for m in ENQUEUING.values()}
    assert counted == plain == traced["tokens"]
    assert len(profiler._collector.events) == n_events
    assert not os.listdir(tmp_path)


# -- the join, on synthetic spans and module events ---------------------------
DEVICE = "/device:TPU:0"
DECODE = DECODE_MODULE


@pytest.fixture()
def split(monkeypatch):
    monkeypatch.syspath_prepend(BENCHMARK)
    for name in ("program_split", "program_trace", "trace_reduce", "common"):
        monkeypatch.delitem(sys.modules, name, raising=False)
    import program_split
    return program_split


def stretch(split, steps, first_launch=7, step_ns=10, early=(), late=0,
            shift=0):
    """A decode loop one step ahead: span k [100 + 10k, +4) enqueues
    ordinal ``first_launch + k`` and reads ordinal ``first_launch + k -
    1`` back in its nested read-back [+1, +4); its execution runs
    [+5, +13) (moved by ``shift``). ``early``: module events of
    executions enqueued before the trace began; ``late``: the last
    ``late`` spans' executions are not in the trace."""
    import program_trace
    spans, mods = [], [(DECODE, a, b) for a, b in early]
    for k in range(steps):
        t = 100 + step_ns * k
        spans.append(("decode.dispatch", t, t + 4, {
            "program": DECODE, "launch": first_launch + k,
            "read_launch": first_launch + k - 1,
            "row_bucket": 4, "page_bucket": 8}))
        spans.append(("decode.readback", t + 1, t + 4, {}))
        if k < steps - late:
            mods.append((DECODE, t + 5 + shift, t + 13 + shift))
    return program_trace.ProgramTrace(
        spans=sorted(spans, key=lambda s: (s[1], -s[2])),
        ops={DEVICE: []}, modules={DEVICE: sorted(mods, key=lambda m: m[1])})


def ctx_of(split, pt, window):
    split.program_trace.of = lambda ctx: pt
    return {"trace": types.SimpleNamespace(window=window), "cell": {},
            "reduce": None}


def test_join_a_stretch_that_begins_with_an_execution_enqueued_before_it(
        split):
    pt = stretch(split, 5, early=[(95, 103)])
    j = split.launches(pt, (90, 200))[DECODE]
    assert len(j.executions) == 6 and j.unjoined == 1 and not j.violated
    assert [e.launch for e in j.executions] == [None, 7, 8, 9, 10, 11]
    # each execution starts 1 after its enqueuing call returned (the
    # read-back's start) and never before its span began
    assert [e.lead_ns for e in j.joined] == [4] * 5
    assert all(e.start >= e.span[1] for e in j.joined)
    ctx = ctx_of(split, pt, (90, 200))
    assert split.decode_device_ms(ctx) == pytest.approx(8e-6)
    assert split.decode_period_ms(ctx) == pytest.approx(10e-6)
    assert split.dispatch_lead_ms(ctx) == pytest.approx(4e-6)
    # a stretch that begins INSIDE that execution still holds its tail
    # (what the module line's own sum holds), clipped where it is summed
    cut = split.launches(pt, (100, 200))[DECODE]
    assert len(cut.executions) == 6 and cut.unjoined == 1
    assert cut.executions[0].ns_within((100, 200)) == 3


def test_join_a_stretch_that_ends_with_an_execution_still_queued(split):
    pt = stretch(split, 5, late=1)
    j = split.launches(pt, (90, 200))[DECODE]
    assert [e.launch for e in j.executions] == [7, 8, 9, 10]
    assert j.unjoined == 1 and j.sound
    # three at the edges are too many: the readers say nothing
    pt = stretch(split, 6, early=[(95, 103)], late=2)
    j = split.launches(pt, (90, 200))[DECODE]
    assert j.unjoined == 3 and not j.sound
    assert split.decode_device_ms(ctx_of(split, pt, (90, 200))) is None


def test_join_a_violated_pair_gives_none(split):
    # every execution ends after the read-back that claims to have
    # waited for it: the order of the module events is not the order of
    # the ordinals, and no reader may give a number
    pt = stretch(split, 5, shift=3)
    j = split.launches(pt, (90, 200))[DECODE]
    assert j.violated and not j.sound
    ctx = ctx_of(split, pt, (90, 200))
    assert split.decode_device_ms(ctx) is None
    assert split.decode_period_ms(ctx) is None
    assert split.dispatch_lead_ms(ctx) is None
    # and an execution is never given to a span that began after it
    # started: with the events moved BEFORE their spans the join shifts
    # by one and leaves the edges unjoined instead
    pt = stretch(split, 5, shift=-6)
    j = split.launches(pt, (80, 200))[DECODE]
    assert all(e.start >= e.span[1] for e in j.joined)
    assert [e.launch for e in j.executions] == [None, 7, 8, 9, 10]


def test_join_spans_without_a_launch_say_nothing(split):
    """An older program's spans: every execution is unjoined."""
    pt = stretch(split, 5)
    pt.spans = [(n, a, b, {k: v for k, v in c.items()
                           if k not in ("program", "launch", "read_launch")})
                for n, a, b, c in pt.spans]
    j = split.launches(pt, (90, 200))[DECODE]
    assert len(j.executions) == 5 and not j.joined and not j.sound
    ctx = ctx_of(split, pt, (90, 200))
    for reader in (split.decode_device_ms, split.decode_period_ms,
                   split.dispatch_lead_ms, split.prefill_device_ms_per_ktok):
        assert reader(ctx) is None
