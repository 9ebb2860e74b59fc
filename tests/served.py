"""The served-family test harness: what the family files (``test_lfm2_moe``,
``test_deepseek``, ``test_sdar``, ``test_falcon_h1``, ``test_nemotron_h``,
``test_exaone_moe``),
the run-ahead files (``test_decode_ahead``, ``test_prefill_ahead``) and the
``*_spans`` files share. Imported, never collected: no test lives here, and
no test file imports another.

How a new family's tests are written (ROADMAP D8: at most 120 s of one
worker, read in the table of ``CHANGES.md``):

* ``bench = served.bench_fixture("<file under benchmark/configs>")`` — ONE
  ``bench(config_name)``: the benchmark's modules, the rehearsal
  configuration, the plain reference. What is particular to the family (a
  driver that places weights a leaf at a time, a cut of the layers, a held
  group) goes in as arguments, not as a copy of the function.
* ``served.build(bench, seed)`` is memoised for the module: the weights
  are read-only in a serving test. A test that writes them
  (``set_value``, ``swap_weights``) asks for ``fresh=True``.
* ``served.tiny_engine(model)`` (the engine's own ladder of buckets),
  ``serve`` under the ``logit_tap`` fixture, ``check_against_reference``
  with the family's tolerance and matmul precision as arguments. Every
  test makes its own engine; the module asks for ``shared_programs``, so
  that engines of one configuration trace, lower and compile a program
  once a module, and the run's compile cache (``conftest.py``) spares XLA
  what another module or worker compiled. A test that counts builds or
  compilations asks for ``own_programs``.
* The span contract of the family: ``seeded_engine`` + ``serve_traced``
  ONCE in a module fixture, the tests read its spans. A profiler session
  goes through ``trace_session`` (no Python tracer).
* Tests that tap logits, the other engine tests, and the model's and the
  kernels' own tests go in files of their own (``test_<family>_logits.py``,
  ``_engine.py``, ``test_<family>.py``): under ``--dist loadfile`` a file
  is one worker's, and none may take a sixth of the limit (245 s).

Every size here is tiny and every Pallas kernel interpreted: a program's
kernels are traced and lowered interpreted by every engine that builds it
(0.4-0.8 s a ``pallas_call``), so count engines and buckets before adding
cases."""

import contextlib
import functools
import glob
import importlib
import importlib.util
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu import profiler
from paddle2_tpu.distributed.fault_tolerance import chaos
from paddle2_tpu.incubate.moe import DroplessExperts
from paddle2_tpu.serving import EngineConfig, ServingEngine, blocks_for_tokens
from paddle2_tpu.serving.model_runner import PagedRunner

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
LOGIT_TOL = 5e-5
STATE_TOL = 2e-5
# kernel vs dense reference, fp32: the kernel reduces per page and then
# across pages ([P, 8, bs] scratch, lane-group dots — the layout the
# chip's compiler accepts) where the reference reduces one [1, S] row —
# the same op sequence under a different summation order, so agreement
# is a few ulp, not bitwise (ROADMAP D8(c))
KERNEL_TOL = dict(rtol=2e-6, atol=2e-6)


# ------------------------------------------- the benchmark's configuration
def bench(config_name, driver=None, **cut):
    """The benchmark's modules and the tiny (rehearsal) configuration of
    ``benchmark/configs/<config_name>.json``, ``cut`` laid over it (fewer
    layers, a prefix of a pattern). ``driver``: the module under
    ``benchmark/drivers`` whose ``place_weights`` places the seed's
    weights; ``drivers.program.set_weights`` without one. ``benchmark/``
    is on ``sys.path`` (``bench_fixture`` puts it there)."""
    harness = importlib.import_module("run")
    common = importlib.import_module("common")
    program = importlib.import_module("drivers.program")
    with open(os.path.join(BENCH, "configs", config_name + ".json")) as f:
        published = json.load(f)
    whole = harness.merge(published, published["rehearsal"])
    whole["name"] = config_name
    cfg = dict(whole, **cut)
    place = program.set_weights if driver is None else \
        importlib.import_module("drivers." + driver).place_weights
    return {"cfg": cfg, "whole": whole, "published": published,
            "ref": common.load_module("reference", cfg["reference"]),
            "program": program, "place": place,
            "make_weights": importlib.import_module("weights").make_weights,
            "load_module": common.load_module, "built": {}}


def bench_fixture(config_name, driver=None, **cut):
    """``bench(...)`` as a module-scoped fixture that holds ``benchmark/``
    on ``sys.path`` for the module's tests (they import ``reference``,
    ``common`` themselves)."""
    @pytest.fixture(scope="module")
    def bench_of_the_module():
        with pytest.MonkeyPatch.context() as patch:
            patch.syspath_prepend(BENCH)
            yield bench(config_name, driver, **cut)
    return bench_of_the_module


# The five served families: what is particular to each, as arguments.
lfm2_bench = bench_fixture("lfm2-24b-a2b")
# a dense layer and two expert layers, this chip holding routing group 0
# of 4
deepseek_bench = bench_fixture("deepseek-v2", num_hidden_layers=3)
# two of the four identical layers: half the interpreted kernels
sdar_bench = bench_fixture("sdar-30b-a3b-chat", num_hidden_layers=2)
falcon_h1_bench = bench_fixture("falcon-h1-34b-instruct",
                                driver="serve_staged_dense")
NEMOTRON_CUT = "MEMEM*EME"     # the benchmark's cut, at the rehearsal's widths
# what the engine tests serve: every kind, 6 layers. The layout names a
# leaf by its layer's index, so a prefix of the pattern is served through
# the same file (``bench["whole"]``: the cut as published)
NEMOTRON_PATTERN = NEMOTRON_CUT[:6]
nemotron_h_bench = bench_fixture(
    "nemotron-3-nano-30b-a3b", driver="serve_staged_dense",
    hybrid_override_pattern=NEMOTRON_PATTERN,
    num_hidden_layers=len(NEMOTRON_PATTERN))

# the benchmark's cut without its last layer, at the rehearsal's widths
# (window 8): a dense sliding layer, two sliding expert layers and the
# global expert layer
EXAONE_LAYERS = 4
exaone_moe_bench = bench_fixture(
    "k-exaone-236b-a23b", driver="serve_staged_dense",
    num_hidden_layers=EXAONE_LAYERS,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 3,
    sliding_windows=[8, 8, 8, 0])


def build(bench, seed, cfg=None, fresh=False, **overrides):
    """(model with the seed's weights, its config, the reference's
    float32 leaves of the same seed). One model per (configuration,
    seed, overrides) and module, handed out again: ``fresh`` for a test
    that writes the weights."""
    cfg = cfg or bench["cfg"]

    def make():
        model, mcfg = bench["program"].build_model(cfg, overrides)
        model.eval()
        bench["place"](model, cfg, "per_layer", bench["ref"], seed)
        return model, mcfg, bench["make_weights"](
            bench["ref"].leaf_specs(cfg), seed, jnp.float32)

    if fresh:
        return make()
    key = json.dumps([cfg, seed, sorted(overrides.items())], sort_keys=True,
                     default=repr)
    if key not in bench["built"]:
        bench["built"][key] = make()
    return bench["built"][key]


def build_as_read(bench, seed, **config):
    """``build`` with ``config`` laid over the configuration as the
    REFERENCE reads it (SDAR's block length is the model's, not a way to
    run it): (model, that configuration, the float32 leaves)."""
    cfg = dict(bench["cfg"], **config)
    model, _, params = build(bench, seed, cfg)
    return model, cfg, params


def ref_logits(bench, params, seq, cfg=None, precision=None, pad_to=None):
    """The reference's logits over ``seq``; ``precision``: the matmul
    precision the family's reference is read under (``"highest"`` where a
    recurrence compounds the default's rounding); ``pad_to``: a causal
    reference run at ONE length whatever the sequence's (it runs op by
    op, and every new length compiles every op again)."""
    ids = list(seq) + [0] * max(0, (pad_to or 0) - len(seq))
    with jax.default_matmul_precision(precision) if precision \
            else contextlib.nullcontext():
        return np.asarray(bench["ref"].logits(
            params, jnp.asarray([ids], jnp.int32),
            cfg or bench["cfg"])[0, :len(seq)])


def check_against_reference(bench, params, engine, rids, rows,
                            tol=LOGIT_TOL, precision=None,
                            reprefilled=False, pad_to=None):
    """Every served logits row against the reference's full forward over
    prompt + generated; the widest difference. ``reprefilled``: an evicted
    sequence's re-prefill yields its next token again, so a request may
    hold more rows than tokens."""
    worst = 0.0
    for rid in rids:
        seq = engine.sequence(rid)
        prompt, gen = seq.request.prompt, seq.generated
        if reprefilled:
            assert len(rows[rid]) >= len(gen)
        else:
            assert len(rows[rid]) == len(gen)
        ref = ref_logits(bench, params, list(prompt) + list(gen),
                         precision=precision, pad_to=pad_to)
        for j, row in enumerate(rows[rid][:len(gen)]):
            worst = max(worst, float(np.abs(
                row - ref[len(prompt) - 1 + j]).max()))
    assert worst <= tol, worst
    return worst


# Falcon-H1 and Nemotron-H: the recurrence compounds the default
# precision's rounding, so the reference is read under "highest"; and an
# evicted sequence's re-prefill yields its next token again
ref_logits_highest = functools.partial(ref_logits, precision="highest")
check_against_reference_highest = functools.partial(
    check_against_reference, precision="highest", reprefilled=True)


# ------------------------------------------------- engines and their drive
NEVER = "drop_decode_step:1000000000"
FAMILIES = ["gpt", "lfm2"]


_TAPPED = []                     # the logits of the test in hand
_sample = PagedRunner._sample


def _tapped_sample(logits, counts):
    jax.debug.callback(lambda lg: _TAPPED.append(np.asarray(lg)), logits,
                       ordered=True)
    return _sample(logits, counts)


@pytest.fixture
def logit_tap(monkeypatch):
    """Every logits array the runner's sampling wrapper is handed, in
    call order, without a new engine flag. ``serve`` pairs a call of
    ``decode_once`` with the logits of the step it ran, so the engine
    is held to reading every step back in the call that enqueued it —
    by its own rule: an armed drop hook (which never fires here). ONE
    wrapper and one list for every test, so that tapped programs can be
    shared (``shared_programs``) among the tests that tap."""
    monkeypatch.setattr(chaos, "_ACTIVE", chaos.ChaosInjector(NEVER))
    monkeypatch.setattr(PagedRunner, "_sample",
                        staticmethod(_tapped_sample))
    del _TAPPED[:]
    yield _TAPPED
    del _TAPPED[:]


def serve(engine, prompts, max_new, store):
    """Drive the engine to idle; {request id: [logits row of each
    generated token, in order]} and the request ids."""
    rids = [engine.submit(p, max_new) for p in prompts]
    rows = {r: [] for r in rids}
    now = 0.0
    while not engine.idle():
        now += 1.0
        for info in engine.admit_and_prefill(now):
            jax.effects_barrier()
            rows[info["seq"].req_id].append(store.pop(0)[0])
        active = [s for s in engine.scheduler.running()
                  if getattr(s, "ready_at", 0.0) <= now]
        before = engine.scheduler.total_evictions
        if engine.decode_once(now):
            jax.effects_barrier()
            lg = store.pop(0)
            # an eviction inside the step drops rows from the END of
            # the running list (LIFO victims)
            gone = engine.scheduler.total_evictions - before
            for i, s in enumerate(active[:len(active) - gone]):
                rows[s.req_id].append(lg[i])
    assert not store
    return rids, rows


def run_to_idle(engine, prompts=(), max_new=0):
    """Submit ``prompts`` (if any) and tick the engine until it is idle;
    the tokens served for each."""
    rids = [engine.submit(p, max_new) for p in prompts]
    now = 0.0
    while not engine.idle():
        now += 1.0
        engine.tick(now)
    return [list(engine.sequence(r).generated) for r in rids]


def generate_both(bench, cfg, params, engine, prompts, max_new, steps):
    """The engine's tokens and passes beside ``reference.generate``'s."""
    rids = [engine.submit(p, n) for p, n in zip(prompts, max_new)]
    run_to_idle(engine)
    for rid, prompt, n in zip(rids, prompts, max_new):
        seq = engine.sequence(rid)
        want, record = bench["ref"].generate(params, prompt, n, cfg, steps)
        assert seq.generated == want, rid
        got = [(s, row.tolist()) for s, row, _, commit
               in engine.block_passes(rid) if not commit]
        assert got == [(s, row.tolist()) for s, row in record], rid
    return rids


# The family files' engine: pages of 8, float32 pools and the engine's own
# ladder of buckets, as the benchmark's drivers leave it: a test builds the
# B = 1, 2 and 4 decode programs its traffic asks for and crosses from one
# to the next. ``shared_programs`` builds each of them once a module.
TINY_ENGINE = dict(block_size=8, num_blocks=64, max_batch=4, max_model_len=96,
                   kv_dtype="float32", interpret=True)


def tiny_engine(model, **kw):
    return ServingEngine(model, config=EngineConfig(**dict(TINY_ENGINE, **kw)))


def seeded_engine(model_class, config, **kw):
    """The span files' engine: a model of ``paddle.seed(0)``, one decode
    program whatever the batch (4 rows x 4 pages)."""
    paddle.seed(0)
    model = model_class(config)
    model.eval()
    conf = dict(block_size=8, num_blocks=64, max_batch=4, max_model_len=96,
                batch_buckets=(4,), page_buckets=(4,), interpret=True)
    conf.update(kw)
    return ServingEngine(model, config=EngineConfig(**conf))


# --------------------------------------- one build of a program a module
# What a runner holds that is NOT part of a program: the tables the
# programs and their costs are kept in, and the weights, which ride as
# arguments. Everything else in ``vars(runner)`` goes into the key, so an
# attribute a later PR gives the runner parts the programs by default.
_RUNNER_TABLES = frozenset(("_decode_programs", "_prefill_programs",
                            "_decode_costs", "_prefill_costs",
                            "_swap_arrays"))


def _trace_time_globals():
    """What a program reads at trace time beside its runner: the sampling
    wrapper and the unmasking rule (the logits taps replace both) and the
    kernels' block budgets (tests patch them)."""
    from paddle2_tpu.kernels import ssd
    from paddle2_tpu.serving import blockdiff, paged_attention
    return (PagedRunner._sample, blockdiff.unmask_low_confidence,
            ssd.STATE_BLOCK_BYTES, paged_attention._MLA_BLOCK_BYTES,
            paged_attention._MLA_COPY_BYTES)


def program_key(runner, kind, args):
    """What tells one program of a runner from another: its kind and
    bucket, every attribute of the runner but its tables (the model as
    its class and configuration, the family as its class, the state as
    its leaves' shapes and types: they tell a quantised model from the
    plain one), and ``_trace_time_globals``. An attribute that cannot be
    hashed fails the lookup, loudly."""
    held = []
    for name, value in sorted(vars(runner).items()):
        if name in _RUNNER_TABLES:
            continue
        if name == "model":
            value = (type(value), repr(value.cfg))
        elif name == "family":
            assert value.model is runner.model
            value = type(value)
        elif name == "_state":
            value = tuple((tuple(t._data.shape), str(t._data.dtype))
                          for t in value)
        held.append((name, value))
    return (kind, args, tuple(held), _trace_time_globals())


@pytest.fixture(scope="module")
def shared_programs():
    """For the module that asks for it (``pytestmark =
    pytest.mark.usefixtures("shared_programs")``): engines whose runners
    would build the SAME program (``program_key``) hand each other the
    one ``jax.jit`` entry; the weights ride as arguments. Every test
    still makes its own engine — scheduler, allocator, pools, the
    engine's own program tables, its ``build`` spans and its costs (each
    engine lowers the entry again for its cost) — but the entry behind a
    table's slot was traced, lowered (Pallas interpreted) and compiled
    once for the module. The entry closes over the FIRST runner and its
    family: of the same class and configuration by the key, and used for
    their structure only. The memo dies with the module."""
    entries = {}
    sharing = types.SimpleNamespace(own=False)      # see own_programs

    def shared(kind, build):
        def build_or_take(runner, *args):
            if sharing.own:
                return build(runner, *args)
            key = program_key(runner, kind, args)
            if key not in entries:
                entries[key] = build(runner, *args)
            return entries[key]
        return build_or_take

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(PagedRunner, "_build_prefill",
                      shared("prefill", PagedRunner._build_prefill))
        patch.setattr(PagedRunner, "_build_decode",
                      shared("decode", PagedRunner._build_decode))
        yield sharing


@pytest.fixture
def own_programs(shared_programs):
    """A test that asks for it builds its engines' programs itself
    although its module shares them: it counts builds or compilations."""
    shared_programs.own = True
    yield
    shared_programs.own = False


# ------------------------------------------------------- the run-ahead files
@contextlib.contextmanager
def armed(spec: str):
    chaos.arm(spec)
    try:
        yield
    finally:
        chaos.disarm()


def step_by_step(also: str = ""):
    """Inside, an engine reads every step back before it selects the
    next (today's order before the run-ahead step): its own rule, an
    armed hook on the step. ``also`` arms further chaos beside it."""
    return armed(",".join(s for s in (NEVER, also) if s))


@pytest.fixture(scope="module")
def models():
    from paddle2_tpu.models import (GPTForCausalLM, Lfm2MoeForCausalLM,
                                    gpt_tiny, lfm2_moe_tiny)
    paddle.seed(0)
    gpt = GPTForCausalLM(gpt_tiny(use_scan=False))
    lfm2 = Lfm2MoeForCausalLM(lfm2_moe_tiny())
    gpt.eval()
    lfm2.eval()
    return {"gpt": gpt, "lfm2": lfm2}


def engine_of(model, **over):
    """One decode program whatever the batch: 4 rows x 8 pages."""
    kw = dict(block_size=8, num_blocks=48, max_batch=4, max_model_len=64,
              prefill_budget_tokens=64, batch_buckets=(4,),
              page_buckets=(8,), interpret=True)
    kw.update(over)
    return ServingEngine(model, config=EngineConfig(**kw))


def prompts_of(model, lengths, seed=0, shared=0):
    rng = np.random.default_rng(seed)
    vocab = model.cfg.vocab_size
    head = rng.integers(1, vocab, shared).tolist()
    return [head + rng.integers(1, vocab, n - shared).tolist()
            for n in lengths]


def drive(engine, arrivals, max_ticks=400):
    """``arrivals``: [(tick at which it is submitted, prompt, max new)].
    Ticks the engine until every arrival is in and it is idle; returns
    ([tokens of each request], [its routed experts or None])."""
    todo = sorted(arrivals, key=lambda a: a[0])
    rids, tick = [], 0
    while todo or not engine.idle():
        while todo and todo[0][0] <= tick:
            _, prompt, max_new = todo.pop(0)
            rids.append(engine.submit(prompt, max_new))
        engine.tick(float(tick))
        tick += 1
        assert tick < max_ticks, "engine did not drain"
    assert engine._ahead is None
    for rid, (_, _, max_new) in zip(rids, sorted(arrivals,
                                                 key=lambda a: a[0])):
        assert len(engine.sequence(rid).generated) == max_new
    return ([list(engine.sequence(r).generated) for r in rids],
            [engine.routed_experts(r) for r in rids])


def assert_same(got, want):
    tokens, routed = got
    ref_tokens, ref_routed = want
    assert tokens == ref_tokens
    for a, b in zip(routed, ref_routed):
        if b is None:
            assert a is None
        else:
            np.testing.assert_array_equal(a, b)


def both(model, arrivals, also="", **over):
    """(run-ahead engine, its result, step-by-step engine, its result)
    over the same arrivals."""
    ahead = engine_of(model, **over)
    with armed(also) if also else contextlib.nullcontext():
        got = drive(ahead, arrivals)
    plain = engine_of(model, **over)
    with step_by_step(also):
        want = drive(plain, arrivals)
    assert plain.ahead_steps == 0 and plain.ahead_dropped == 0
    # what it was for the same buckets: the one program of the grid
    assert ahead.num_decode_programs == plain.num_decode_programs <= 1
    return ahead, got, plain, want


# ------------------------------------------------------------ the span files
PARENT = {
    "train.prepare": "train.step", "train.dispatch": "train.step",
    "train.rebind": "train.step",
    # a prefill's first token is read back inside a second `prefill`
    # span, in the `decode` that enqueued the step consuming it
    "admit.schedule": "admit", "prefill": ("admit", "decode"),
    "prefill.dispatch": "prefill", "prefill.readback": "prefill",
    "prefill.scatter": "prefill",
    "decode.select": "decode", "decode.build_batch": "decode",
    "decode.dispatch": "decode", "decode.readback": "decode.dispatch",
    "decode.emit": "decode", "build.cost": "build",
}
PROMPTS = ([1, 2, 3, 4, 5], [6, 7, 8])
ROUTING = DroplessExperts.COUNT_NAMES


def tiny_trainer(**cfg):
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny(**cfg))
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    step = paddle.jit.train_step(
        lambda ids, labels: model(ids, labels=labels)[-1], opt)
    ids = paddle.to_tensor(
        np.random.default_rng(0).integers(0, 128, (2, 16)).astype("int32"))
    return step, ids


def tiny_gpt_engine():
    """The span contract's GPT engine (pages of 4, the bucket ladder)."""
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    return ServingEngine(model, config=EngineConfig(
        block_size=4, num_blocks=32, max_batch=4, max_model_len=64))


def read_spans(trace_dir):
    """[(name without the prefix, start_ns, end_ns, counts)] of the
    program's spans in the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(profiler.SPAN_PREFIX):
                    out.append((e.name[len(profiler.SPAN_PREFIX):],
                                e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@contextlib.contextmanager
def trace_session(trace_dir):
    """A ``jax.profiler`` session that writes its ``.xplane.pb`` under
    ``trace_dir`` WITHOUT the Python tracer: the program's spans are
    TraceMe events, which the host tracer keeps, while the Python tracer
    records every call of JAX's own tracing and lowering — a traced
    serve of three requests took 93 s with it and 20 s without, beside
    17 s under no session (CHANGES.md, PR 44), and wrote the same spans."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def serve_traced(tmp_path_factory, engine, requests):
    """Serve ``requests`` ([(prompt, max new)]) to the end under a
    profiler session; the program's spans."""
    trace_dir = str(tmp_path_factory.mktemp("p2t_ahead"))
    with trace_session(trace_dir):
        for prompt, max_new in requests:
            engine.submit(prompt, max_new)
        now = 0.0
        while not engine.idle():
            engine.tick(now)
            now += 1.0
    return read_spans(trace_dir)


def reader(name):
    """``benchmark/layer_metrics/<name>.py`` as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def scope_in(text: str, scope: str) -> bool:
    """A scope of the program somewhere in an op's name-stack path, as
    it is or wrapped by a transformation: ``/attn/``, ``jvp(attn)``,
    ``transpose(jvp(attn))``; or at the path's end, where the scope
    holds one op that lowers to a call (``.../sample"``), which the
    trace reader's ``_SCOPE_TOKEN`` takes too."""
    return re.search(r"[/(\"]" + scope + r"[/)\"]", text) is not None


# --------------------------------------------------- shared by kernel tests
def visits_by_hand(sizes, first, held, tm):
    """(row tile, group) pairs that share rows, held groups only."""
    ends = np.cumsum(sizes)
    starts = ends - sizes
    return sum(int((ends[g] - 1) // tm - starts[g] // tm + 1)
               for g in range(first, first + held) if sizes[g])


def fragmented_setup(rng, bs, ctx_lens, H, D, num_blocks=32):
    """Pools + deliberately NON-CONTIGUOUS (shuffled) block tables, with
    finite stale garbage in every unused slot to prove masking."""
    B = len(ctx_lens)
    n_pages = max(blocks_for_tokens(c, bs) for c in ctx_lens)
    perm = rng.permutation(np.arange(1, num_blocks))
    tables = np.zeros((B, n_pages), np.int32)
    kp = (rng.normal(size=(num_blocks, bs, H, D)) * 7).astype(np.float32)
    vp = (rng.normal(size=(num_blocks, bs, H, D)) * 7).astype(np.float32)
    dense_k, dense_v = [], []
    used = 0
    for b, c in enumerate(ctx_lens):
        nb = blocks_for_tokens(c, bs)
        blks = perm[used:used + nb]
        used += nb
        tables[b, :nb] = blks
        ks = rng.normal(size=(c, H, D)).astype(np.float32)
        vs = rng.normal(size=(c, H, D)).astype(np.float32)
        dense_k.append(ks)
        dense_v.append(vs)
        for i, blk in enumerate(blks):
            lo, hi = i * bs, min(c, (i + 1) * bs)
            kp[blk, :hi - lo] = ks[lo:hi]
            vp[blk, :hi - lo] = vs[lo:hi]
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    # the pools merge a token's heads into one row: [N, bs, H*D]
    return (q, kp.reshape(num_blocks, bs, H * D),
            vp.reshape(num_blocks, bs, H * D), tables, dense_k, dense_v)
