"""ISSUE 13: request-lifecycle tracing + exact tail-latency attribution.

The observability tentpole for the serving fleet: per-request span
trees (``observability/tracing.py``) recorded through the shared
``reliability.flight_record`` sites, an integer-picosecond latency
decomposition whose components sum EXACTLY to each request's e2e
latency, the ``serve_doctor`` CLI that attributes the p99-p50 gap and
diffs BASE vs CAND, the SLO plane, and the histogram bucket-count
satellites. Everything runs under virtual-clock stamps — no wall
clocks in any assertion.
"""

import json
import os

import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.distributed.fault_tolerance import chaos
from paddle2_tpu.observability import metrics, tracing
from paddle2_tpu.serving import (
    EngineConfig, EngineFailoverRouter, ReliabilityConfig, SLOConfig,
    ServingEngine, SeqState, poisson_trace, simulate_router)
from paddle2_tpu.tools import perf_doctor, serve_doctor

from served import shared_programs  # noqa: F401,E402

pytestmark = [pytest.mark.filterwarnings("ignore::DeprecationWarning"),
              pytest.mark.usefixtures("shared_programs")]


@pytest.fixture(autouse=True)
def _clean_planes():
    yield
    chaos.disarm()
    tracing.disable()
    metrics.disable()


@pytest.fixture(scope="module")
def tiny_model():
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(0)
    return GPTForCausalLM(gpt_tiny(use_scan=False))


def _engine(model, **over):
    kw = dict(block_size=8, num_blocks=32, max_batch=4,
              prefill_budget_tokens=64, max_model_len=64)
    rel = over.pop("reliability", None)
    kw.update(over)
    return ServingEngine(model, config=EngineConfig(reliability=rel,
                                                    **kw))


def _prompts(model, n, size=10, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, model.cfg.vocab_size, size=size).tolist()
            for _ in range(n)]


def _trace(model, n, seed=0, rate=3000.0, gen=4):
    return poisson_trace(n, rate_per_s=rate, prompt_lens=[8, 12],
                         gen_tokens=[gen], vocab=model.cfg.vocab_size,
                         seed=seed)


def _ps_sum_identity(c):
    """The acceptance invariant, recomputed from the report's own
    integer-ps fields: ordered component sum == e2e, bitwise."""
    total = sum(c[comp[:-2] + "_ps"] for comp in tracing.COMPONENTS)
    return total == c["e2e_ps"] and all(
        c[comp[:-2] + "_ps"] >= 0 for comp in tracing.COMPONENTS)


# --------------------------------------------------- disabled-path shape
class TestDisabledPath:
    def test_disabled_hooks_are_noops(self):
        """Same shape as the metrics/flight_recorder disabled tests:
        every hook is a no-op (one module-attribute load) when off."""
        assert tracing.active() is None
        tracing.event("admit", 1.0, tid=1)            # must not raise
        tracing.serving_span({"event": "admit", "t": 1.0, "tid": 1})
        tracing.flush()

    def test_disabled_hook_is_one_attribute_load(self):
        """The off path must not allocate, format, or touch the event
        arguments — the guard is the FIRST statement. Verified
        structurally: the hook bytecode loads _ACTIVE before anything
        else, the same check the metrics plane is held to."""
        import dis
        for fn in (tracing.event, tracing.serving_span):
            ops = list(dis.get_instructions(fn))
            globals_loaded = [o.argval for o in ops
                              if o.opname == "LOAD_GLOBAL"]
            assert globals_loaded[0] == "_ACTIVE", fn

    def test_flight_record_off_planes_no_side_effects(self):
        """flight_record with both planes off: no raise, no files."""
        from paddle2_tpu.serving.reliability import flight_record
        flight_record(event="admit", req=1, tid=1, t=0.5)


# ------------------------------------------------- decomposition (unit)
def _rec(event, t, **kw):
    return {"type": "span", "event": event, "t": t, **kw}


class TestDecompose:
    def test_basic_lifecycle_sums_exact(self):
        evs = [_rec("submit", 1.0, tid=7),
               _rec("admit", 1.25, tid=7),
               _rec("prefill", 1.25, end=1.5, tid=7),
               _rec("decode_step", 1.5, dur=0.1, tids=[7]),
               _rec("decode_step", 1.7, dur=0.1, tids=[7]),
               _rec("finish", 1.8, tid=7, tokens=3)]
        dec = tracing.decompose(evs)
        c = dec[7]
        assert c["finished"] and c["exact"]
        assert _ps_sum_identity(c)
        assert c["queue_wait_s"] == pytest.approx(0.25)
        assert c["prefill_s"] == pytest.approx(0.25)
        assert c["decode_compute_s"] == pytest.approx(0.2)
        # the 1.6..1.7 gap between steps is host residual
        assert c["host_s"] == pytest.approx(0.1)
        assert c["ttft_s"] == pytest.approx(0.5)

    def test_eviction_and_failover_waits_attributed_to_cause(self):
        evs = [_rec("submit", 0.0, tid=1),
               _rec("admit", 0.1, tid=1),
               _rec("prefill", 0.1, end=0.2, tid=1),
               _rec("evict", 0.3, tid=1),
               _rec("admit", 0.5, tid=1),          # evict -> re-admit
               _rec("prefill", 0.5, end=0.7, tid=1),
               _rec("engine_failed", 0.8, tids=[1]),
               _rec("adopt", 0.9, tid=1),
               _rec("admit", 1.0, tid=1),
               _rec("prefill", 1.0, end=1.1, tid=1),
               _rec("finish", 1.1, tid=1, tokens=1)]
        c = tracing.decompose(evs)[1]
        assert c["exact"] and _ps_sum_identity(c)
        assert c["queue_wait_s"] == pytest.approx(0.1)
        assert c["eviction_stall_s"] == pytest.approx(0.2)
        # death at 0.8 -> re-admission at 1.0 (detection included)
        assert c["failover_stall_s"] == pytest.approx(0.2)
        assert c["evictions"] == 1 and c["failovers"] == 1

    def test_midflight_death_clips_doomed_prefill(self):
        """A prefill whose lane completion lies beyond the engine's
        death never materialized — its tail is clipped, TTFT moves to
        the re-prefill, and the sum still closes exactly."""
        evs = [_rec("submit", 0.0, tid=3),
               _rec("admit", 0.1, tid=3),
               _rec("prefill", 0.1, end=0.6, tid=3),   # doomed
               _rec("engine_failed", 0.3, tids=[3]),
               _rec("adopt", 0.4, tid=3),
               _rec("admit", 0.5, tid=3),
               _rec("prefill", 0.5, end=0.7, tid=3),
               _rec("finish", 0.7, tid=3, tokens=1)]
        c = tracing.decompose(evs)[3]
        assert c["exact"] and _ps_sum_identity(c)
        # 0.1..0.3 of the doomed prefill counts; 0.3..0.6 is clipped
        assert c["prefill_s"] == pytest.approx(0.4)
        assert c["failover_stall_s"] == pytest.approx(0.2)
        assert c["ttft_s"] == pytest.approx(0.7)

    def test_overlapping_bookkeeping_is_flagged_not_hidden(self):
        """A decode interval extending past finish = broken span
        bookkeeping -> exact is False (negative host), never silently
        'close enough'."""
        evs = [_rec("submit", 0.0, tid=9),
               _rec("admit", 0.0, tid=9),
               _rec("decode_step", 0.0, dur=2.0, tids=[9]),
               _rec("finish", 1.0, tid=9, tokens=1)]
        c = tracing.decompose(evs)[9]
        assert c["finished"] and not c["exact"]

    def test_dropped_decode_counts_as_retry_compute(self):
        evs = [_rec("submit", 0.0, tid=2),
               _rec("admit", 0.0, tid=2),
               _rec("prefill", 0.0, end=0.1, tid=2),
               _rec("decode_step_dropped", 0.1, dur=0.1, tids=[2],
                    chaos="drop_decode_step"),
               _rec("decode_step", 0.2, dur=0.1, tids=[2]),
               _rec("finish", 0.3, tid=2, tokens=2)]
        c = tracing.decompose(evs)[2]
        assert c["exact"] and c["retries"] == 1
        assert c["decode_compute_s"] == pytest.approx(0.2)


# --------------------------------------- property test: the PR 11 drills
@pytest.mark.parametrize("drill", ["kill", "transient", "overload",
                                   "evict"])
def test_decomposition_exact_across_chaos_drills(tiny_model, tmp_path,
                                                 drill):
    """ACCEPTANCE: every finished request of the PR 11 chaos-drill
    shapes decomposes exactly (integer-ps bitwise) — components +
    host == e2e — with the stalls landing in the right component."""
    d = str(tmp_path / drill)
    tracing.enable(d, rank=0)
    kw = dict(num_blocks=32)
    n_eng, rel, n, rate = 2, None, 10, 3000.0
    if drill == "kill":
        chaos.arm("kill_engine:3:1")
    elif drill == "transient":
        chaos.arm("drop_decode_step:2,corrupt_block_table:4")
        n_eng = 1
    elif drill == "overload":
        rel, n_eng, rate = ReliabilityConfig(max_queue_depth=4), 1, 3e5
        n = 16
    gen = 4
    if drill == "evict":
        # tight pool + long generations: running sequences must grow
        # into an exhausted free list -> LIFO eviction + re-prefill
        kw["num_blocks"] = 10
        n_eng, n, gen, rate = 1, 6, 12, 3e5
    router = EngineFailoverRouter(
        [_engine(tiny_model, reliability=rel, **kw)
         for _ in range(n_eng)],
        probe_interval_s=1e-4)
    rep = simulate_router(router, _trace(tiny_model, n, seed=31,
                                         rate=rate, gen=gen))
    chaos.disarm()
    tracing.flush()
    tracing.disable()
    dec = tracing.decompose(tracing.load_trace_dir(d))
    fin = {t: c for t, c in dec.items() if c["finished"]}
    assert len(fin) == rep.completed > 0
    assert all(c["exact"] for c in fin.values())
    assert all(_ps_sum_identity(c) for c in fin.values())
    if drill == "kill":
        assert any(c["failover_stall_s"] > 0 for c in fin.values())
    if drill == "evict":
        assert any(c["eviction_stall_s"] > 0 for c in fin.values())
    if drill == "transient":
        assert sum(c["retries"] for c in fin.values()) >= 1


def test_trace_id_survives_failover_rekey(tiny_model):
    """req_id re-keys on adoption; trace_id (the span join key) never
    changes."""
    eng, target = _engine(tiny_model), _engine(tiny_model)
    rid = eng.submit([1, 2, 3], max_new_tokens=2, trace_id=777)
    seq = eng.sequence(rid)
    assert seq.trace_id == 777
    eng.fail("test", now=1.0)
    (rec,) = eng.recover_inflight()
    new_rid = target.adopt(rec, now=2.0)
    assert rec.trace_id == 777
    assert target.sequence(new_rid) is rec


def test_tracing_is_transparent_to_the_simulation(tiny_model, tmp_path):
    """Tracing is pure recording: the traced run's tokens are
    bitwise-identical to the untraced run's."""
    tr = _trace(tiny_model, 6, seed=11)
    r_off = EngineFailoverRouter([_engine(tiny_model)],
                                 probe_interval_s=1e-4)
    rep_off = simulate_router(r_off, [dict(x) for x in tr])
    toks_off = [r_off.sequence(i).generated for i in rep_off.rids]
    tracing.enable(str(tmp_path / "on"), rank=0)
    r_on = EngineFailoverRouter([_engine(tiny_model)],
                                probe_interval_s=1e-4)
    rep_on = simulate_router(r_on, [dict(x) for x in tr])
    tracing.disable()
    toks_on = [r_on.sequence(i).generated for i in rep_on.rids]
    assert toks_on == toks_off


# ------------------------------------------------------- serve_doctor
def _write_stream(path, records):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def _synthetic_dir(tmp_path, name, queue_s):
    """Three finished requests with controllable queue wait."""
    recs = []
    for tid in range(3):
        t0 = float(tid)
        q = queue_s * (1 + tid)
        recs += [_rec("submit", t0, tid=tid),
                 _rec("admit", t0 + q, tid=tid),
                 _rec("prefill", t0 + q, end=t0 + q + 0.1, tid=tid),
                 _rec("decode_step", t0 + q + 0.1, dur=0.2, tids=[tid]),
                 _rec("finish", t0 + q + 0.3, tid=tid, tokens=2)]
    d = str(tmp_path / name)
    _write_stream(os.path.join(d, "trace_rank_0.jsonl"), recs)
    return d


class TestServeDoctor:
    def test_summary_names_tail_owner_and_exits_clean(self, tmp_path,
                                                      capsys):
        d = _synthetic_dir(tmp_path, "base", queue_s=0.5)
        rc = serve_doctor.main([d])
        out = capsys.readouterr().out
        assert rc == 0
        assert "decomposition exact on all 3" in out
        assert "TAIL" in out and "queue-wait" in out

    def test_diff_identical_streams_exactly_zero(self, tmp_path,
                                                 capsys):
        d = _synthetic_dir(tmp_path, "a", queue_s=0.5)
        rc = serve_doctor.main(["diff", d, d])
        out = capsys.readouterr().out
        assert rc == 0
        assert "+0.00%" in out and "verdict: ok" in out

    def test_diff_regression_exits_4_names_component(self, tmp_path,
                                                     capsys):
        base = _synthetic_dir(tmp_path, "b", queue_s=0.1)
        cand = _synthetic_dir(tmp_path, "c", queue_s=0.6)
        rc = serve_doctor.main(["diff", base, cand, "--threshold",
                                "10"])
        out = capsys.readouterr().out
        assert rc == serve_doctor.REGRESSION_EXIT == 4
        assert "TOP REGRESSED COMPONENT: queue-wait" in out
        assert "REGRESSION" in out

    def test_summary_flags_violations_exit_3(self, tmp_path, capsys):
        recs = [_rec("submit", 0.0, tid=0), _rec("admit", 0.0, tid=0),
                _rec("decode_step", 0.0, dur=9.0, tids=[0]),
                _rec("finish", 1.0, tid=0, tokens=1)]
        d = str(tmp_path / "bad")
        _write_stream(os.path.join(d, "trace_rank_0.jsonl"), recs)
        rc = serve_doctor.main([d])
        out = capsys.readouterr().out
        assert rc == 3
        assert "DECOMPOSITION VIOLATIONS" in out

    def test_chaos_attribution_lists_tids(self, tmp_path):
        recs = [_rec("submit", 0.0, tid=5), _rec("admit", 0.0, tid=5),
                _rec("prefill", 0.0, end=0.1, tid=5),
                _rec("decode_step_dropped", 0.1, dur=0.1, tids=[5],
                     chaos="drop_decode_step"),
                _rec("decode_step", 0.2, dur=0.1, tids=[5]),
                _rec("finish", 0.3, tid=5, tokens=2)]
        d = str(tmp_path / "ch")
        _write_stream(os.path.join(d, "trace_rank_0.jsonl"), recs)
        rep = serve_doctor.summarize(serve_doctor._load(d))
        assert rep["chaos"] == {"drop_decode_step": [5]}
        assert rep["counters"]["retries"] == 1


# ------------------------------------------------------------ SLO plane
def test_slo_ledger_good_bad_and_burn_rate(tiny_model, tmp_path):
    metrics.enable(str(tmp_path), rank=0, flush_steps=1)
    slo = SLOConfig(e2e_target_s=1e-9,       # everything misses e2e
                    availability_target=0.9)
    eng = _engine(tiny_model,
                  reliability=ReliabilityConfig(slo=slo))
    for p in _prompts(tiny_model, 2, seed=3):
        eng.submit(p, max_new_tokens=2)
    steps = 0.0
    while not eng.idle() and steps < 50:
        eng.tick(now=steps)
        steps += 1.0
    pl = metrics.active()
    assert pl.counter("serving_slo_bad_total").value() == 2
    assert pl.counter("serving_slo_checks_total").value(
        slo="e2e", verdict="bad") == 2
    # bad_frac 1.0 / budget 0.1 -> burn rate 10x
    assert pl.gauge("serving_slo_burn_rate").value() == pytest.approx(
        10.0)
    metrics.disable()


def test_slo_shed_requests_consume_error_budget(tiny_model, tmp_path):
    metrics.enable(str(tmp_path), rank=0, flush_steps=1)
    slo = SLOConfig(e2e_target_s=1e6)
    eng = _engine(tiny_model, reliability=ReliabilityConfig(
        max_queue_depth=1, slo=slo))
    p = _prompts(tiny_model, 1, seed=5)[0]
    eng.submit(p, max_new_tokens=2, priority=0)
    eng.submit(p, max_new_tokens=2, priority=5)    # sheds the first
    assert eng.scheduler.slo_bad == 1
    pl = metrics.active()
    assert pl.counter("serving_slo_bad_total").value() == 1
    metrics.disable()


# ------------------------------------------- histogram bucket satellite
class TestHistogramBuckets:
    def test_snapshot_round_trips_percentiles(self, tmp_path):
        pl = metrics.enable(str(tmp_path), rank=0, flush_steps=1)
        vals = [0.003, 0.004, 0.02, 0.04, 0.2, 0.4, 0.7, 2.0]
        for v in vals:
            pl.observe("lat_s", v)
        snap = pl.snapshot()["histograms"]["lat_s"][""]
        assert snap["count"] == len(vals)
        assert snap["buckets"][-1] is None          # +Inf -> None
        assert snap["counts"][-1] == len(vals)      # cumulative
        assert all(a <= b for a, b in zip(snap["counts"],
                                          snap["counts"][1:]))
        pl.flush()
        metrics.disable()
        lanes = perf_doctor.histogram_lanes(
            perf_doctor.load_streams(str(tmp_path)))
        h = lanes["lat_s"]
        # the estimate lands inside the bucket that owns the
        # nearest-rank p50 sample (Prometheus histogram_quantile
        # semantics — not numpy's between-sample interpolation)
        rank_p50 = sorted(vals)[-(-50 * len(vals) // 100) - 1]
        assert h["count"] == len(vals)
        lo = max((b for b in snap["buckets"][:-1] if b < rank_p50),
                 default=0.0)
        hi = min(b for b in snap["buckets"][:-1] if b >= rank_p50)
        assert lo <= h["p50"] <= hi
        assert h["p99"] >= h["p50"]

    def test_prometheus_export_has_cumulative_buckets(self, tmp_path):
        pl = metrics.enable(str(tmp_path), rank=0)
        pl.observe("lat_s", 0.004)
        pl.observe("lat_s", 3.0)
        path = pl.export_prometheus()
        text = open(path).read()
        assert 'lat_s_bucket{le="0.005"} 1' in text
        assert 'lat_s_bucket{le="+Inf"} 2' in text
        assert "lat_s_count 2" in text
        metrics.disable()

    def test_quantile_estimator_edge_cases(self):
        assert perf_doctor.hist_quantile([0.1, None], [0, 0], 50) \
            is None
        # everything in +Inf bucket -> highest finite bound
        assert perf_doctor.hist_quantile([0.1, None], [0, 5], 99) \
            == 0.1
        # exact interpolation inside one bucket
        q = perf_doctor.hist_quantile([1.0, 2.0, None], [0, 4, 4], 50)
        assert 1.0 <= q <= 2.0


# ----------------------------------------------- exports + correlation
def test_chrome_trace_export_and_flight_join(tiny_model, tmp_path):
    """The chrome export is valid trace JSON with per-request tracks,
    and the flight dump's SERVING section renders the tid/t join keys
    (satellite: flight dumps join the traces)."""
    from paddle2_tpu.distributed.fault_tolerance import flight_recorder
    from paddle2_tpu.tools import flight_doctor
    tdir = str(tmp_path / "tr")
    fdir = str(tmp_path / "fl")
    tracing.enable(tdir, rank=0)
    flight_recorder.enable(fdir, rank=0)
    try:
        eng = _engine(tiny_model)
        eng.submit(_prompts(tiny_model, 1, seed=9)[0], max_new_tokens=3,
                   trace_id=42)
        steps = 0.0
        while not eng.idle() and steps < 50:
            eng.tick(now=steps)
            steps += 1.0
        flight_recorder.dump("test_join")
        path = tracing.active().export_chrome_trace()
    finally:
        flight_recorder.disable()
        tracing.disable()
    with open(path) as f:
        tr = json.load(f)
    names = {e["name"] for e in tr["traceEvents"]}
    assert {"submit", "admit", "prefill", "decode_step",
            "finish"} <= names
    assert any(e.get("tid") == 42 and e.get("ph") == "X"
               for e in tr["traceEvents"])
    report = flight_doctor.diagnose(flight_doctor.load_dumps(fdir))
    text = flight_doctor.format_report(report, fdir)
    assert "SERVING" in text and "tid=42" in text and "t=" in text


def test_stream_records_carry_no_wall_clock(tiny_model, tmp_path):
    """Byte-stability depends on it: span records carry only the
    caller's virtual stamps, never time.time()."""
    d = str(tmp_path / "nv")
    tracing.enable(d, rank=0)
    eng = _engine(tiny_model)
    eng.submit(_prompts(tiny_model, 1, seed=13)[0], max_new_tokens=2)
    steps = 0.0
    while not eng.idle() and steps < 50:
        eng.tick(now=steps)
        steps += 1.0
    tracing.flush()
    tracing.disable()
    for rec in tracing.load_trace_dir(d):
        assert rec["t"] < 1e6            # a wall stamp would be ~2e9
