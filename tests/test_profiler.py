"""Profiler edge cases: scheduler state machine boundaries, multi-epoch
trace merging, summary() knobs, and RecordEvent's three-timeline
correlation (host trace + xprof annotation + flight ring)."""

import json
import os

import pytest

import paddle2_tpu as paddle
from paddle2_tpu import profiler
from paddle2_tpu.profiler import (ProfilerState, RecordEvent, SortedKeys,
                                  make_scheduler, merge_traces)
from paddle2_tpu.distributed.fault_tolerance import flight_recorder


# ------------------------------------------------------- make_scheduler
class TestMakeScheduler:
    def test_skip_first_boundary(self):
        sched = make_scheduler(closed=1, ready=1, record=2, skip_first=3)
        # steps 0..2 are skipped outright
        for s in range(3):
            assert sched(s) == ProfilerState.CLOSED
        # step 3 is cycle position 0 -> the CLOSED phase of the cycle,
        # step 4 READY, step 5 RECORD, step 6 the cycle-end return
        assert sched(3) == ProfilerState.CLOSED
        assert sched(4) == ProfilerState.READY
        assert sched(5) == ProfilerState.RECORD
        assert sched(6) == ProfilerState.RECORD_AND_RETURN

    def test_repeat_window_expiry(self):
        sched = make_scheduler(closed=1, ready=0, record=1, repeat=2,
                               skip_first=2)
        cycle = 2
        repeat_steps = 2 * cycle
        # two full cycles run after skip_first...
        states = [sched(2 + i) for i in range(repeat_steps)]
        assert states == [ProfilerState.CLOSED,
                          ProfilerState.RECORD_AND_RETURN] * 2
        # ...and the scheduler is CLOSED forever past the repeat window,
        # exactly at the boundary and far beyond it
        assert sched(2 + repeat_steps) == ProfilerState.CLOSED
        assert sched(2 + repeat_steps + 1) == ProfilerState.CLOSED
        assert sched(10_000) == ProfilerState.CLOSED

    def test_record_and_return_exactly_at_cycle_end(self):
        sched = make_scheduler(closed=2, ready=1, record=3)
        cycle = 6
        for base in (0, cycle, 5 * cycle):  # every cycle, not just the 1st
            assert sched(base + cycle - 2) == ProfilerState.RECORD
            assert sched(base + cycle - 1) == \
                ProfilerState.RECORD_AND_RETURN
            assert sched(base + cycle) == ProfilerState.CLOSED

    def test_single_step_cycle_is_always_return(self):
        sched = make_scheduler(record=1)
        for s in range(4):
            assert sched(s) == ProfilerState.RECORD_AND_RETURN


# ---------------------------------------------------------- merge_traces
def _write_trace(dir_path, worker, t0_us, spans):
    """A hand-built chrome trace whose timestamps start at ``t0_us`` —
    simulating a rank whose monotonic clock epoch differs wildly."""
    events = [{"name": n, "cat": "user", "ph": "X",
               "ts": t0_us + off, "dur": dur, "pid": 1, "tid": 1,
               "args": {}} for n, off, dur in spans]
    path = os.path.join(dir_path, f"{worker}_time_123.paddle_trace.json")
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)


class TestMergeTraces:
    def test_mixed_epoch_lane_alignment(self, tmp_path):
        # rank0's clock starts near 0, rank1's 40 YEARS later — lanes
        # must still be comparable after align (each starts at ts 0)
        _write_trace(str(tmp_path), "rank0", 5_000,
                     [("a", 0, 100), ("b", 200, 50)])
        _write_trace(str(tmp_path), "rank1", 1.26e15,
                     [("a", 0, 120), ("b", 180, 60)])
        merged = merge_traces(str(tmp_path))
        lanes = {}
        for e in merged["traceEvents"]:
            if e.get("ph") == "M" and e["name"] == "process_name":
                lanes[e["pid"]] = e["args"]["name"]
        assert sorted(lanes.values()) == ["rank0", "rank1"]
        for pid in lanes:
            ts = [e["ts"] for e in merged["traceEvents"]
                  if e.get("ph") != "M" and e["pid"] == pid]
            assert min(ts) == 0.0          # start-aligned
            assert max(ts) < 1e6           # no epoch leaked through
        assert merged["metadata"]["aligned_per_rank"] is True

    def test_no_align_keeps_offsets(self, tmp_path):
        _write_trace(str(tmp_path), "rank0", 5_000, [("a", 0, 100)])
        _write_trace(str(tmp_path), "rank1", 9_000, [("a", 0, 100)])
        merged = merge_traces(str(tmp_path), align=False)
        ts = sorted(e["ts"] for e in merged["traceEvents"]
                    if e.get("ph") != "M")
        assert ts == [5_000, 9_000]
        assert merged["metadata"]["aligned_per_rank"] is False

    def test_worker_name_without_time_suffix(self, tmp_path):
        with open(tmp_path / "oddname.paddle_trace.json", "w") as f:
            json.dump({"traceEvents": [{"name": "x", "ph": "X",
                                        "ts": 1.0, "dur": 1.0,
                                        "pid": 0, "tid": 0}]}, f)
        merged = merge_traces(str(tmp_path))
        names = [e["args"]["name"] for e in merged["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"]
        assert names == ["oddname"]

    def test_empty_dir_raises(self, tmp_path):
        with pytest.raises(ValueError):
            merge_traces(str(tmp_path))


# ------------------------------------------------------ Profiler.summary
def _profiled_spans():
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    with RecordEvent("short"):
        pass
    for _ in range(3):
        with RecordEvent("long"):
            x = paddle.ones([64, 64])
            paddle.matmul(x, x)
    prof.stop()
    return prof


class TestSummaryKnobs:
    def test_time_unit_scales_and_names_columns(self):
        prof = _profiled_spans()
        ms_rows = {r["name"]: r for r in prof.summary(time_unit="ms")}
        us_rows = {r["name"]: r for r in prof.summary(time_unit="us")}
        s_rows = {r["name"]: r for r in prof.summary(time_unit="s")}
        assert {"total_ms", "avg_ms", "max_ms"} <= set(
            ms_rows["long"])
        assert {"total_us", "avg_us", "max_us"} <= set(
            us_rows["long"])
        # us ~ 1000x ms (rounding tolerance)
        assert us_rows["long"]["total_us"] == pytest.approx(
            ms_rows["long"]["total_ms"] * 1e3, rel=0.01, abs=2.0)
        assert s_rows["long"]["total_s"] == pytest.approx(
            ms_rows["long"]["total_ms"] / 1e3, rel=0.01, abs=1e-5)

    def test_invalid_time_unit_raises(self):
        prof = _profiled_spans()
        with pytest.raises(ValueError):
            prof.summary(time_unit="fortnights")

    def test_sorted_by_avg_vs_total(self):
        prof = profiler.Profiler(timer_only=True)
        prof.start()
        prof.stop()
        # synthetic events: "many_small" dominates total, "one_big" avg
        prof._events = (
            [{"name": "many_small", "dur": 1000.0}] * 10
            + [{"name": "one_big", "dur": 4000.0}])
        by_total = prof.summary(sorted_by=SortedKeys.CPUTotal)
        by_avg = prof.summary(sorted_by=SortedKeys.CPUAvg)
        by_max = prof.summary(sorted_by=SortedKeys.CPUMax)
        assert by_total[0]["name"] == "many_small"
        assert by_avg[0]["name"] == "one_big"
        assert by_max[0]["name"] == "one_big"
        # GPUTotal aliases to total (device stream == TPU timeline)
        assert prof.summary(
            sorted_by=SortedKeys.GPUTotal)[0]["name"] == "many_small"


# ----------------------------------------------- RecordEvent correlation
class TestRecordEventCorrelation:
    def test_span_lands_in_flight_ring(self, tmp_path):
        fr = flight_recorder.enable(str(tmp_path), rank=0,
                                    install_hooks=False)
        try:
            with RecordEvent("fwd_pass"):
                pass
            kinds = [(e[2], e[3]) for e in fr.events()]
            assert ("user_span_begin", {"name": "fwd_pass"}) in kinds
            ends = [f for k, f in kinds if k == "user_span_end"]
            assert ends and ends[0]["name"] == "fwd_pass"
            assert ends[0]["dur_s"] >= 0.0
        finally:
            flight_recorder.disable()

    def test_trace_annotation_when_device_trace_active(self, tmp_path):
        """RecordEvent opens ``profiler.span``: a mark is written into
        ANY active profiler session — here a bare ``jax.profiler`` one,
        which no ``Profiler`` object knows of — as a ``p2t:`` row."""
        import jax
        jax.profiler.start_trace(str(tmp_path))
        try:
            with RecordEvent("annotated"):
                pass
            ev = RecordEvent("begin_end")
            ev.begin()
            ev.end()
        finally:
            jax.profiler.stop_trace()
        assert _program_spans(tmp_path) == ["p2t:annotated",
                                            "p2t:begin_end"]

    def test_no_annotation_when_no_device_trace(self, tmp_path):
        """...and exactly then: a mark made while no session is active
        is in no trace, a session started afterwards holds only the
        marks made inside it."""
        import jax
        with RecordEvent("before_any_session"):
            pass
        assert not os.listdir(tmp_path)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with RecordEvent("inside"):
                pass
        finally:
            jax.profiler.stop_trace()
        with RecordEvent("after_the_session"):
            pass
        assert _program_spans(tmp_path) == ["p2t:inside"]


def _program_spans(trace_dir):
    """Names of the program's spans in the trace under ``trace_dir``,
    in time order."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    events = [e for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(profiler.SPAN_PREFIX)]
    return [e.name for e in sorted(events, key=lambda e: e.start_ns)]
