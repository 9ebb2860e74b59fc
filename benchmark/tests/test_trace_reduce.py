"""The reduction from a trace to numbers, on the hand-built trace of
``make_tiny_trace.py`` (read its docstring for the intervals)."""

import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "tiny_trace.pbtxt")) as f:
        return tr.from_profile(ProfileData.from_text_proto(f.read()))


def test_loads_devices_spans_and_window(trace):
    assert sorted(trace.devices) == ["/device:TPU:0", "/device:TPU:1"]
    assert len(trace.devices["/device:TPU:0"]) == 6
    assert all(n.startswith("bench:") for n, _, _ in trace.host)
    assert trace.window == (1000.0, 1300.0)


def test_short_names_keep_kernel_names(trace):
    names = [e[0] for e in trace.devices["/device:TPU:0"]]
    assert names == ["while.1:while", "fusion.1:fusion",
                     "custom-call.1:flash_fwd", "all-reduce.1:all-reduce",
                     "fusion.2:fusion", "custom-call.2:paged_decode"]


def test_busy_is_the_union_not_the_sum(trace):
    busy = tr.device_busy(trace)
    # device 0: [0,100) + [120,160) + [200,230) = 170; the while's
    # children and the overlap of all-reduce.1 with fusion.2 count once
    assert busy["/device:TPU:0"] == 170.0
    assert busy["/device:TPU:1"] == 100.0
    busy_s, window_s = tr.busy_and_window_s(trace)
    assert busy_s == pytest.approx(135e-9)
    assert window_s == pytest.approx(300e-9)


def test_busy_within_spans(trace):
    within = tr.spans_named(trace, "in_flight")        # [100, 260)
    busy = tr.device_busy(trace, within=within)
    assert busy["/device:TPU:0"] == 70.0               # 40 + 30
    assert busy["/device:TPU:1"] == 0.0
    _, window_s = tr.busy_and_window_s(trace, within=within)
    assert window_s == pytest.approx(160e-9)


def test_pattern_time(trace):
    got = tr.pattern_time(trace, r"flash_fwd")
    assert got["/device:TPU:0"] == (20.0, 1)
    assert got["/device:TPU:1"] == (0.0, 0)
    assert tr.pattern_time(trace, r"paged_decode")["/device:TPU:0"] \
        == (30.0, 1)
    # the pattern reads the whole HLO line; an op that holds others
    # (the while) never counts, whatever its text says
    assert tr.pattern_time(trace, r"custom_call_target=.tpu_custom_call")[
        "/device:TPU:0"] == (50.0, 2)
    assert tr.pattern_time(trace, r"while")["/device:TPU:0"] == (0.0, 0)


def test_exposed_collective(trace):
    got = tr.exposed_collective(trace)
    # device 0: all-reduce [120,150) minus fusion.2 [140,160) = 20
    assert got["/device:TPU:0"] == 20.0
    assert got["/device:TPU:1"] == 60.0


def test_top_ops_are_self_times(trace):
    top = dict(tr.top_ops(trace, 10))
    # the while keeps only what its children do not cover: 100 - 40
    assert top["while.1:while"] == pytest.approx(60e-9 / 2)
    assert top["all-reduce.1:all-reduce"] == pytest.approx((20 + 60) / 2e9)


def test_idle_gaps_are_labelled_by_the_open_host_span(trace):
    # device 1 idles longest: [100, 300) -> middle 200 -> in_flight...
    # the INNERMOST span open at 200 ns is none of block/make_batch
    # (make_batch ends at 200), so in_flight [100,260) it is
    gaps = tr.idle_gaps(trace)
    assert gaps == [["in_flight", pytest.approx(200e-9)]]
    assert tr.span_at(trace, 1000 + 170) == "make_batch"
    assert tr.span_at(trace, 1000 + 280) == "none"


def test_interval_algebra():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]
