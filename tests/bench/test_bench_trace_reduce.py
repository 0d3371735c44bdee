"""The reduction from a trace to busy time, gaps and top ops, on a small
recorded trace; and the work counts against hand counts."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.lib import by_name, peaks, roofline, trace_reduce  # noqa: E402
from benchmarks.work import tree_fit  # noqa: E402

MS = 1_000_000


@pytest.fixture(scope="module")
def recorded():
    """small_trace.textproto: one device plane (two overlapping fusions, a
    gap, a kernel), host spans window / sweep / thresholds, and an op
    outside the window."""
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "small_trace.textproto")) as f:
        profile = ProfileData.from_text_proto(f.read())
    return trace_reduce.from_profile(profile)


def test_recorded_trace_loads(recorded):
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    assert sorted(n for n, _s, _d in recorded["spans"]) == [
        "sweep", "thresholds", "window"]


def test_busy_union_merges_overlap_and_clips(recorded):
    r = trace_reduce.reduce(recorded)
    # window 10..110 ms; fusion.1 20..40, fusion.2 30..50 (overlap -> 20..50),
    # kernel 80..100, tail op 105..120 clipped to 105..110: 30 + 20 + 5 ms
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.055)


def test_idle_time_is_attributed_to_the_covering_spans(recorded):
    r = trace_reduce.reduce(recorded)
    # gaps 10..20, 50..80 and 100..105 ms, cut where a span starts or ends:
    # 10..12 under no span; 12..20, 50..55, 75..80 and 100..105 inside sweep
    # (12..108) only; 55..75 inside thresholds
    assert r["breakdown"]["idle_gaps"] == [
        ["sweep", pytest.approx(0.023)],
        ["thresholds", pytest.approx(0.020)],
        ["other", pytest.approx(0.002)],
    ]
    assert sum(r["idle_by_span_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_a_short_gap_is_the_devices_own():
    spans = [("sweep", 0, 100_000)]
    assert trace_reduce.idle_by_span(spans, 10, 20) == {"between_ops": 10}


def test_top_ops_order_and_step_prepare(recorded):
    r = trace_reduce.reduce(recorded)
    ops = r["breakdown"]["device_ops"]
    assert [n for n, _ in ops] == ["fusion.1", "fusion.2", "kernel", "tail"]
    assert ops[0][1] == pytest.approx(0.020) and ops[3][1] == pytest.approx(0.005)
    # sweep starts at 12 ms, first device op inside it at 20 ms
    assert r["step_prepare_s"] == [pytest.approx(0.008)]
    assert r["steps"] == 1


def test_no_device_plane_is_an_error_unless_rehearsal():
    data = {"devices": {}, "spans": [("window", 0, 10 * MS)]}
    with pytest.raises(ValueError):
        trace_reduce.reduce(data)
    assert trace_reduce.reduce(data, require_device=False)["busy_s"] is None


def test_tree_work_by_hand():
    # 100 rows, 4 features, 3 lanes, 2 rounds, depth 5
    flops, nbytes = tree_fit.tree_fit_work(100, 4, 3, 2, 5)
    assert flops == 2 * 3 * 100 * 4 * 10
    assert nbytes == 10 * (100 * 4 + 3 * 100 * 12) + 100 * 4 * 5


class _Ctx:
    device_kind = "TPU v5 lite"
    counters = {"plane_shape": (100, 4), "lanes": 3}

    def __init__(self, work):
        self.cfg = {"work": work, "default_grid": {"num_round": [2]},
                    "grid": {"max_depth": [5]}}


def test_roofline_takes_the_work_count_the_configuration_names():
    trace = {"busy_s": 2e-6, "steps": 2, "window_s": 1.0}
    # bytes-bound: 42,000 bytes at 819 GB/s over 1 us of busy time a sweep
    share = roofline.read(trace, [], _Ctx.counters, _Ctx("tree_fit"))
    assert share == pytest.approx(100 * (42_000 / 819e9) / 1e-6)
    assert roofline.read({"busy_s": None, "steps": 0}, [], {}, _Ctx("tree_fit")) is None


def test_a_name_with_no_file_is_a_clear_error():
    with pytest.raises(SystemExit, match="no work file for 'forest_fit'"):
        roofline.sweep_work(_Ctx("forest_fit"))
    with pytest.raises(SystemExit, match="no checks file for 'rf_winner'"):
        by_name("checks", "rf_winner")


def test_peaks_table():
    least, bound = peaks.roofline_seconds(197e12, 819e9 * 2, "TPU v5 lite")
    assert least == pytest.approx(2.0) and bound == "bytes"
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
