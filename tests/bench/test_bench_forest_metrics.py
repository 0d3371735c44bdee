"""The forest cell's work count by hand, and its two counter readers on
records written by hand."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.lib import by_name  # noqa: E402

COUNTERS = {"window": {"sweeps": 1}}


def test_forest_work_by_hand():
    work = by_name("work", "forest_work")
    # 2 lanes to depth 3 and 4 lanes to depth 5, 2 trees, 1,000 x 10:
    # levels = 2 x 3 and 2 x 5
    flops, nbytes = work.forest_fit_work(1000, 10, {3: 2, 5: 4}, 2)
    assert flops == 2.0 * 1000 * 10 * (2 * 6 + 4 * 10)
    assert nbytes == (
        6 * (1000 * 10 + 2 * 1000 * 12) + 10 * (1000 * 10 + 4 * 1000 * 12)
        + 1000 * 10 * 5)


def test_forest_sweep_work_shares_the_lanes_over_the_depths():
    work = by_name("work", "forest_work")
    with open(os.path.join(ROOT, "benchmarks", "configs", "flagship_rf.json")) as f:
        cfg = json.load(f)
    # 6 grid points x (1 split + the refit) = 12 lanes: 4 a depth
    counters = {"plane_shape": (1002701, 357), "lanes": 12}
    trees = cfg["grid"]["num_trees"][0]
    assert work.sweep_work(cfg, counters) == work.forest_fit_work(
        1002701, 357, {3: 4.0, 6: 4.0, 12: 4.0}, trees)
    flops, nbytes = work.sweep_work(cfg, counters)
    # every lane over all 357 columns: 2 x 4 x N x F x trees x (3 + 6 + 12)
    assert flops == pytest.approx(2.0 * 4 * 1002701 * 357 * trees * 21)
    from benchmarks.lib import peaks

    least, bound = peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "bytes" and 0.01 * trees < least < 0.1 * trees


def _records(first_wait: dict, second_wait: dict):
    def rec(sid, parent, name, ts, dur, **args):
        out = {"name": name, "ts": ts, "dur": dur, "tid": 1, "id": sid,
               "parent": parent, "trace": 1}
        if args:
            out["args"] = args
        return out

    return [
        rec(3, 2, "tree/await_outputs", 20.0, 25.0, **first_wait),
        rec(2, 1, "selector/validate", 2.0, 46.0),
        rec(5, 4, "tree/await_outputs", 48.5, 0.5, **second_wait),
        rec(4, 1, "selector/refit", 48.0, 1.0),
        rec(1, None, "selector/sweep", 0.0, 50.0),
    ]


@pytest.fixture
def program(monkeypatch):
    from transmogrifai_tpu.telemetry import spans

    def install(records):
        monkeypatch.setattr(spans, "snapshot_events", lambda: list(records))
        monkeypatch.setattr(
            spans, "buffer_bounds", lambda: (65536, 64), raising=False)

    return install


@pytest.mark.parametrize(
    "first,second,expected",
    [
        # every live node admitted 19 of 357 columns
        ({"subset_admitted": 19 * 400, "subset_pairs": 357 * 400},
         {"bytes": 16}, 100.0 * 19 / 357),
        # two fits in one window add up; one searched every column
        ({"subset_admitted": 19 * 100, "subset_pairs": 357 * 100},
         {"subset_admitted": 357 * 100, "subset_pairs": 357 * 100},
         100.0 * (19 + 357) / (2 * 357)),
        # a program that went back to all columns
        ({"subset_admitted": 357 * 7, "subset_pairs": 357 * 7}, {}, 100.0),
    ],
)
def test_subset_share_is_admitted_over_pairs(program, first, second, expected):
    program(_records(first, second))
    reader = by_name("layer_metrics", "forest_subset_share_pct")
    assert reader.read(None, [], COUNTERS, None) == pytest.approx(expected)


@pytest.mark.parametrize(
    "metric,first,second",
    [
        # a program from before the counters, or a boosted fit
        ("forest_subset_share_pct", {"bytes": 16}, {"bytes": 16}),
        ("forest_subset_share_pct",
         {"slots_live": 9, "slots_built": 32}, {}),
        ("forest_slot_occupancy_pct", {"bytes": 16}, {}),
    ],
)
def test_a_program_that_does_not_count_reads_nothing(
    program, metric, first, second
):
    program(_records(first, second))
    reader = by_name("layer_metrics", metric)
    assert reader.read(None, [], COUNTERS, None) is None


@pytest.mark.parametrize(
    "first,second,expected",
    [
        # a depth-12 tree whose last level needs five of eight chunks
        ({"slots_live": 1100, "slots_built": 5 * 256}, {},
         100.0 * 1100 / 1280),
        ({"slots_live": 10, "slots_built": 32},
         {"slots_live": 30, "slots_built": 48}, 50.0),
    ],
)
def test_forest_slot_occupancy_is_live_over_built(
    program, first, second, expected
):
    program(_records(first, second))
    reader = by_name("layer_metrics", "forest_slot_occupancy_pct")
    assert reader.read(None, [], COUNTERS, None) == pytest.approx(expected)


def test_no_program_spans_reads_nothing(program):
    program([])
    for metric in ("forest_subset_share_pct", "forest_slot_occupancy_pct"):
        assert by_name("layer_metrics", metric).read(
            None, [], COUNTERS, None) is None
