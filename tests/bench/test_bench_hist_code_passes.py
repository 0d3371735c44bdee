"""``hist_code_passes_pct`` on records written by hand: the sums over the
window's ``tree/await_outputs`` spans, nothing where the program does not
count, and its entry in ``BENCHMARK.json``."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.lib import by_name  # noqa: E402

COUNTERS = {"window": {"sweeps": 1}}
NAME = "hist_code_passes_pct"


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
        # every build folds its four lanes into one pass
        ({"bytes": 16, "code_passes": 24, "lane_builds": 96},
         {"bytes": 16}, 25.0),
        # nothing folds: a pass a lane
        ({"bytes": 16, "code_passes": 96, "lane_builds": 96},
         {"bytes": 16}, 100.0),
        # two fits in one window add up
        ({"bytes": 16, "code_passes": 24, "lane_builds": 96},
         {"bytes": 16, "code_passes": 8, "lane_builds": 8},
         100.0 * 32 / 104),
    ],
)
def test_share_is_code_passes_over_lanes_built_across_the_window(
    program, first, second, expected
):
    program(_records(first, second))
    reader = by_name("layer_metrics", NAME)
    assert reader.read(None, [], COUNTERS, None) == pytest.approx(expected)


@pytest.mark.parametrize(
    "first,second",
    [
        # a program from before the counters: the span's older attributes
        ({"bytes": 16, "partition_run": 6, "partition_skipped": 42},
         {"bytes": 16}),
        ({"bytes": 16, "code_passes": 0, "lane_builds": 0}, {}),
    ],
)
def test_a_program_that_does_not_count_reads_nothing(program, first, second):
    program(_records(first, second))
    reader = by_name("layer_metrics", NAME)
    assert reader.read(None, [], COUNTERS, None) is None


def test_no_wait_in_the_window_reads_nothing(program):
    program([r for r in _records({}, {}) if r["name"] != "tree/await_outputs"])
    assert by_name("layer_metrics", NAME).read(None, [], COUNTERS, None) is None


def test_the_metric_is_declared_for_the_four_tree_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "tree fit", "moves": "sweep_s",
        "workloads": [
            "flagship_xgb.fit", "flagship_rf.fit", "flagship_gbt.fit",
            "flagship_rf_multiclass.fit",
        ],
    }
    assert bench["per_layer"][-1] == entry
