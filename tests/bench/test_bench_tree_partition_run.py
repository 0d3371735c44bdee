"""``tree_partition_run_pct`` on records written by hand: the sums over the
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
NAME = "tree_partition_run_pct"


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
        # two stumps at depth 12: 3 passes of 24 a tree
        ({"bytes": 16, "partition_run": 6, "partition_skipped": 42},
         {"bytes": 16}, 100.0 * 6 / 48),
        # two fits in one window add up; the second splits at every level
        ({"bytes": 16, "partition_run": 6, "partition_skipped": 42},
         {"bytes": 16, "partition_run": 20, "partition_skipped": 0},
         100.0 * 26 / 68),
        # trees that split to their last level skip nothing
        ({"bytes": 16, "partition_run": 48, "partition_skipped": 0},
         {"bytes": 16}, 100.0),
    ],
)
def test_share_is_passes_run_over_all_passes_across_the_window(
    program, first, second, expected
):
    program(_records(first, second))
    reader = by_name("layer_metrics", NAME)
    assert reader.read(None, [], COUNTERS, None) == pytest.approx(expected)


@pytest.mark.parametrize(
    "first,second",
    [
        # a program from before the counters: the span's older attributes
        ({"bytes": 16, "nodes_built": 512, "nodes_derived": 511},
         {"bytes": 16}),
        ({"bytes": 16, "partition_run": 0, "partition_skipped": 0}, {}),
    ],
)
def test_a_program_that_does_not_count_reads_nothing(program, first, second):
    program(_records(first, second))
    reader = by_name("layer_metrics", NAME)
    assert reader.read(None, [], COUNTERS, None) is None


def test_no_wait_in_the_window_reads_nothing(program):
    program([r for r in _records({}, {}) if r["name"] != "tree/await_outputs"])
    assert by_name("layer_metrics", NAME).read(None, [], COUNTERS, None) is None


def test_the_metric_is_declared_for_all_four_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "tree fit", "moves": "sweep_s",
        "workloads": [w["name"] for w in bench["workloads"][:4]],
    }
    assert entry["workloads"] == [
        "flagship_xgb.fit", "flagship_rf.fit", "flagship_gbt.fit",
        "flagship_rf_multiclass.fit",
    ]
