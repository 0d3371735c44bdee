"""``hist_derived_nodes_pct`` on records written by hand: the sums over the
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
NAME = "hist_derived_nodes_pct"


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
        # a full depth-10 tree: the root built, 511 pairs under it
        ({"bytes": 16, "nodes_built": 512, "nodes_derived": 511},
         {"bytes": 16}, 100.0 * 511 / 1023),
        # two fits in one window add up; the second is a root and one pair
        ({"bytes": 16, "nodes_built": 512, "nodes_derived": 511},
         {"bytes": 16, "nodes_built": 2, "nodes_derived": 1},
         100.0 * 512 / 1026),
        # a fit with no room for its parents' histograms counts and reads 0
        ({"bytes": 16, "nodes_built": 1023, "nodes_derived": 0},
         {"bytes": 16}, 0.0),
    ],
)
def test_share_is_derived_over_all_nodes_across_the_window(
    program, first, second, expected
):
    program(_records(first, second))
    reader = by_name("layer_metrics", NAME)
    assert reader.read(None, [], COUNTERS, None) == pytest.approx(expected)


@pytest.mark.parametrize(
    "first,second",
    [
        # a program from before the counters: the span's older attributes
        ({"bytes": 16, "slots_live": 1023, "slots_built": 1152},
         {"bytes": 16}),
        ({"bytes": 16, "nodes_built": 0, "nodes_derived": 0}, {}),
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
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "tree fit", "moves": "sweep_s",
        "workloads": [w["name"] for w in bench["workloads"][:4]],
    }
    assert entry["workloads"] == [
        "flagship_xgb.fit", "flagship_rf.fit", "flagship_gbt.fit",
        "flagship_rf_multiclass.fit",
    ]
