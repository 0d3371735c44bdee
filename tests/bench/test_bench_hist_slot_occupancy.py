"""``hist_slot_occupancy_pct`` on records written by hand: the sums over
the window's ``tree/await_outputs`` spans, and nothing where the program
does not count."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.lib import by_name  # noqa: E402

COUNTERS = {"window": {"sweeps": 1}}


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
        # a depth-10 fit, every build at 256 slots: 1,023 live of 11 x 256
        ({"bytes": 16, "slots_live": 1023, "slots_built": 2816},
         {"bytes": 16}, 100.0 * 1023 / 2816),
        # the same fit on a ladder from 32
        ({"bytes": 16, "slots_live": 1023, "slots_built": 1152},
         {"bytes": 16}, 100.0 * 1023 / 1152),
        # two fits in one window add up
        ({"bytes": 16, "slots_live": 10, "slots_built": 32},
         {"bytes": 16, "slots_live": 30, "slots_built": 48}, 50.0),
    ],
)
def test_occupancy_is_live_over_built_across_the_window(
    program, first, second, expected
):
    program(_records(first, second))
    reader = by_name("layer_metrics", "hist_slot_occupancy_pct")
    assert reader.read(None, [], COUNTERS, None) == pytest.approx(expected)


@pytest.mark.parametrize(
    "first,second",
    [
        ({"bytes": 16}, {"bytes": 16}),  # a program from before the counter
        ({"bytes": 16, "slots_live": 0, "slots_built": 0}, {}),
    ],
)
def test_a_program_that_does_not_count_reads_nothing(program, first, second):
    program(_records(first, second))
    reader = by_name("layer_metrics", "hist_slot_occupancy_pct")
    assert reader.read(None, [], COUNTERS, None) is None


def test_no_spans_at_all_reads_nothing(program):
    program([])
    reader = by_name("layer_metrics", "hist_slot_occupancy_pct")
    assert reader.read(None, [], COUNTERS, None) is None
