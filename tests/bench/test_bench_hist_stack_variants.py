"""``hist_stack_variant_pct`` on records written by hand: the sums over the
window's ``tree/await_outputs`` spans, and nothing where the program does
not count."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.lib import by_name  # noqa: E402

COUNTERS = {"window": {"sweeps": 1}}
NAME = "hist_stack_variant_pct"


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
        # a first-order boosted fit: (g hi, g lo, h) of four
        ({"bytes": 16, "stack_variants": 96, "stack_variants_full": 128},
         {"bytes": 16}, 75.0),
        # nothing stated exact: every statistic a (hi, lo) pair
        ({"bytes": 16, "stack_variants": 128, "stack_variants_full": 128},
         {"bytes": 16}, 100.0),
        # a forest at lowp: one variant a statistic
        ({"bytes": 16, "stack_variants": 64, "stack_variants_full": 128},
         {"bytes": 16}, 50.0),
        # two fits in one window add up
        ({"bytes": 16, "stack_variants": 96, "stack_variants_full": 128},
         {"bytes": 16, "stack_variants": 4, "stack_variants_full": 4},
         100.0 * 100 / 132),
    ],
)
def test_share_is_variants_carried_over_the_full_pairs_across_the_window(
    program, first, second, expected
):
    program(_records(first, second))
    reader = by_name("layer_metrics", NAME)
    assert reader.read(None, [], COUNTERS, None) == pytest.approx(expected)


@pytest.mark.parametrize(
    "first,second",
    [
        # a program from before the counters: the span's older attributes
        ({"bytes": 16, "code_passes": 24, "lane_builds": 96},
         {"bytes": 16}),
        ({"bytes": 16, "stack_variants": 0, "stack_variants_full": 0}, {}),
    ],
)
def test_a_program_that_does_not_count_reads_nothing(program, first, second):
    program(_records(first, second))
    reader = by_name("layer_metrics", NAME)
    assert reader.read(None, [], COUNTERS, None) is None


def test_no_wait_in_the_window_reads_nothing(program):
    program([r for r in _records({}, {}) if r["name"] != "tree/await_outputs"])
    assert by_name("layer_metrics", NAME).read(None, [], COUNTERS, None) is None
