"""The gradient-boosted cell's work count by hand, and its three readers on
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


def test_gbt_sweep_work_by_hand():
    work = by_name("work", "gbt_work")
    with open(os.path.join(ROOT, "benchmarks", "configs", "flagship_gbt.json")) as f:
        cfg = json.load(f)
    # 6 grid points x (1 split + the refit) = 12 lanes, 4 a depth; a round
    # is a tree: 2 rounds x (3 + 6 + 12) levels of 4 lanes over 357 columns
    counters = {"plane_shape": (1002701, 357), "lanes": 12}
    rounds = cfg["grid"]["max_iter"][0]
    assert rounds == 2
    flops, nbytes = work.sweep_work(cfg, counters)
    n, f = 1002701, 357
    assert flops == pytest.approx(2.0 * 4 * n * f * rounds * 21)
    assert nbytes == pytest.approx(
        rounds * 21 * (n * f * 1.0 + 4 * n * 12.0) + n * f * 5.0)
    # twice the rounds, twice the builds (binning is counted once)
    more = {**cfg, "grid": {**cfg["grid"], "max_iter": [4]}}
    flops4, nbytes4 = work.sweep_work(more, counters)
    assert flops4 == pytest.approx(2 * flops)
    assert nbytes4 - nbytes == pytest.approx(nbytes - n * f * 5.0)
    from benchmarks.lib import peaks

    least, bound = peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "bytes" and 0.02 < least < 0.03


def _records(dispatches, first_wait: dict, second_wait: dict):
    def rec(sid, parent, name, ts, dur, **args):
        out = {"name": name, "ts": ts, "dur": dur, "tid": 1, "id": sid,
               "parent": parent, "trace": 1}
        if args:
            out["args"] = args
        return out

    out = [
        rec(10 + i, 2, "tree/fit_dispatch", 3.0 + i, 0.5, **args)
        for i, args in enumerate(dispatches)
    ]
    return out + [
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


@pytest.mark.parametrize("first,second,expected", [
    # max_iter 2 on 4 lanes: a round on the labels, one on a residual
    ({"boost_rounds_label": 4, "boost_rounds_residual": 4}, {"bytes": 16},
     50.0),
    # the three depth programs of a sweep add up; 20 rounds read 95
    ({"boost_rounds_label": 4, "boost_rounds_residual": 4},
     {"boost_rounds_label": 4, "boost_rounds_residual": 4}, 50.0),
    ({"boost_rounds_label": 12, "boost_rounds_residual": 228}, {}, 95.0),
    # one round: every tree is fitted to the labels
    ({"boost_rounds_label": 4, "boost_rounds_residual": 0}, {}, 0.0),
])
def test_residual_rounds_share_is_residual_over_all_rounds(
    program, first, second, expected
):
    program(_records([], first, second))
    reader = by_name("layer_metrics", "gbt_residual_rounds_pct")
    assert reader.read(None, [], COUNTERS, None) == pytest.approx(expected)


def test_slot_occupancy_is_live_over_built(program):
    program(_records([], {"slots_live": 4095, "slots_built": 4224},
                     {"slots_live": 63, "slots_built": 192}))
    reader = by_name("layer_metrics", "gbt_slot_occupancy_pct")
    assert reader.read(None, [], COUNTERS, None) == pytest.approx(
        100.0 * 4158 / 4416)


@pytest.mark.parametrize("metric,first", [
    # a program from before the counters, or one that boosts in second
    # order and counts no round: nothing, never 0 by default
    ("gbt_residual_rounds_pct", {"bytes": 16}),
    ("gbt_residual_rounds_pct", {"slots_live": 63, "slots_built": 192,
                                 "chunks_run": 3, "chunks_skipped": 0}),
    ("gbt_residual_rounds_pct",
     {"boost_rounds_label": 0, "boost_rounds_residual": 0}),
    ("gbt_slot_occupancy_pct", {"bytes": 16}),
    ("gbt_slot_occupancy_pct", {"boost_rounds_label": 4}),
])
def test_a_program_that_does_not_count_reads_nothing(program, metric, first):
    program(_records([], first, {}))
    assert by_name("layer_metrics", metric).read(
        None, [], COUNTERS, None) is None


def test_no_program_spans_reads_nothing(program):
    program([])
    for metric in ("gbt_residual_rounds_pct", "gbt_slot_occupancy_pct"):
        assert by_name("layer_metrics", metric).read(
            None, [], COUNTERS, None) is None


def test_the_roofline_reader_is_the_librarys_over_the_gbt_work(program):
    """``gbt_fit_roofline`` is ``lib/roofline.read`` under the cell's name:
    the least seconds of ``work/gbt_work.py`` over the busy seconds a sweep,
    nothing without a trace."""
    from types import SimpleNamespace

    from benchmarks.lib import peaks

    reader = by_name("layer_metrics", "gbt_fit_roofline")
    with open(os.path.join(ROOT, "benchmarks", "configs", "flagship_gbt.json")) as f:
        cfg = json.load(f)
    counters = {"plane_shape": (1002701, 357), "lanes": 12}
    ctx = SimpleNamespace(cfg=cfg, counters=counters, device_kind="TPU v5 lite")
    assert reader.read(None, [], counters, ctx) is None
    trace = {"busy_s": 46.0, "steps": 2, "window_s": 55.0}
    flops, nbytes = by_name("work", "gbt_work").sweep_work(cfg, counters)
    least, _ = peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")
    got = reader.read(trace, [], counters, ctx)
    assert got == pytest.approx(100.0 * least / 23.0) and 0 < got < 100
