"""The multiclass forest cell's work count by hand, and its readers on
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
CELL = "flagship_rf_multiclass.fit"


def test_multiclass_forest_work_by_hand():
    work = by_name("work", "forest_multiclass_work")
    # 2 lanes to depth 3 and 4 lanes to depth 5, 2 trees, 1,000 x 10, 7
    # classes: levels = 2 x 3 and 2 x 5; 7 adds a cell, 8 words a row a lane
    flops, nbytes = work.forest_fit_work(1000, 10, {3: 2, 5: 4}, 2, 7)
    assert flops == 7.0 * 1000 * 10 * (2 * 6 + 4 * 10)
    assert nbytes == (
        6 * (1000 * 10 + 2 * 1000 * 32) + 10 * (1000 * 10 + 4 * 1000 * 32))
    # two classes: the binary forest's count less the binning it leaves out
    binary = by_name("work", "forest_work")
    f2, b2 = work.forest_fit_work(1000, 10, {3: 2, 5: 4}, 2, 2)
    f0, b0 = binary.forest_fit_work(1000, 10, {3: 2, 5: 4}, 2)
    assert f2 == f0 and b2 == b0 - 1000 * 10 * 5


def test_multiclass_sweep_work_shares_the_lanes_over_the_depths():
    work = by_name("work", "forest_multiclass_work")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "flagship_rf_multiclass.json")) as f:
        cfg = json.load(f)
    counters = {"plane_shape": (1002701, 357), "lanes": 12}
    assert work.sweep_work(cfg, counters) == work.forest_fit_work(
        1002701, 357, {3: 4.0, 6: 4.0, 12: 4.0}, 2, 7)
    flops, nbytes = work.sweep_work(cfg, counters)
    assert flops == pytest.approx(7.0 * 4 * 1002701 * 357 * 2 * 21)
    from benchmarks.lib import peaks

    least, bound = peaks.roofline_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "bytes" and 0.02 < least < 0.2


def test_the_cell_and_its_four_metrics_are_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "flagship_rf_multiclass",
                    "traffic": "resweep_multiclass", "chips": 1}
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    assert config["reduced"] == cfg["reduced"] == sorted(
        cfg["reduced_from"], key=cfg["reduced"].index)
    assert cfg["classes"] == 7 and cfg["rows"] == 1114112
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert {n: (m["source"], m["unit"], m["better"]) for n, m in mine.items()} == {
        "mc_forest_fit_roofline": ("device_trace", "%", "higher"),
        "mc_forest_slot_occupancy_pct": ("program_counter", "%", "higher"),
        "mc_stat_channel_fill_pct": ("program_counter", "%", "higher"),
        "mc_evaluate_s": ("host_clock", "s", "lower"),
    }
    assert all(m["moves"] == "sweep_s" for m in mine.values())
    for name in mine:
        assert hasattr(by_name("layer_metrics", name), "read")


def _records(fits: list, evaluate=((30.0, 4.0), (48.2, 0.5))):
    def rec(sid, parent, name, ts, dur, **args):
        out = {"name": name, "ts": ts, "dur": dur, "tid": 1, "id": sid,
               "parent": parent, "trace": 1}
        if args:
            out["args"] = args
        return out

    out = [rec(10 + i, 2, "tree/fit_dispatch", 3.0 + i, 0.5, **args)
           for i, args in enumerate(fits)]
    out += [
        rec(2, 1, "selector/validate", 2.0, 46.0),
        rec(4, 1, "selector/refit", 48.0, 1.0),
        rec(1, None, "selector/sweep", 0.0, 50.0),
    ]
    for i, ((ts, dur), parent) in enumerate(zip(evaluate, (2, 4))):
        out.append(rec(20 + i, parent, "selector/evaluate", ts, dur,
                       lanes=6, classes=7))
    # a wait inside the first evaluate span is not its self time
    if evaluate:
        out.append(rec(30, 20, "tree/await_outputs", 30.5, 1.0, bytes=8))
    return out


@pytest.fixture
def program(monkeypatch):
    from transmogrifai_tpu.telemetry import spans

    def install(records):
        monkeypatch.setattr(spans, "snapshot_events", lambda: list(records))
        monkeypatch.setattr(
            spans, "buffer_bounds", lambda: (65536, 64), raising=False)

    return install


@pytest.mark.parametrize("fits,expected", [
    # the cell's three depth programs: 7 channels in lanes for 8 at the
    # 32-slot rungs, for 16 at the depth-3 program's 8 slots
    ([{"stat_channels": 7, "stat_channels_built": 8}] * 2
     + [{"stat_channels": 7, "stat_channels_built": 16}], 100.0 * 21 / 32),
    # the binary forest: two one-variant channels in one 128-lane tile
    ([{"stat_channels": 2, "stat_channels_built": 4}], 50.0),
    # a program from before the statistic axis states neither
    ([{"lanes": 4}], None),
    ([], None),
])
def test_channel_fill_is_channels_over_those_built(program, fits, expected):
    program(_records(fits))
    reader = by_name("layer_metrics", "mc_stat_channel_fill_pct")
    got = reader.read(None, [], COUNTERS, None)
    assert got == (None if expected is None else pytest.approx(expected))


def test_evaluate_seconds_are_the_spans_self_time(program):
    program(_records([]))
    reader = by_name("layer_metrics", "mc_evaluate_s")
    # 4.0 less the 1.0 s wait inside it, and the refit's 0.5
    assert reader.read(None, [], COUNTERS, None) == pytest.approx(3.5)
    program(_records([], evaluate=()))
    assert reader.read(None, [], COUNTERS, None) is None


def test_occupancy_reader_is_bound_to_the_cell(program):
    recs = _records([])
    recs.append({"name": "tree/await_outputs", "ts": 20.0, "dur": 1.0,
                 "tid": 1, "id": 40, "parent": 2, "trace": 1,
                 "args": {"slots_live": 700, "slots_built": 7 * 128}})
    program(recs)
    reader = by_name("layer_metrics", "mc_forest_slot_occupancy_pct")
    assert reader.read(None, [], COUNTERS, None) == pytest.approx(
        100.0 * 700 / 896)
