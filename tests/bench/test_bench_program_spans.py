"""The per-layer readers of the program's own spans, on records written by
hand: which sweeps are the window's, what a whole sum is, self time under
children that overlap, and each of the seven metrics by name."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.lib import by_name, program_spans  # noqa: E402

READERS = {
    "sweep_row_select_s": 2.0,
    # root 50 - (2 + 46 + 1) = 1; validate 46 - 45 = 1; family 45 -
    # (18 + 1 + 25 + 0.5) = 0.5
    "sweep_unattributed_s": 2.5,
    "sweep_device_wait_s": 25.5,
    "tree_bin_prepare_s": 18.0,
    "tree_thresholds_s": 15.0,
    "bin_cache_hit_pct": 0.0,
    "bin_cache_held_gib": 2.5,
}


def _rec(sid, parent, trace, name, ts, dur, **args):
    rec = {"name": name, "ts": ts, "dur": dur, "tid": 1, "id": sid,
           "parent": parent, "trace": trace}
    if args:
        rec["args"] = args
    return rec


def _sweep(first_id, t0, parent=None, trace=None, cache="miss", held=2.5):
    """One sweep's records in the order the program appends them (a span
    is recorded when it ends: children before parents)."""
    i, tr = first_id, trace or first_id
    return [
        _rec(i + 1, i, tr, "selector/row_select", t0, 2.0),
        _rec(i + 5, i + 4, tr, "tree/thresholds", t0 + 3.5, 15.0),
        _rec(i + 4, i + 3, tr, "tree/bin_prepare", t0 + 3.5, 18.0,
             cache=cache, cache_device_bytes=int(held * 2**30)),
        _rec(i + 6, i + 3, tr, "tree/fit_dispatch", t0 + 21.5, 1.0),
        _rec(i + 7, i + 3, tr, "tree/await_outputs", t0 + 22.5, 25.0),
        _rec(i + 8, i + 3, tr, "selector/evaluate", t0 + 47.5, 0.5),
        _rec(i + 3, i + 2, tr, "selector/family", t0 + 3.0, 45.0),
        _rec(i + 2, i, tr, "selector/validate", t0 + 2.0, 46.0),
        _rec(i + 10, i + 9, tr, "tree/await_outputs", t0 + 48.5, 0.5),
        _rec(i + 9, i, tr, "selector/refit", t0 + 48.0, 1.0),
        _rec(i, parent, tr, "selector/sweep", t0, 50.0),
    ]


@pytest.fixture
def program(monkeypatch):
    """Stand-in for the program's span buffer: ``program(records, bound)``
    makes the readers see ``records`` in a buffer of ``bound``."""
    from transmogrifai_tpu.telemetry import spans

    def install(records, bound=65536):
        monkeypatch.setattr(spans, "snapshot_events", lambda: list(records))
        monkeypatch.setattr(
            spans, "buffer_bounds", lambda: (bound, 64), raising=False)

    return install


def test_window_sweeps_are_the_last_roots_with_their_traces(program):
    # set-up's sweep ran inside Workflow.train(): it has a parent and the
    # train's trace id, and is no root; then two window sweeps, and a
    # warm-up thread's span of another trace in between
    cold = _sweep(10, 100.0, parent=3, trace=1)
    stray = [_rec(30, None, 30, "compile/warmup", 160.0, 1.0)]
    records = cold + _sweep(40, 200.0) + stray + _sweep(60, 260.0)
    program(records)
    got = program_spans.window_sweeps({"window": {"sweeps": 2}})
    assert [root["id"] for root, _kids in got] == [40, 60]
    for root, kids in got:
        assert len(kids) == 10
        assert {k["trace"] for k in kids} == {root["id"]}
    (last,) = program_spans.window_sweeps({"window": {"sweeps": 1}})
    assert last[0]["id"] == 60


@pytest.mark.parametrize("case", [
    "no_ids", "no_roots", "too_few_roots", "no_sweeps_counted", "wrapped",
])
def test_a_reader_reports_a_whole_sum_or_nothing(program, case):
    records = _sweep(40, 200.0) + _sweep(60, 260.0)
    counters = {"window": {"sweeps": 2}}
    bound = 65536
    if case == "no_ids":  # a program from before the spans carried ids
        records = [{k: v for k, v in r.items()
                    if k not in ("id", "parent", "trace")} for r in records]
    elif case == "no_roots":  # both sweeps ran inside a train
        records = _sweep(40, 200.0, parent=3, trace=1)
    elif case == "too_few_roots":
        counters = {"window": {"sweeps": 3}}
    elif case == "no_sweeps_counted":
        counters = {}
    else:  # the buffer is full and its oldest record ended inside sweep 1
        records = records[1:]
        bound = len(records)
    program(records, bound)
    assert program_spans.window_sweeps(counters) is None
    for name in READERS:
        assert by_name("layer_metrics", name).read(None, [], counters, None) is None


def test_a_full_buffer_that_dropped_only_older_records_still_reads(program):
    older = [_rec(5, None, 5, "train/fit", 10.0, 5.0)]
    records = older + _sweep(40, 200.0)
    program(records, bound=len(records))
    assert program_spans.window_sweeps({"window": {"sweeps": 1}}) is not None


def test_self_time_under_children_that_overlap():
    parent = _rec(1, None, 1, "selector/validate", 10.0, 20.0)
    kids = [
        _rec(2, 1, 1, "selector/family", 11.0, 8.0),    # 11..19
        _rec(3, 1, 1, "selector/family", 15.0, 10.0),   # 15..25, overlaps
        _rec(4, 1, 1, "selector/family", 28.0, 5.0),    # 28..33, clipped at 30
        _rec(5, 2, 1, "tree/fit_dispatch", 12.0, 1.0),  # a grandchild: not counted
    ]
    # covered 11..25 and 28..30 = 16 of 20
    assert program_spans.self_seconds(parent, kids) == pytest.approx(4.0)
    assert program_spans.self_seconds(kids[3], kids) == pytest.approx(1.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_reader_by_name(program, name):
    cold = _sweep(10, 100.0, parent=3, trace=1, held=1.25)
    program(cold + _sweep(40, 200.0, held=2.5) + _sweep(60, 260.0, held=2.5))
    counters = {"window": {"sweeps": 2}}
    value = by_name("layer_metrics", name).read(None, [], counters, None)
    assert value == pytest.approx(READERS[name])


def test_hit_share_counts_hits_over_all_lookups(program):
    program(_sweep(40, 200.0, cache="miss") + _sweep(60, 260.0, cache="hit"))
    read = by_name("layer_metrics", "bin_cache_hit_pct").read
    assert read(None, [], {"window": {"sweeps": 2}}, None) == pytest.approx(50.0)


def test_benchmark_json_names_the_seven_with_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert entries[name]["workloads"] == ["flagship_xgb.fit"]
        assert entries[name]["moves"] == "sweep_s"
        assert os.path.isfile(
            os.path.join(ROOT, "benchmarks", "layer_metrics", f"{name}.py"))
