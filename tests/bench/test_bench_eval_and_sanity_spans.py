"""The seven readers of the sweep's scoring and of the SanityChecker's fit,
on records written by hand: what each sums, what is read and what is not,
and none from a program without the spans or attributes."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.lib import by_name, setup_spans  # noqa: E402

SWEEP = ("sweep_eval_predict_s", "sweep_eval_read_s", "sweep_eval_unread_s")
SETUP = (
    "setup_sanity_matrix_s", "setup_sanity_stats_s",
    "setup_sanity_categorical_s", "setup_sanity_unattributed_s",
)


def _rec(sid, parent, trace, name, ts, dur, **args):
    rec = {"name": name, "ts": ts, "dur": dur, "tid": 1, "id": sid,
           "parent": parent, "trace": trace}
    if args:
        rec["args"] = args
    return rec


def _sweep(i, t0, timed=True):
    """One sweep: two fold lanes scored under the family (AuPR read), the
    refit's prediction on ``selector/refit`` and its train evaluation (all
    read). ``timed=False`` is a program from before the attributes."""
    fold = dict(lanes=2, rows=100, reads="AuPR", predict_seconds=0.5,
                metric_seconds={"AuPR": 1.0, "AuROC": 2.0, "Counts": 0.25,
                                "ThresholdCurve": 4.0})
    train = dict(lanes=1, rows=400, reads="all",
                 metric_seconds={"AuPR": 3.0, "AuROC": 3.0, "Counts": 1.0,
                                 "ThresholdCurve": 5.0})
    refit = dict(prefit=True, predict_seconds=1.5)
    if not timed:
        for args in (fold, train, refit):
            for k in ("reads", "predict_seconds", "metric_seconds"):
                args.pop(k, None)
    return [
        _rec(i + 3, i + 2, i, "selector/evaluate", t0 + 5.0, 10.0, **fold),
        _rec(i + 2, i + 1, i, "selector/family", t0 + 1.0, 15.0),
        _rec(i + 1, i, i, "selector/validate", t0 + 0.5, 16.0),
        _rec(i + 5, i + 4, i, "selector/evaluate", t0 + 19.0, 12.0, **train),
        _rec(i + 4, i, i, "selector/refit", t0 + 17.0, 15.0, **refit),
        _rec(i, None, i, "selector/sweep", t0, 33.0),
    ]


def _train(t0=0.0, parts=True, stage="SanityChecker"):
    """Set-up's one train: a SanityChecker fit of 40 s, 35 s of it under
    its five parts, and another stage's fit beside it."""
    recs = []
    if parts:
        recs += [
            _rec(13, 12, 10, "sanity/matrix", t0 + 2.0, 12.0,
                 rows_in=1000, rows=900, cols=5, bytes=36000),
            _rec(14, 12, 10, "sanity/stats", t0 + 14.0, 15.0, kind="pearson"),
            _rec(15, 12, 10, "sanity/categorical", t0 + 29.0, 7.0,
                 groups=3, cols=9),
            _rec(16, 12, 10, "sanity/rules", t0 + 36.0, 0.5),
            _rec(17, 12, 10, "sanity/report", t0 + 36.5, 0.5),
        ]
    recs += [
        _rec(12, 11, 10, "train/fit", t0 + 1.0, 40.0, stage=stage),
        _rec(18, 11, 10, "train/fit", t0 + 42.0, 3.0, stage="OpTransmogrify"),
        _rec(11, 10, 10, "train/layer", t0 + 0.5, 45.0, index=1),
        _rec(10, None, 10, "train/run", t0, 50.0),
    ]
    return recs


@pytest.fixture
def program(monkeypatch):
    """Stand-in for the program's span buffer."""
    from transmogrifai_tpu.telemetry import spans

    def install(records, bound=65536):
        monkeypatch.setattr(spans, "snapshot_events", lambda: list(records))
        monkeypatch.setattr(
            spans, "buffer_bounds", lambda: (bound, 64), raising=False)

    return install


def _read(name, counters=None):
    return by_name("layer_metrics", name).read(None, [], counters or {}, None)


WINDOW = {"window": {"sweeps": 2}}


@pytest.mark.parametrize("name, want", [
    # fold 0.5 + refit 1.5 a sweep
    ("sweep_eval_predict_s", 2.0),
    # the fold lanes' AuPR 1.0 + the train evaluation's every group 12.0
    ("sweep_eval_read_s", 13.0),
    # the fold lanes' AuROC, Counts and ThresholdCurve
    ("sweep_eval_unread_s", 6.25),
])
def test_sweep_readers_split_what_is_read(program, name, want):
    # set-up's sweep ran inside the train: not a window sweep
    cold = [dict(r, trace=10, parent=r["parent"] or 11) for r in _sweep(40, 0.0)]
    program(cold + _sweep(100, 100.0) + _sweep(200, 200.0))
    assert _read(name, WINDOW) == pytest.approx(want)


@pytest.mark.parametrize("name", SWEEP)
def test_sweep_readers_give_none_without_the_attributes(program, name):
    program(_sweep(100, 100.0, timed=False) + _sweep(200, 200.0, timed=False))
    assert _read(name, WINDOW) is None
    program(_sweep(100, 100.0) + _sweep(200, 200.0))
    assert _read(name, {}) is None, "no window sweeps counted"


def test_a_group_that_reads_another_metric_is_split_by_its_name(program):
    recs = _sweep(100, 100.0)
    recs[0]["args"]["reads"] = "F1"
    recs[0]["args"]["metric_seconds"] = {
        "F1": 0.5, "TopKAccuracy": 2.0, "ThresholdMetrics": 3.0}
    program(recs)
    one = {"window": {"sweeps": 1}}
    assert _read("sweep_eval_read_s", one) == pytest.approx(0.5 + 12.0)
    assert _read("sweep_eval_unread_s", one) == pytest.approx(5.0)


@pytest.mark.parametrize("name, want", [
    ("setup_sanity_matrix_s", 12.0),
    ("setup_sanity_stats_s", 15.0),
    ("setup_sanity_categorical_s", 7.0),
    # 40 - (12 + 15 + 7 + 0.5 + 0.5)
    ("setup_sanity_unattributed_s", 5.0),
])
def test_setup_readers_take_the_sanity_checker_fit(program, name, want):
    # the window's sweeps after the train change nothing
    program(_train() + _sweep(100, 100.0) + _sweep(200, 200.0))
    assert _read(name, WINDOW) == pytest.approx(want)


@pytest.mark.parametrize("case", ["no_parts", "no_checker", "two_trains",
                                  "no_train", "wrapped"])
@pytest.mark.parametrize("name", SETUP)
def test_setup_readers_report_a_whole_reading_or_nothing(program, case, name):
    bound = 65536
    if case == "no_parts":  # a program that does not break the fit down
        records = _train(parts=False)
    elif case == "no_checker":
        records = _train(stage="DataBalancer")
    elif case == "two_trains":
        records = _train() + [
            dict(r, id=r["id"] + 100, trace=110,
                 parent=None if r["parent"] is None else r["parent"] + 100)
            for r in _train(t0=60.0)
        ]
    elif case == "no_train":
        records = _sweep(100, 100.0)
    else:  # the buffer is full and its oldest record ended inside the train
        records = _train()[1:]
        bound = len(records)
    program(records, bound)
    assert _read(name, WINDOW) is None


def test_a_full_buffer_that_dropped_only_older_records_still_reads(program):
    older = [_rec(5, None, 5, "compile/warmup", -10.0, 5.0)]
    records = older + _train()
    program(records, bound=len(records))
    assert setup_spans.cold_train() is not None
    assert _read("setup_sanity_matrix_s") == pytest.approx(12.0)


def test_benchmark_json_names_the_seven_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for names, moves, layer in ((SWEEP, "sweep_s", "sweep driver"),
                                (SETUP, "setup_s", "host featurize")):
        for name in names:
            m = entries[name]
            assert (m["workloads"], m["moves"], m["layer"]) == (
                cells, moves, layer)
            assert (m["unit"], m["source"], m["better"]) == (
                "s", "host_clock", "lower")
            assert os.path.isfile(os.path.join(
                ROOT, "benchmarks", "layer_metrics", f"{name}.py"))
