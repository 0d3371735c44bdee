"""The evaluators time each group of metrics they compute together and add
the seconds to the caller's span (``metric_seconds``): the results are the
same with telemetry on and off, and the groups cover every metric an
evaluator returns exactly once. A fold lane is scored by the selection
metric alone (``evaluate_metric``): the same float as the whole record's,
from the one group that computes it, and the same selection as the whole
record gives."""
import numpy as np
import pytest

from transmogrifai_tpu.evaluators.base import Evaluator
from transmogrifai_tpu.evaluators.binary import BinaryClassificationEvaluator
from transmogrifai_tpu.evaluators.multiclass import MultiClassificationEvaluator
from transmogrifai_tpu.evaluators.regression import RegressionEvaluator
from transmogrifai_tpu.telemetry import spans as tspans

#: group -> the keys of the result it computes
BINARY_GROUPS = {
    "AuPR": ("AuPR",),
    "AuROC": ("AuROC",),
    "Counts": ("Precision", "Recall", "F1", "Error", "TP", "TN", "FP", "FN"),
    "ThresholdCurve": (
        "thresholds", "precisionByThreshold", "recallByThreshold",
        "f1ByThreshold",
    ),
}
MULTICLASS_GROUPS = {
    "F1": ("Precision", "Recall", "F1", "Error"),
    "TopKAccuracy": ("TopKAccuracy",),
    "ThresholdMetrics": ("ThresholdMetrics",),
}
REGRESSION_GROUPS = {
    "RMSE": ("RMSE",),
    "Errors": ("MSE", "R2", "MAE"),
    "SignedPercentageErrorHistogram": ("SignedPercentageErrorHistogram",),
}


@pytest.fixture(autouse=True)
def _telemetry_on():
    yield
    tspans.set_enabled(True)


def _binary(with_prob, n=3000, seed=0):
    rng = np.random.default_rng(seed)
    y = (rng.random(n) < 0.4).astype(np.float64)
    # ties among the scores, as a thin forest gives
    s = np.round(np.clip(0.3 * y + 0.7 * rng.random(n), 0, 1), 2)
    pred = (s >= 0.5).astype(np.float64)
    prob = np.stack([1.0 - s, s], axis=1) if with_prob else None
    return BinaryClassificationEvaluator(), y, pred, prob


def _multiclass(with_prob, n=3000, seed=1):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 5, n).astype(np.float64)
    prob = rng.dirichlet(np.ones(5), n)
    pred = prob.argmax(axis=1).astype(np.float64)
    return (
        MultiClassificationEvaluator(), y, pred, prob if with_prob else None
    )


def _regression(n=3000, seed=2):
    rng = np.random.default_rng(seed)
    y = rng.normal(size=n)
    return RegressionEvaluator(), y, y + 0.3 * rng.normal(size=n), None


CASES = {
    "binary_prob": (lambda: _binary(True), BINARY_GROUPS),
    "binary_pred_only": (lambda: _binary(False), BINARY_GROUPS),
    "multiclass_prob": (lambda: _multiclass(True), MULTICLASS_GROUPS),
    "multiclass_pred_only": (lambda: _multiclass(False), MULTICLASS_GROUPS),
    "regression": (_regression, REGRESSION_GROUPS),
}


def _evaluate(case):
    ev, y, pred, prob = CASES[case][0]()
    with tspans.span("selector/evaluate") as sp:
        got = ev.evaluate_arrays(y, pred, prob)
    return got, sp.attrs.get("metric_seconds")


def _same(a, b):
    """Equal key for key, floats to the last bit, nested lists and dicts
    alike."""
    if isinstance(a, dict):
        return list(a) == list(b) and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(_same(x, z) for x, z in zip(a, b))
    return type(a) is type(b) and (a == b or (a != a and b != b))


@pytest.mark.parametrize("case", sorted(CASES))
def test_results_are_the_same_with_telemetry_on_and_off(case):
    on, seconds = _evaluate(case)
    tspans.set_enabled(False)
    off, no_seconds = _evaluate(case)
    assert _same(on, off)
    assert seconds and no_seconds is None


@pytest.mark.parametrize("case", sorted(CASES))
def test_groups_cover_every_returned_key_exactly_once(case):
    got, seconds = _evaluate(case)
    groups = CASES[case][1]
    assert set(seconds) <= set(groups)
    covered = [key for g in seconds for key in groups[g]]
    assert sorted(covered) == sorted(got), "each key in one timed group"
    assert all(s >= 0.0 for s in seconds.values())


def test_calls_under_one_span_add_their_seconds():
    ev, y, pred, prob = _binary(True)
    with tspans.span("selector/evaluate") as sp:
        ev.evaluate_arrays(y, pred, prob)
        once = dict(sp.attrs["metric_seconds"])
        ev.evaluate_arrays(y, pred, prob)
    twice = sp.attrs["metric_seconds"]
    assert set(twice) == set(once)
    assert all(twice[g] >= once[g] for g in once)


def test_no_span_open_is_a_no_op():
    ev, y, pred, prob = _binary(True)
    assert tspans.current() is None
    assert "AuPR" in ev.evaluate_arrays(y, pred, prob)


#: (evaluator case, default_metric, whether the evaluator computes that
#: metric's group alone; else it falls back to the whole record)
METRIC_CASES = [
    (case, metric, metric != "F1")
    for case in ("binary_prob", "binary_pred_only")
    for metric in ("AuPR", "AuROC", "F1")
] + [
    (case, metric, metric == "F1")
    for case in ("multiclass_prob", "multiclass_pred_only")
    for metric in ("F1", "Error")
] + [("regression", "RMSE", True), ("regression", "MSE", False)]


def _metric_case(case, metric):
    ev, y, pred, prob = CASES[case][0]()
    ev.default_metric = metric
    return ev, y, pred, prob


@pytest.mark.parametrize("case,metric,alone", METRIC_CASES)
def test_selection_metric_alone_equals_the_whole_record(case, metric, alone):
    ev, y, pred, prob = _metric_case(case, metric)
    with tspans.span("selector/evaluate") as whole:
        want = ev.metric_of(ev.evaluate_arrays(y, pred, prob))
    with tspans.span("selector/evaluate") as one:
        got = ev.evaluate_metric(y, pred, prob)
    assert _same(got, want)
    # where the evaluator specialises, the span holds the metric's group
    # alone; where it falls back, every group the whole record times
    groups = set(one.attrs.get("metric_seconds") or ())
    every = set(whole.attrs.get("metric_seconds") or ())
    assert groups == ({metric} if alone else every)
    assert groups <= every


def _fold_groups(records):
    return [set(r["args"]["metric_seconds"]) for r in records
            if r["name"] == "selector/evaluate"
            and r["args"].get("reads") not in (None, "all")]


def _xgb_binary():
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector
    from transmogrifai_tpu.selector.model_selector import make_candidates
    from transmogrifai_tpu.selector.validators import TrainValidationSplit

    rng = np.random.default_rng(0)
    n = 600
    x = np.concatenate(
        [rng.normal(size=(n, 4)), rng.integers(0, 2, (n, 3))], axis=1
    ).astype(np.float32)
    y = (x[:, 0] + x[:, 4] + rng.normal(size=n) > 0.5).astype(np.float64)
    models = make_candidates("BinaryClassification", ["OpXGBoostClassifier"])
    for _est, grid in models:
        grid.update(num_round=[2], max_depth=[3])
    return BinaryClassificationModelSelector(
        seed=3, models=models, validator=TrainValidationSplit(seed=3)), x, y


def _seven_classes(n, seed):
    rng = np.random.default_rng(seed)
    x = np.asfortranarray(
        (rng.normal(size=(n, 9)) * 2 + 1).astype(np.float32))
    w = rng.normal(size=(9, 7))
    y = np.argmax(x @ w + rng.gumbel(size=(n, 7)), axis=1)
    return x, y.astype(np.float32)


def _multiclass_selector(family, grid_update, x, y):
    from transmogrifai_tpu.selector import MultiClassificationModelSelector
    from transmogrifai_tpu.selector.model_selector import make_candidates
    from transmogrifai_tpu.selector.validators import TrainValidationSplit

    models = make_candidates("MultiClassification", [family])
    for _est, grid in models:
        grid.update(grid_update)
    return MultiClassificationModelSelector(
        seed=3, models=models, validator=TrainValidationSplit(0.75, seed=3)
    ), x, y


SWEEPS = {
    "xgb_binary": (_xgb_binary, "AuPR"),
    "forest_seven_class": (lambda: _multiclass_selector(
        "OpRandomForestClassifier",
        {"num_trees": [2], "max_depth": [3, 4], "min_info_gain": [0.001],
         "min_instances_per_node": [5]},
        *_seven_classes(1400, 7)), "F1"),
    "lr_seven_class": (lambda: _multiclass_selector(
        "OpLogisticRegression", {}, *_seven_classes(2100, 11)), "F1"),
}


@pytest.mark.parametrize("sweep", sorted(SWEEPS))
def test_a_sweep_selects_the_same_with_the_whole_record(
        sweep, monkeypatch, tmp_path):
    """A sweep whose fold lanes are scored by the selection metric alone
    keeps the validation results, the winner and the train evaluation that
    scoring every fold lane's whole record gives, to the last bit."""
    from transmogrifai_tpu.models import gbdt

    # the sweeps' programs bank where this test puts them
    monkeypatch.setenv("TPTPU_COMPILE_CACHE", str(tmp_path))
    make, metric = SWEEPS[sweep]
    selector, x, y = make()
    mask = np.ones(len(y), np.float32)
    summaries, groups = [], []
    for whole in (False, True):
        with monkeypatch.context() as m:
            if whole:
                m.setattr(type(selector.evaluator), "evaluate_metric",
                          Evaluator.evaluate_metric)
            gbdt._BINNED_CACHE.clear()
            tspans.reset_for_tests()
            summaries.append(selector.fit_arrays(x, y, mask).summary)
            groups.append(_fold_groups(tspans.snapshot_events()))
    alone, every = groups
    assert alone and all(g == {metric} for g in alone)
    assert every and all(g > {metric} for g in every)
    new, old = summaries
    for key in ("validationResults", "bestModelName", "bestGrid",
                "trainEvaluation"):
        assert _same(new[key], old[key]), key
