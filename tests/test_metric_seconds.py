"""The evaluators time each group of metrics they compute together and add
the seconds to the caller's span (``metric_seconds``): the results are the
same with telemetry on and off, and the groups cover every metric an
evaluator returns exactly once."""
import numpy as np
import pytest

from transmogrifai_tpu.evaluators.binary import BinaryClassificationEvaluator
from transmogrifai_tpu.evaluators.multiclass import MultiClassificationEvaluator
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


CASES = {
    "binary_prob": (lambda: _binary(True), BINARY_GROUPS),
    "binary_pred_only": (lambda: _binary(False), BINARY_GROUPS),
    "multiclass_prob": (lambda: _multiclass(True), MULTICLASS_GROUPS),
    "multiclass_pred_only": (lambda: _multiclass(False), MULTICLASS_GROUPS),
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
