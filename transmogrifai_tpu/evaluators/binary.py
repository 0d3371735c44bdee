"""Binary classification evaluator.

Reference: core/.../evaluators/OpBinaryClassificationEvaluator.scala —
AuROC, AuPR, Precision, Recall, F1, Error, TP/TN/FP/FN and threshold curves.
Default selection metric: AuPR (larger better), matching
BinaryClassificationModelSelector's default.

AuROC/AuPR follow mllib's BinaryClassificationMetrics semantics: sort by
descending score, one curve point per distinct score threshold, trapezoidal
area for ROC and rectangular-interpolation area for PR.
"""
from __future__ import annotations

import numpy as np

from ..telemetry import spans as _tspans
from .base import Evaluator


def _curve_counts(y: np.ndarray, score: np.ndarray):
    """Cumulative TP/FP at each distinct descending score threshold."""
    order = np.argsort(-score, kind="stable")
    ys = y[order]
    ss = score[order]
    tp = np.cumsum(ys)
    fp = np.cumsum(1.0 - ys)
    # keep last index of each run of equal scores
    distinct = np.nonzero(np.diff(ss, append=-np.inf))[0]
    return tp[distinct], fp[distinct], ss[distinct]


def auroc(y: np.ndarray, score: np.ndarray) -> float:
    pos, neg = y.sum(), (1.0 - y).sum()
    if pos == 0 or neg == 0:
        return 0.0
    tp, fp, _ = _curve_counts(y, score)
    tpr = np.concatenate([[0.0], tp / pos, [1.0]])
    fpr = np.concatenate([[0.0], fp / neg, [1.0]])
    return float(np.trapezoid(tpr, fpr))


def aupr(y: np.ndarray, score: np.ndarray) -> float:
    pos = y.sum()
    if pos == 0:
        return 0.0
    tp, fp, _ = _curve_counts(y, score)
    precision = tp / np.maximum(tp + fp, 1e-12)
    recall = tp / pos
    # mllib prepends (0, p@first) and uses trapezoids
    recall = np.concatenate([[0.0], recall])
    precision = np.concatenate([[precision[0]], precision])
    return float(np.trapezoid(precision, recall))


def _score(pred: np.ndarray, prob: np.ndarray | None) -> np.ndarray:
    """What the areas rank rows by: the positive class's probability where
    there is one, else the prediction."""
    return prob[:, 1] if prob is not None and prob.ndim == 2 else pred


class BinaryClassificationEvaluator(Evaluator):
    default_metric = "AuPR"
    is_larger_better = True
    name = "binEval"

    def __init__(self, num_thresholds: int = 100):
        self.num_thresholds = num_thresholds

    def evaluate_arrays(self, y, pred, prob):
        # each group of metrics computed together is timed by the metric it
        # exists for; the seconds go to the caller's span (``metric_seconds``)
        clock = _tspans.clock
        t0 = clock()
        score = _score(pred, prob)
        tp = float(((pred == 1) & (y == 1)).sum())
        tn = float(((pred == 0) & (y == 0)).sum())
        fp = float(((pred == 1) & (y == 0)).sum())
        fn = float(((pred == 0) & (y == 1)).sum())
        n = max(len(y), 1)
        precision = tp / (tp + fp) if tp + fp > 0 else 0.0
        recall = tp / (tp + fn) if tp + fn > 0 else 0.0
        f1 = (
            2 * precision * recall / (precision + recall)
            if precision + recall > 0
            else 0.0
        )
        t1 = clock()
        thresholds = np.linspace(0.0, 1.0, self.num_thresholds, endpoint=False)
        curve_p, curve_r, curve_f = [], [], []
        for t in thresholds:
            p_t = (score >= t).astype(np.float64)
            tp_t = float(((p_t == 1) & (y == 1)).sum())
            fp_t = float(((p_t == 1) & (y == 0)).sum())
            fn_t = float(((p_t == 0) & (y == 1)).sum())
            pr = tp_t / (tp_t + fp_t) if tp_t + fp_t > 0 else 0.0
            rc = tp_t / (tp_t + fn_t) if tp_t + fn_t > 0 else 0.0
            curve_p.append(pr)
            curve_r.append(rc)
            curve_f.append(2 * pr * rc / (pr + rc) if pr + rc > 0 else 0.0)
        t2 = clock()
        au_roc = auroc(y, score)
        t3 = clock()
        au_pr = aupr(y, score)
        t4 = clock()
        _tspans.add_seconds("metric_seconds", {
            "Counts": t1 - t0, "ThresholdCurve": t2 - t1,
            "AuROC": t3 - t2, "AuPR": t4 - t3,
        })
        return {
            "AuROC": au_roc,
            "AuPR": au_pr,
            "Precision": precision,
            "Recall": recall,
            "F1": f1,
            "Error": (fp + fn) / n,
            "TP": tp,
            "TN": tn,
            "FP": fp,
            "FN": fn,
            "thresholds": thresholds.tolist(),
            "precisionByThreshold": curve_p,
            "recallByThreshold": curve_r,
            "f1ByThreshold": curve_f,
        }

    def evaluate_metric(self, y, pred, prob):
        # the areas a selection metric can name, each a timed group of its
        # own; looked up at the call, as evaluate_arrays does
        area = {"AuPR": aupr, "AuROC": auroc}.get(self.default_metric)
        if area is None:
            return super().evaluate_metric(y, pred, prob)
        t0 = _tspans.clock()
        value = area(y, _score(pred, prob))
        _tspans.add_seconds(
            "metric_seconds", {self.default_metric: _tspans.clock() - t0})
        return value
