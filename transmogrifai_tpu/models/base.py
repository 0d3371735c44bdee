"""Predictor stage bases.

Reference: core/.../stages/sparkwrappers/specific/OpPredictorWrapper.scala:70 —
every model family is an Estimator[(RealNN label, OPVector features)] ->
Prediction, producing a model whose transform emits the Prediction column
(prediction + probability_* + rawPrediction_*).

TPU design: ``fit_arrays(x, y, row_mask)`` is the whole training step — a
pure jitted function of dense arrays, so fold masks and hyperparameter grids
become vmap axes in the model selector rather than driver threads.
"""
from __future__ import annotations

import logging
from typing import Any

import numpy as np

from ..dataset import Dataset
from ..stages.base import Estimator, Model
from ..types import OPVector, Prediction, RealNN
from ..types.columns import Column, NumericColumn, PredictionColumn, VectorColumn

log = logging.getLogger(__name__)


class PredictorModel(Model):
    output_type = Prediction
    label_inputs = (0,)  # (label, features) — label slot is sanctioned

    def predict_arrays(
        self, x: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray | None, np.ndarray | None]:
        """(prediction [N], probability [N,C]|None, raw [N,C]|None)."""
        raise NotImplementedError

    def transform_columns(self, *cols: Column, num_rows: int) -> PredictionColumn:
        vec = cols[-1]
        assert isinstance(vec, VectorColumn), "predictor expects (label, features)"
        pred, prob, raw = self.predict_arrays(np.asarray(vec.values, dtype=np.float32))
        return PredictionColumn(
            Prediction,
            np.asarray(pred, dtype=np.float64),
            None if prob is None else np.asarray(prob, dtype=np.float64),
            None if raw is None else np.asarray(raw, dtype=np.float64),
        )


class PredictorEstimator(Estimator):
    """Base for model-family estimators. Subclasses implement
    ``fit_arrays(x, y, row_mask) -> PredictorModel`` and expose their
    hyperparameters as attributes + ``get_params``."""

    input_types = (RealNN, OPVector)
    output_type = Prediction
    label_inputs = (0,)  # the response is THIS stage's training target

    def extract_xy(self, dataset: Dataset) -> tuple[np.ndarray, np.ndarray]:
        label_name, vec_name = self.input_names
        label = dataset[label_name]
        vec = dataset[vec_name]
        assert isinstance(label, NumericColumn) and isinstance(vec, VectorColumn)
        return (
            np.asarray(vec.values, dtype=np.float32),
            label.values.astype(np.float32),
        )

    def fit_model(self, dataset: Dataset) -> PredictorModel:
        x, y = self.extract_xy(dataset)
        mask = np.ones(len(y), dtype=np.float32)
        return self.fit_arrays(x, y, mask)

    def fit_arrays(
        self, x: np.ndarray, y: np.ndarray, row_mask: np.ndarray
    ) -> PredictorModel:
        raise NotImplementedError

    # ---- grid support ----------------------------------------------------
    def with_params(self, **params: Any) -> "PredictorEstimator":
        """A copy of this estimator with hyperparameters overridden (used by
        the model selector's grid expansion)."""
        import copy

        c = copy.copy(self)
        from ..utils import uid as uid_util

        c.uid = uid_util.make_uid(type(self))
        c.metadata = {}
        for k, v in params.items():
            if not hasattr(c, k):
                raise AttributeError(f"{type(self).__name__} has no param {k}")
            setattr(c, k, v)
        return c


def score_sweep_lanes(models_by_fold, y, folds, evaluator, lane_outputs,
                      *, lanes: int, nbytes: int) -> list[list[float]]:
    """The host half of a family's ``sweep_eval_batched``: every fold lane
    scored from outputs its fit program computed on the device, under one
    ``selector/evaluate`` span. ``lane_outputs(fold, model, val_idx)`` is a
    lane's raw outputs on the fold's validation rows, the rows last (what
    the model's ``predictions_from_sweep`` takes); ``lanes`` and ``nbytes``
    (the outputs pulled to the host for them) go on the span. Returns
    ``[n_points][n_folds]`` metric values."""
    from ..telemetry import spans as _tspans

    values: list[list[float]] = [[] for _ in range(len(models_by_fold[0]))]
    with _tspans.span(
        "selector/evaluate", lanes=lanes, rows=len(y), bytes=nbytes,
    ) as sp:
        # predictions and metrics are timed with clock reads into
        # attributes, not child spans: the sweep's readers take the self
        # time of selector/evaluate as the whole of the scoring
        clock, predict_s = _tspans.clock, 0.0
        prob = None
        for fi, (_train_mask, val_mask) in enumerate(folds):
            t0 = clock()
            val_idx = np.nonzero(val_mask)[0]
            y_val = y[val_idx]
            predict_s += clock() - t0
            for gi, m in enumerate(models_by_fold[fi]):
                t0 = clock()
                pred, prob, _ = m.predictions_from_sweep(
                    lane_outputs(fi, m, val_idx))
                predict_s += clock() - t0
                values[gi].append(
                    evaluator.evaluate_metric(y_val, pred, prob))
        sp.attrs.update(
            classes=0 if prob is None else prob.shape[1],
            predict_seconds=predict_s,
            reads=evaluator.default_metric,
        )
    return values


def score_sweep_stacks(models_by_fold, y, folds, evaluator):
    """A family's ``sweep_eval_batched`` where its lanes' outputs stay on
    the device in the fit program's stack (``_sweep_stack``, whose
    ``lane_rows(lanes, rows)`` gathers lanes at rows): every fold lane
    scored from them, only the validation rows of a fold's lanes
    downloaded, one gather a fold and stack. None where a lane has no
    stack (points fitted one by one): the validator then scores by
    ``predict_arrays``."""
    flat = [m for fold_models in models_by_fold for m in fold_models]
    if not flat or any(
        getattr(m, "_sweep_stack", None) is None for m in flat
    ):
        return None
    try:
        fetched: dict = {}
        for fi, (_train_mask, val_mask) in enumerate(folds):
            val_idx = np.nonzero(val_mask)[0]
            by_stack: dict = {}
            for m in models_by_fold[fi]:
                by_stack.setdefault(
                    id(m._sweep_stack), (m._sweep_stack, []))[1].append(m)
            for stack, ms in by_stack.values():
                block = stack.lane_rows([m._sweep_lane for m in ms], val_idx)
                for m, lane in zip(ms, block):
                    fetched[fi, id(m)] = lane
        return score_sweep_lanes(
            models_by_fold, y, folds, evaluator,
            lambda fi, m, _val_idx: fetched[fi, id(m)],
            lanes=len(flat),
            nbytes=sum(int(a.nbytes) for a in fetched.values()),
        )
    except Exception:
        log.warning("batched sweep-eval failed; falling back", exc_info=True)
        return None


def group_grid_by_statics(points, known_keys, statics_of):
    """Group grid-point indices by their STATIC (shape-affecting) params so
    dynamic params batch as lanes of one program; points carrying unknown
    keys fall out to a sequential list. Shared by the logistic and linear
    batched-masks sweeps (the grouping logic diverging between families was
    exactly how the round-1 'statics compared against ctor defaults' bug
    hid — see LogisticRegression._static_groups history).

    ``statics_of(point) -> hashable key``; returns (groups, sequential)
    where groups maps key -> [point indices].
    """
    groups: dict[Any, list[int]] = {}
    sequential: list[int] = []
    for i, p in enumerate(points):
        if set(p) - known_keys:
            sequential.append(i)
            continue
        groups.setdefault(statics_of(p), []).append(i)
    return groups, sequential
