"""Workflow-level cross-validation — refit the label-dependent DAG per fold.

Reference: core/.../OpWorkflow.scala:403-453 (fitStages withWorkflowCV) and
FitStagesUtil.cutDAG (core/.../utils/stages/FitStagesUtil.scala:302-355):
the DAG is cut into *before* (label-independent), *during* (label-dependent
estimators feeding the selector, e.g. SanityChecker), and *after*. Selector-
level CV would fit the during-stages once on all training rows — their
statistics (correlations, drop decisions) would then leak validation rows
into candidate selection. Workflow CV re-fits the during-DAG inside each
fold instead.

Mechanics here: for each fold, fit the DAG up to the selector's inputs on
the fold-train rows only, transform the fold-validation rows through those
fitted stages, and sweep every candidate × grid point on the resulting
arrays (per-candidate failure isolation as in OpValidator.scala:318-357).
The aggregated CandidateResults are handed to the ModelSelector, which then
skips its own validator and refits the winner on the full training data.

The sweep itself is pipelined: GLM families expose ``sweep_dispatch_masks``
(models/linear.py, models/logistic.py) which *dispatches* every grid lane
as one sharded program (SweepLayout PartitionSpecs over the execution
mesh's model axis, fold-level buffer donation — parallel/sweep.py) and
returns a collector closure. The fold loop dispatches all GLM lanes first,
fits the tree families while that device work is in flight, then collects.
Failure isolation is lane-granular: a lane whose predict/eval dies drops
only its own (uid, grid-point) entry; surviving lanes keep their results.

Fault tolerance: after each completed fold the aggregated results are
stashed (module-level, keyed by selector + fold plan + label hash). When a
mid-sweep host loss unwinds this function (HostLostError, a BaseException,
sails past the candidate handlers into the workflow failover loop) the
re-entry resumes from the last completed fold — strictly less than one
fold of rework. Any other exception clears the stash.
"""
from __future__ import annotations

import hashlib
import logging
import threading
import weakref
from typing import Any, Sequence

import numpy as np

from ..compiler import stats as _cstats
from ..dataset import Dataset
from ..evaluators.base import Evaluator
from ..resilience import distributed
from ..selector.model_selector import ModelSelector
from ..selector.validators import CandidateResult, expand_grid
from ..telemetry import runlog as _runlog
from ..telemetry import spans as _tspans
from ..types.columns import NumericColumn, VectorColumn
from .fit import apply_transformations_dag, fit_and_transform_dag

log = logging.getLogger(__name__)

#: completed-fold resume stash: key -> {"fold", "per_candidate", "failed",
#: "failed_lanes", "selector" (weakref — the stash serves only the same
#: selector instance)}. Written after every fold, consumed when the workflow
#: failover loop re-enters after a host loss, dropped on normal completion
#: or non-host-loss failure. Plain threading.Lock on purpose: the traced
#: lock census (analysis/schedule.py) covers device-side ordering, and
#: this host-only stash must not widen that static surface.
_RESUME: dict[tuple, dict] = {}
_RESUME_LOCK = threading.Lock()
_RESUME_MAX = 4


def _resume_key(selector: ModelSelector, n_folds: int, y_all: np.ndarray):
    label_h = hashlib.blake2s(
        np.ascontiguousarray(y_all).tobytes()
    ).hexdigest()[:16]
    return (selector.uid, n_folds, label_h)


def _copy_results(per_candidate: dict) -> dict:
    """Deep enough a post-stash mutation can't corrupt the stash: the
    metric lists are the only thing the fold loop appends to."""
    return {
        k: CandidateResult(
            model_name=v.model_name, model_uid=v.model_uid,
            grid=v.grid, metric_values=list(v.metric_values),
        )
        for k, v in per_candidate.items()
    }


def workflow_cv_results(
    selector: ModelSelector,
    train_data: Dataset,
    prefitted: dict[str, Any] | None = None,
) -> list[CandidateResult]:
    """Run the per-fold DAG refit + candidate sweep; returns aggregated
    candidate results for the selector to consume."""
    label_feature, vector_feature = selector.input_features
    targets = [label_feature, vector_feature]

    # label per row (labels may be derived; fit a throwaway label-only DAG)
    label_data, _ = fit_and_transform_dag(
        train_data, [label_feature], prefitted=prefitted
    )
    label_col = label_data[label_feature.name]
    assert isinstance(label_col, NumericColumn)
    y_all = label_col.values.astype(np.float64)

    # pre-validation prepare, mirroring ModelSelector.fit_arrays: DataCutter
    # trims rare labels BEFORE folds so fold-train and fold-val draw from
    # the same label universe the final refit will see
    from ..prep.splitters import DataCutter

    if isinstance(selector.splitter, DataCutter):
        keep = np.nonzero(selector.splitter.prepare(y_all))[0]
        train_data = train_data.take(keep)
        y_all = y_all[keep]

    folds = selector.validator.split_masks(y_all)
    evaluator = selector.evaluator
    per_candidate: dict[tuple[str, int], CandidateResult] = {}
    failed: set[str] = set()
    failed_lanes: set[tuple[str, int]] = set()

    resume_key = _resume_key(selector, len(folds), y_all)
    with _RESUME_LOCK:
        stash = _RESUME.get(resume_key)
        # the key alone is not proof of identity: selector uids restart
        # after uid_util.reset(), so an unrelated later run over the same
        # labels can collide. The stash only ever serves the failover
        # loop re-entering with the SAME selector instance — anything
        # else is stale and must refit from fold 0.
        if stash is not None and stash["selector"]() is not selector:
            _RESUME.pop(resume_key, None)
            stash = None
    start_fold = 0
    if stash is not None:
        start_fold = stash["fold"] + 1
        per_candidate = _copy_results(stash["per_candidate"])
        failed = set(stash["failed"])
        failed_lanes = set(stash["failed_lanes"])
        log.warning(
            "workflow CV resuming at fold %d/%d from the post-fold stash "
            "(host loss re-entry)", start_fold, len(folds),
        )

    try:
        for fold_i, (train_mask, val_mask) in enumerate(folds):
            if fold_i < start_fold:
                continue  # completed before the host loss; zero rework
            _run_fold(
                selector, train_data, prefitted, targets, label_feature,
                vector_feature, evaluator, folds, fold_i, train_mask,
                val_mask, per_candidate, failed, failed_lanes,
            )
            with _RESUME_LOCK:
                _RESUME.pop(resume_key, None)  # re-insert as newest
                _RESUME[resume_key] = {
                    "fold": fold_i,
                    "per_candidate": _copy_results(per_candidate),
                    "failed": set(failed),
                    "failed_lanes": set(failed_lanes),
                    "selector": weakref.ref(selector),
                }
                while len(_RESUME) > _RESUME_MAX:
                    _RESUME.pop(next(iter(_RESUME)))
    except BaseException as e:
        # keep the stash ONLY for host loss — the failover loop re-enters
        # this function and resumes. Real errors (and KeyboardInterrupt)
        # must not leave a stale stash to poison an unrelated later run.
        if not isinstance(e, distributed.HostLostError):
            with _RESUME_LOCK:
                _RESUME.pop(resume_key, None)
        raise
    with _RESUME_LOCK:
        _RESUME.pop(resume_key, None)

    results = list(per_candidate.values())
    if not results:
        raise RuntimeError("All model candidates failed workflow-level CV")
    return results


def _run_fold(
    selector,
    train_data,
    prefitted,
    targets,
    label_feature,
    vector_feature,
    evaluator,
    folds,
    fold_i: int,
    train_mask,
    val_mask,
    per_candidate: dict,
    failed: set,
    failed_lanes: set,
) -> None:
    """One fold: DAG refit, pipelined candidate sweep, ledger pulses."""
    # fold-boundary heartbeat pulse: a silent host is declared dead
    # between folds, and HostLostError (a BaseException) sails past the
    # candidate-isolation handlers below into the workflow failover loop
    controller = distributed.active_controller()
    if controller is not None:
        controller.on_fold(fold_i)
    # run-ledger pulse: fold boundaries land in the flight recorder's
    # per-fold timings and progress/ETA stream (telemetry/runlog.py)
    recorder = _runlog.active_recorder()
    if recorder is not None:
        recorder.on_fold_start(fold_i, total=len(folds))
    # compile-plane snapshot: the fold's lane occupancy / pad waste is the
    # delta of the sweep counters across this fold (per-fold run ledger)
    sweep_before = _cstats.snapshot()
    with _tspans.span("cv/fold", fold=fold_i):
        tr_idx = np.nonzero(train_mask)[0]
        va_idx = np.nonzero(val_mask)[0]
        fold_train = train_data.take(tr_idx)
        fold_val = train_data.take(va_idx)

        # the leak-free part: every estimator up to the selector's
        # inputs is re-fit on the fold's training rows only
        fitted_t, fitted_stages = fit_and_transform_dag(
            fold_train, targets, prefitted=prefitted
        )
        transformed_v = apply_transformations_dag(
            fold_val, targets, fitted_stages
        )

        xt, yt = _arrays(fitted_t, label_feature.name, vector_feature.name)
        xv, yv = _arrays(
            transformed_v, label_feature.name, vector_feature.name
        )
        ones = np.ones(len(yt), dtype=np.float32)

        # pipelined lanes: dispatch every GLM family's sweep first (async
        # device work behind a collector closure), fit the tree families
        # on the host while those lanes are in flight, then collect
        pending: list[tuple[Any, list[dict], Any, float]] = []
        host_side: list[tuple[Any, list[dict]]] = []
        for est, grid in selector.models:
            if est.uid in failed:
                continue
            points = expand_grid(grid)
            dispatcher = getattr(est, "sweep_dispatch_masks", None)
            if dispatcher is None:
                host_side.append((est, points))
                continue
            cand_t0 = _tspans.clock()
            try:
                handle = dispatcher(xt, yt, [ones], points)
                pending.append((est, points, handle, cand_t0))
            except Exception as e:  # dispatch-level (whole family)
                _drop_family(
                    est, points, e, per_candidate, failed, recorder,
                    fold_i, cand_t0, len(yt),
                )

        for est, points in host_side:
            cand_t0 = _tspans.clock()
            try:
                with _tspans.span(
                    "cv/candidate",
                    model=type(est).__name__, points=len(points),
                ):
                    _sweep_fold(
                        est, points, xt, yt, xv, yv, evaluator,
                        per_candidate, fold_i, failed_lanes,
                    )
                if recorder is not None:
                    recorder.on_candidate(
                        type(est).__name__, len(points),
                        _tspans.clock() - cand_t0,
                        rows=len(yt), fold=fold_i,
                    )
            except Exception as e:  # candidate-level isolation
                _drop_family(
                    est, points, e, per_candidate, failed, recorder,
                    fold_i, cand_t0, len(yt),
                )

        for est, points, handle, cand_t0 in pending:
            try:
                with _tspans.span(
                    "cv/candidate",
                    model=type(est).__name__, points=len(points),
                ):
                    models = handle()[0]
                    _eval_lanes(
                        est, points, models, xv, yv, evaluator,
                        per_candidate, failed_lanes,
                    )
                if recorder is not None:
                    recorder.on_candidate(
                        type(est).__name__, len(points),
                        _tspans.clock() - cand_t0,
                        rows=len(yt), fold=fold_i,
                    )
            except Exception as e:  # collect-level (whole family)
                _drop_family(
                    est, points, e, per_candidate, failed, recorder,
                    fold_i, cand_t0, len(yt),
                )

    if recorder is not None:
        recorder.on_fold_end(
            fold_i, total=len(folds),
            rows=int(train_mask.sum() + val_mask.sum()),
            sweep=_cstats.delta(sweep_before),
        )


def _drop_family(
    est, points, e, per_candidate, failed, recorder, fold_i, cand_t0, rows
) -> None:
    """Whole-family failure: lane-granular pops of exactly this family's
    grid keys (no full-dict rebuild — the sweep map scales with
    families × points × folds)."""
    log.warning(
        "Model %s failed workflow CV: %s", type(est).__name__, e,
    )
    if recorder is not None:
        recorder.on_candidate(
            type(est).__name__, len(points),
            _tspans.clock() - cand_t0,
            rows=rows, fold=fold_i, error=str(e),
        )
    failed.add(est.uid)
    for gi in range(len(points)):
        per_candidate.pop((est.uid, gi), None)


def _arrays(data: Dataset, label_name: str, vec_name: str):
    label = data[label_name]
    vec = data[vec_name]
    assert isinstance(label, NumericColumn) and isinstance(vec, VectorColumn)
    return (
        np.asarray(vec.values, dtype=np.float32),
        label.values.astype(np.float64),
    )


def _eval_lanes(
    est,
    points: list[dict[str, Any]],
    models: Sequence,
    xv: np.ndarray,
    yv: np.ndarray,
    evaluator: Evaluator,
    per_candidate: dict,
    failed_lanes: set,
) -> None:
    """Lane-granular scoring: a lane whose predict/eval dies loses only
    its own (uid, grid-point) entry; the other lanes of the same family
    keep their results and their earlier-fold metric values."""
    for gi, model in enumerate(models):
        key = (est.uid, gi)
        if key in failed_lanes:
            continue
        try:
            pred, prob, _ = model.predict_arrays(xv)
            value = evaluator.evaluate_metric(yv, pred, prob)
        except Exception as e:  # lane-level isolation
            log.warning(
                "Lane %d (%s) of %s failed scoring: %s",
                gi, points[gi], type(est).__name__, e,
            )
            failed_lanes.add(key)
            per_candidate.pop(key, None)
            continue
        if key not in per_candidate:
            per_candidate[key] = CandidateResult(
                model_name=type(est).__name__,
                model_uid=est.uid,
                grid=points[gi],
                metric_values=[],
            )
        per_candidate[key].metric_values.append(value)


def _sweep_fold(
    est,
    points: list[dict[str, Any]],
    xt: np.ndarray,
    yt: np.ndarray,
    xv: np.ndarray,
    yv: np.ndarray,
    evaluator: Evaluator,
    per_candidate: dict,
    fold_i: int,
    failed_lanes: set | None = None,
) -> None:
    """One fold's fits for one model family. Fold vector widths can differ
    (per-fold SanityChecker drops differ) so models never cross folds."""
    ones = np.ones(len(yt), dtype=np.float32)
    batched = getattr(est, "fit_arrays_batched", None)
    if batched is not None:
        models = batched(xt, yt, ones, points)
    else:
        models = [est.with_params(**p).fit_arrays(xt, yt, ones) for p in points]
    _eval_lanes(
        est, points, models, xv, yv, evaluator, per_candidate,
        failed_lanes if failed_lanes is not None else set(),
    )
