"""Validators: k-fold cross validation and train/validation split.

Reference: core/.../stages/impl/tuning/{OpCrossValidation,OpTrainValidationSplit,
OpValidator}.scala. Defaults (OpValidator.scala:371-379): 3 folds, train ratio
0.75, candidate-fit parallelism 8, per-candidate failure tolerance (a failed
model/grid is logged and skipped; error only if ALL fail).

TPU mapping (SURVEY.md §2.6): folds are row masks and hyperparameter grids are
stacked arrays. The primary model-family hook is
``fit_arrays_batched_masks(x, y, masks, points)`` — the whole folds × grid
sweep trains batched over the fit axis of one compiled program per
static-shape group; ``fit_arrays_batched`` (one mask, many points) is the
legacy fallback, and families with neither hook fit sequentially.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
from typing import Any, Sequence

import numpy as np

from ..evaluators.base import Evaluator
from ..models.base import PredictorEstimator, PredictorModel
from ..resilience import faults
from ..resilience.retry import RetryPolicy
from ..telemetry import runlog as _runlog
from ..telemetry import spans as _tspans

log = logging.getLogger(__name__)


@dataclasses.dataclass
class CandidateResult:
    model_name: str
    model_uid: str
    grid: dict[str, Any]
    metric_values: list[float]

    @property
    def metric_mean(self) -> float:
        return float(np.mean(self.metric_values)) if self.metric_values else float("nan")

    def to_json(self) -> dict[str, Any]:
        return {
            "modelName": self.model_name,
            "modelUID": self.model_uid,
            "grid": {k: v for k, v in self.grid.items()},
            "metricValues": self.metric_values,
            "metricMean": self.metric_mean,
        }


def expand_grid(grid: dict[str, Sequence[Any]]) -> list[dict[str, Any]]:
    """Cartesian product of param value lists (ParamGridBuilder.build)."""
    points: list[dict[str, Any]] = [{}]
    for key, values in grid.items():
        points = [{**p, key: v} for p in points for v in values]
    return points


def _data_fingerprint(x: np.ndarray, y: np.ndarray) -> str:
    """Cheap content fingerprint of the sweep's training arrays, so a CV
    checkpoint recorded against one dataset can never answer for another.
    Bounded sampling via resilience.checkpoint.update_array_sample — never
    a full-array scan/copy for big data."""
    from ..resilience.checkpoint import update_array_sample

    h = hashlib.sha256()
    for a in (x, y):
        update_array_sample(h, a)
    return h.hexdigest()[:16]


def _folds_fingerprint(
    folds: Sequence[tuple[np.ndarray, np.ndarray]]
) -> str:
    """Fingerprint of the actual fold masks — covers every split-shaping
    knob (validator class, seed, num_folds, stratify, train ratio) at once,
    so checkpointed fold metrics can never answer for a differently-split
    resume."""
    h = hashlib.sha256()
    for train_mask, val_mask in folds:
        h.update(np.packbits(np.asarray(train_mask, dtype=bool)).tobytes())
        h.update(np.packbits(np.asarray(val_mask, dtype=bool)).tobytes())
    return h.hexdigest()[:16]


def _candidate_key(
    index: int,
    est: PredictorEstimator,
    points: list[dict[str, Any]],
    folds_fp: str,
    evaluator: Evaluator,
    data_fp: str,
) -> str:
    """Stable checkpoint key for one candidate family's sweep: the family
    class + position + a hash of (grid points, fold masks, metric, data
    fingerprint). Uids are process-local, so they stay out of the key on
    purpose — a resumed process regenerates them but the sweep identity is
    unchanged."""
    blob = json.dumps(
        {
            "model": type(est).__name__,
            "points": points,
            "folds": folds_fp,
            "metric": evaluator.default_metric,
            "data": data_fp,
        },
        sort_keys=True,
        default=str,
    )
    digest = hashlib.sha256(blob.encode()).hexdigest()[:12]
    return f"{type(est).__name__}-{index}-{digest}"


class Validator:
    """Shared candidate-sweep logic; subclasses provide the fold masks."""

    #: retry policy for candidate sweeps: transient failures (preempted
    #: device, torn I/O) back off and retry BEFORE the candidate-exclusion
    #: path; fatal errors (bad grid, shape mismatch) exclude immediately
    retry_policy: RetryPolicy = RetryPolicy(max_attempts=3, base_delay=0.25,
                                            max_delay=2.0)

    def __init__(self, seed: int = 42):
        self.seed = seed
        #: family_uid -> (points, models[extra_mask_i][point_i]) from the
        #: last validate(extra_masks=...) call — pre-fitted refit lanes
        self.last_extra_models: dict[str, tuple[list, list]] = {}
        #: per-candidate attempt accounting from the last validate() call:
        #: [{modelName, modelUID, attempts, error, excluded, fromCheckpoint}]
        self.last_attempt_info: list[dict[str, Any]] = []

    def split_masks(self, y: np.ndarray) -> list[tuple[np.ndarray, np.ndarray]]:
        raise NotImplementedError

    #: candidate-fit parallelism (OpValidator.scala:371-379 default 8).
    #: Families sweep in a thread pool: device executions serialize on the
    #: chip anyway, but
    #: each family's program acquisition (tracing + XLA compile-cache
    #: round-trips, the actual wall-clock cost) overlaps across threads.
    parallelism: int = 8

    def validate(
        self,
        candidates: Sequence[tuple[PredictorEstimator, dict[str, Sequence[Any]]]],
        x: np.ndarray,
        y: np.ndarray,
        evaluator: Evaluator,
        extra_masks: Sequence[np.ndarray] = (),
        checkpoint=None,
        resume: bool = False,
    ) -> list[CandidateResult]:
        """Fit every model family x grid point on every fold; returns results
        with per-fold metric values. Failed families are skipped
        (OpValidator.scala:318-357); raises only if everything failed.

        ``extra_masks`` ride the SAME batched program as the folds as
        additional fit lanes that contribute no metrics — the selector
        passes the post-balancing full-train mask here so the winner's
        refit is already fitted when validation returns (no separate K=1
        refit program to acquire/execute). Results land in
        ``self.last_extra_models[family_uid] = (points, models)`` with
        ``models[mask_i][point_i]``; families without the batched-masks
        hook are omitted (the selector falls back to a direct refit).

        ``checkpoint`` (a resilience.CheckpointManager) persists each
        finished family's fold metrics; with ``resume=True`` a matching
        entry (same grid/fold-masks/metric AND data fingerprint) is
        consumed so only unfinished candidates re-run — a fresh train
        always re-sweeps. A checkpoint hit skips the family's sweep
        entirely, so its ``extra_masks`` refit lanes stay empty and the
        selector pays one direct refit of the winner (metrics are the
        expensive part; that trade is deliberate). Transient per-candidate
        failures retry under ``self.retry_policy`` before the exclusion
        path, and attempt counts land in ``self.last_attempt_info``."""
        from concurrent.futures import ThreadPoolExecutor

        folds = self.split_masks(y)
        data_fp = _data_fingerprint(x, y) if checkpoint is not None else ""
        folds_fp = _folds_fingerprint(folds) if checkpoint is not None else ""
        results: list[CandidateResult] = []
        errors: list[str] = []
        self.last_extra_models: dict[str, tuple[list, list]] = {}
        self.last_attempt_info = []

        # grids expand ONCE, defensively: a malformed grid must stay a
        # per-candidate failure (caught at f.result() below), never abort
        # the sweep from the submission loop or the ordering sort
        points_list: list = []
        for _, grid in candidates:
            try:
                points_list.append(expand_grid(grid))
            except Exception as e:
                points_list.append(e)

        # the pool's threads start with an empty span stack: the span that
        # is open here (the selector's) is handed to them as the parent
        caller = _tspans.current()

        def run(i, est, points):
            """One candidate under its ``selector/family`` span."""
            if isinstance(points, Exception):
                raise points
            with _tspans.span(
                "selector/family", parent=caller,
                family=type(est).__name__, points=len(points),
            ) as sp:
                out, attempts, from_ckpt = sweep_one(i, est, points)
                sp.attrs["attempts"] = attempts
            return out, attempts, from_ckpt

        def sweep_one(i, est, points):
            """One candidate: checkpoint hit, or retried sweep + save.
            Returns (CandidateResults, attempts, from_checkpoint)."""
            # run-ledger pulse (telemetry/runlog.py): one timing per
            # candidate family sweep — the fold axis is batched into the
            # program, so the family IS the timing unit here (workflow CV
            # pulses per fold instead). RunRecorder is thread-safe: these
            # fire from the candidate pool's worker threads.
            recorder = _runlog.active_recorder()
            cand_t0 = _tspans.clock() if recorder is not None else 0.0
            key = None
            if checkpoint is not None:
                key = _candidate_key(
                    i, est, points, folds_fp, evaluator, data_fp
                )
            if key is not None and resume:
                cached = checkpoint.load_candidate(key)
                if cached is not None and len(
                    cached.get("metricValues", [])
                ) == len(points):
                    out = [
                        CandidateResult(
                            model_name=type(est).__name__,
                            model_uid=est.uid,
                            grid=points[gi],
                            metric_values=list(cached["metricValues"][gi]),
                        )
                        for gi in range(len(points))
                    ]
                    log.info(
                        "CV checkpoint hit: %s (%d points)", key, len(points)
                    )
                    return out, int(cached.get("attempts", 1)), True
            out, attempts = self.retry_policy.call(
                lambda: self._sweep_family(
                    est, points, folds, x, y, evaluator,
                    extra_masks=extra_masks,
                )
            )
            if recorder is not None:
                recorder.on_candidate(
                    type(est).__name__, len(points),
                    _tspans.clock() - cand_t0, rows=len(y),
                )
            if key is not None:
                checkpoint.save_candidate(
                    key,
                    {
                        "modelName": type(est).__name__,
                        "metricValues": [r.metric_values for r in out],
                        "attempts": attempts,
                    },
                )
            return out, attempts, False

        import jax

        # Candidate families overlap on a thread pool (program acquisition
        # is the wall-clock cost; device execs serialize on-chip anyway).
        # The ONE broken combination is threads × multi-device XLA:CPU:
        # concurrent multi-device dispatch intermittently aborts its async
        # runtime. Gate on that backend —
        # a real multi-chip TPU mesh keeps the overlap (round-2 VERDICT
        # item 6: the old device-count gate would serialize acquisition
        # exactly where it costs the most).
        if jax.default_backend() == "cpu" and len(jax.devices()) > 1:
            n_workers = 1
        else:
            n_workers = max(1, min(self.parallelism, len(candidates)))
        # longest grid first: the biggest family's dispatch chain heads the
        # single-device queue, so its uploads don't wait behind a shorter
        # family's executing program (the RF sweep's first dispatch was
        # measured blocking ~3.4 s behind the XGB chunk when submitted
        # later)
        order = sorted(
            range(len(candidates)),
            key=lambda i: -(
                len(points_list[i]) if isinstance(points_list[i], list)
                else 0
            ),
        )
        with ThreadPoolExecutor(max_workers=n_workers) as pool:
            futs_by_cand = {}
            for i in order:
                est, _ = candidates[i]
                futs_by_cand[i] = pool.submit(run, i, est, points_list[i])
            outs = []
            for i in range(len(candidates)):
                try:
                    outs.append(futs_by_cand[i].result())
                except Exception as e:
                    outs.append(e)
        for (est, _), out in zip(candidates, outs):
            name = type(est).__name__
            if isinstance(out, Exception):  # candidate-level isolation
                attempts = getattr(out, "_retry_attempts", 1)
                log.warning(
                    "Model %s failed validation after %d attempt(s): %s",
                    name, attempts, out,
                )
                errors.append(f"{name}: {out}")
                self.last_attempt_info.append({
                    "modelName": name,
                    "modelUID": est.uid,
                    "attempts": attempts,
                    "error": str(out),
                    "excluded": True,
                    "fromCheckpoint": False,
                })
            else:
                cand_results, attempts, from_ckpt = out
                results.extend(cand_results)
                self.last_attempt_info.append({
                    "modelName": name,
                    "modelUID": est.uid,
                    "attempts": attempts,
                    "error": None,
                    "excluded": False,
                    "fromCheckpoint": from_ckpt,
                })
        if not results:
            raise RuntimeError(
                f"All model candidates failed validation: {errors}"
            )
        return results

    def _sweep_family(
        self,
        est: PredictorEstimator,
        points: list[dict[str, Any]],
        folds: list[tuple[np.ndarray, np.ndarray]],
        x: np.ndarray,
        y: np.ndarray,
        evaluator: Evaluator,
        extra_masks: Sequence[np.ndarray] = (),
    ) -> list[CandidateResult]:
        plan = faults.active()
        if plan is not None:
            # inside the retried region: each retry attempt re-consults the
            # plan, so "fails twice then succeeds" scripts exactly
            plan.on_candidate_fit(est)
        per_point_values: list[list[float]] = [[] for _ in points]
        batched_masks = getattr(est, "sweep_dispatch_masks", None)
        if batched_masks is not None:
            # dispatch + collect: validator-level sweeps have no other
            # host work to overlap, so the collector runs immediately —
            # but GLM lanes still go through the one sharded/bucketed
            # program the dispatcher builds (SweepLayout, donation)
            dispatcher = batched_masks
            batched_masks = lambda *a: dispatcher(*a)()  # noqa: E731
        else:
            batched_masks = getattr(est, "fit_arrays_batched_masks", None)
        if batched_masks is not None:
            # the whole folds × grid sweep in as few compiled programs as
            # the family's static shapes allow (fold = batch-axis entry);
            # extra_masks (e.g. the refit mask) are additional lanes of the
            # same program — they produce models but no metrics
            all_masks = [tm.astype(np.float32) for tm, _ in folds] + [
                np.asarray(m, dtype=np.float32) for m in extra_masks
            ]
            models_by_fold = batched_masks(x, y, all_masks, points)
            if extra_masks:
                self.last_extra_models[est.uid] = (
                    points, models_by_fold[len(folds):]
                )
                models_by_fold = models_by_fold[: len(folds)]
            # family-managed batched validation: one device program per
            # fitted stack instead of a predict dispatch per model
            sweep_eval = getattr(est, "sweep_eval_batched", None)
            if sweep_eval is not None:
                vals = sweep_eval(models_by_fold, x, y, folds, evaluator)
                if vals is not None:
                    per_point_values = vals
                    models_by_fold = None  # skip the per-model loop below
                    folds = []
        else:
            models_by_fold = None
        for fi, (train_mask, val_mask) in enumerate(folds):
            if models_by_fold is not None:
                models = models_by_fold[fi]
            else:
                batched = getattr(est, "fit_arrays_batched", None)
                if batched is not None:
                    models = batched(x, y, train_mask.astype(np.float32), points)
                else:
                    models = [
                        est.with_params(**p).fit_arrays(
                            x, y, train_mask.astype(np.float32)
                        )
                        for p in points
                    ]
            val_idx = np.nonzero(val_mask)[0]
            with _tspans.span(
                "selector/evaluate", lanes=len(models), rows=len(val_idx),
                reads=evaluator.default_metric,
            ) as sp:
                predict_s = 0.0
                for gi, model in enumerate(models):
                    # lane-granular isolation: one lane's scoring failure
                    # poisons only its own grid point (NaN metric —
                    # ``best`` filters non-finite means), not the whole
                    # family. Fit failures above still propagate: the retry
                    # machinery scripts those at the candidate level.
                    try:
                        t0 = _tspans.clock()
                        pred, prob, _ = model.predict_arrays(x[val_idx])
                        predict_s += _tspans.clock() - t0
                        value = evaluator.evaluate_metric(
                            y[val_idx], pred, prob
                        )
                    except Exception as e:  # lane-level isolation
                        log.warning(
                            "Lane %d (%s) of %s failed scoring in fold %d: "
                            "%s", gi, points[gi], type(est).__name__, fi, e,
                        )
                        value = float("nan")
                    per_point_values[gi].append(value)
                sp.attrs["predict_seconds"] = predict_s
        return [
            CandidateResult(
                model_name=type(est).__name__,
                model_uid=est.uid,
                grid=points[gi],
                metric_values=per_point_values[gi],
            )
            for gi in range(len(points))
        ]

    @staticmethod
    def best(
        results: Sequence[CandidateResult], evaluator: Evaluator
    ) -> CandidateResult:
        key = lambda r: r.metric_mean  # noqa: E731
        finite = [r for r in results if np.isfinite(r.metric_mean)]
        pool = finite or list(results)
        return max(pool, key=key) if evaluator.is_larger_better else min(pool, key=key)


class CrossValidator(Validator):
    """k-fold CV (OpCrossValidation.scala:42-190; default 3 folds, optional
    label-stratified folds)."""

    def __init__(self, num_folds: int = 3, stratify: bool = False, seed: int = 42):
        super().__init__(seed)
        if num_folds < 2:
            raise ValueError("num_folds must be >= 2")
        self.num_folds = num_folds
        self.stratify = stratify

    def split_masks(self, y: np.ndarray):
        n = len(y)
        rng = np.random.default_rng(self.seed)
        assignment = np.empty(n, dtype=np.int64)
        if self.stratify:
            for cls in np.unique(y):
                idx = np.nonzero(y == cls)[0]
                assignment[idx] = rng.permutation(len(idx)) % self.num_folds
        else:
            assignment = rng.permutation(n) % self.num_folds
        folds = []
        for f in range(self.num_folds):
            val = assignment == f
            folds.append((~val, val))
        return folds


class TrainValidationSplit(Validator):
    """Single random split (OpTrainValidationSplit.scala; default ratio .75)."""

    def __init__(self, train_ratio: float = 0.75, seed: int = 42):
        super().__init__(seed)
        self.train_ratio = train_ratio

    def split_masks(self, y: np.ndarray):
        n = len(y)
        rng = np.random.default_rng(self.seed)
        train = rng.random(n) < self.train_ratio
        return [(train, ~train)]
