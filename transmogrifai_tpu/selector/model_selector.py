"""ModelSelector — automated model selection with CV over model families ×
hyperparameter grids.

Reference: core/.../stages/impl/selector/ModelSelector.scala:72-264 and the
problem-specific factories (BinaryClassificationModelSelector.scala,
MultiClassificationModelSelector.scala, RegressionModelSelector.scala).
Flow (ModelSelector.scala:116-208): validator.validate over candidates ->
best estimator -> splitter.validationPrepare -> refit winner on prepared
train -> train metrics -> SelectedModel with ModelSelectorSummary metadata.

Default binary candidates are LogisticRegression + RandomForest + XGBoost
(BinaryClassificationModelSelector.scala:61-63); tree families join the
default list here once the histogram-GBDT milestone lands.
"""
from __future__ import annotations

import logging
from typing import Any, Sequence

import numpy as np

from ..evaluators import (
    BinaryClassificationEvaluator,
    Evaluator,
    MultiClassificationEvaluator,
    RegressionEvaluator,
)
from ..models.base import PredictorEstimator, PredictorModel
from ..models.gbdt import (
    await_stack_outputs,
    DecisionTreeClassifier,
    DecisionTreeRegressor,
    GBTClassifier,
    GBTRegressor,
    RandomForestClassifier,
    RandomForestRegressor,
    XGBoostClassifier,
    XGBoostRegressor,
)
from ..models.glm import GeneralizedLinearRegression
from ..models.linear import LinearRegression
from ..models.logistic import LogisticRegression
from ..models.mlp import MLPClassifier
from ..models.naive_bayes import NaiveBayes
from ..models.svc import LinearSVC
from ..prep.splitters import DataBalancer, DataCutter, DataSplitter
from ..telemetry import spans as _tspans
from .validators import CrossValidator, TrainValidationSplit, Validator

log = logging.getLogger(__name__)

# DefaultSelectorParams.scala:37-75
REGULARIZATION = [0.001, 0.01, 0.1, 0.2]
ELASTIC_NET = [0.1, 0.5]
MAX_ITER_LIN = [50]
FIT_INTERCEPT = [True]
MAX_DEPTH = [3, 6, 12]
MIN_INSTANCES = [10, 100]
MIN_INFO_GAIN = [0.001, 0.01, 0.1]
MAX_TREES = [50]
MAX_ITER_TREE = [20]
XGB_NUM_ROUND = [200]
XGB_ETA = [0.02]
XGB_MIN_CHILD_WEIGHT = [1.0, 10.0]
XGB_MAX_DEPTH_BINARY = [10]
XGB_GAMMA_BINARY = [0.8]
GLR_FAMILY = ["gaussian", "poisson"]
GLR_LINK = ["identity", "log"]
GLR_TOL = [1e-6]


# Full candidate enums (BinaryClassificationModelsToTry / MultiClassification /
# RegressionModelsToTry — *ModelSelector.scala full enums; entries beyond the
# defaults are opt-in via ``make_candidates(problem, names)``, which expands
# each name to an (estimator instance, default grid) pair accepted by the
# selectors' ``models=`` argument).
BINARY_CLASSIFICATION_MODELS = {
    "OpLogisticRegression": LogisticRegression,
    "OpRandomForestClassifier": RandomForestClassifier,
    "OpXGBoostClassifier": XGBoostClassifier,
    "OpGBTClassifier": GBTClassifier,
    "OpDecisionTreeClassifier": DecisionTreeClassifier,
    "OpNaiveBayes": NaiveBayes,
    "OpLinearSVC": LinearSVC,
    "OpMultilayerPerceptronClassifier": MLPClassifier,
}
MULTI_CLASSIFICATION_MODELS = {
    "OpLogisticRegression": LogisticRegression,
    "OpRandomForestClassifier": RandomForestClassifier,
    "OpXGBoostClassifier": XGBoostClassifier,
    "OpDecisionTreeClassifier": DecisionTreeClassifier,
    "OpNaiveBayes": NaiveBayes,
    "OpMultilayerPerceptronClassifier": MLPClassifier,
}
REGRESSION_MODELS = {
    "OpLinearRegression": LinearRegression,
    "OpRandomForestRegressor": RandomForestRegressor,
    "OpGBTRegressor": GBTRegressor,
    "OpXGBoostRegressor": XGBoostRegressor,
    "OpDecisionTreeRegressor": DecisionTreeRegressor,
    "OpGeneralizedLinearRegression": GeneralizedLinearRegression,
}


def make_candidates(
    problem_kind: str, names: Sequence[str]
) -> list[tuple["PredictorEstimator", dict[str, Sequence[Any]]]]:
    """Expand reference model-enum names into (estimator, default grid) pairs
    for the selectors' ``models=`` argument, e.g.
    ``BinaryClassificationModelSelector(models=make_candidates(
    "BinaryClassification", ["OpNaiveBayes", "OpLinearSVC"]))``."""
    catalog = {
        "BinaryClassification": BINARY_CLASSIFICATION_MODELS,
        "MultiClassification": MULTI_CLASSIFICATION_MODELS,
        "Regression": REGRESSION_MODELS,
    }.get(problem_kind)
    if catalog is None:
        raise ValueError(f"unknown problem kind {problem_kind!r}")
    out = []
    for name in names:
        cls = catalog.get(name)
        if cls is None:
            raise ValueError(
                f"{name!r} is not a {problem_kind} model; choose from "
                f"{sorted(catalog)}"
            )
        out.append((cls(), _default_grid_for(cls)))
    return out


def _default_grid_for(cls: type) -> dict[str, Sequence[Any]]:
    grids: dict[type, dict[str, Sequence[Any]]] = {
        LogisticRegression: _lr_grid(),
        LinearRegression: _lr_grid(),
        RandomForestClassifier: _rf_grid(),
        RandomForestRegressor: _rf_grid(),
        GBTClassifier: _gbt_grid(),
        GBTRegressor: _gbt_grid(),
        XGBoostClassifier: _xgb_binary_grid(),
        XGBoostRegressor: _xgb_binary_grid(),
        DecisionTreeClassifier: {
            "max_depth": MAX_DEPTH,
            "min_info_gain": MIN_INFO_GAIN,
            "min_instances_per_node": MIN_INSTANCES,
        },
        DecisionTreeRegressor: {
            "max_depth": MAX_DEPTH,
            "min_info_gain": MIN_INFO_GAIN,
            "min_instances_per_node": MIN_INSTANCES,
        },
        NaiveBayes: {"smoothing": [1.0]},
        LinearSVC: {"reg_param": REGULARIZATION, "max_iter": MAX_ITER_LIN},
        MLPClassifier: {},
        GeneralizedLinearRegression: _glr_grid(),
    }
    return grids.get(cls, {})


def _lr_grid() -> dict[str, Sequence[Any]]:
    return {
        "fit_intercept": FIT_INTERCEPT,
        "elastic_net_param": ELASTIC_NET,
        "max_iter": MAX_ITER_LIN,
        "reg_param": REGULARIZATION,
    }


def _glr_grid() -> dict[str, Sequence[Any]]:
    """The regression selector's GLR grid (DefaultSelectorParams: family
    {gaussian, poisson} × link {identity, log} × regParam, maxIter 50, tol
    1e-6, an intercept; quoted from memory of the published source)."""
    return {
        "fit_intercept": FIT_INTERCEPT,
        "family": GLR_FAMILY,
        "link": GLR_LINK,
        "max_iter": MAX_ITER_LIN,
        "reg_param": REGULARIZATION,
        "tol": GLR_TOL,
    }


def _rf_grid() -> dict[str, Sequence[Any]]:
    return {
        "max_depth": MAX_DEPTH,
        "min_info_gain": MIN_INFO_GAIN,
        "min_instances_per_node": MIN_INSTANCES,
        "num_trees": MAX_TREES,
    }


def _gbt_grid() -> dict[str, Sequence[Any]]:
    return {
        "max_depth": MAX_DEPTH,
        "min_info_gain": MIN_INFO_GAIN,
        "min_instances_per_node": MIN_INSTANCES,
        "max_iter": MAX_ITER_TREE,
    }


def _xgb_binary_grid() -> dict[str, Sequence[Any]]:
    return {
        "num_round": XGB_NUM_ROUND,
        "eta": XGB_ETA,
        "gamma": XGB_GAMMA_BINARY,
        "max_depth": XGB_MAX_DEPTH_BINARY,
        "min_child_weight": XGB_MIN_CHILD_WEIGHT,
    }


class SelectedModel(PredictorModel):
    """The fitted winner (SelectedModel in ModelSelector.scala) — delegates
    to the best inner model and carries the selection summary."""

    def __init__(self, best_model: PredictorModel, summary: dict[str, Any], uid=None):
        super().__init__("modelSelector", uid=uid)
        self.best_model = best_model
        self.metadata["modelSelectorSummary"] = summary

    def predict_arrays(self, x: np.ndarray):
        return self.best_model.predict_arrays(x)

    def fused_predict_spec(self):
        """Delegate the fused-graph device core to the winning family (the
        spec's epilogue is the winner's too, so parity carries over)."""
        spec_fn = getattr(self.best_model, "fused_predict_spec", None)
        if spec_fn is None:
            from ..compiler.fused import Unfuseable

            raise Unfuseable(
                f"selected model family {type(self.best_model).__name__} "
                "has no fused device predict"
            )
        return spec_fn()

    def fused_bin_thresholds(self):
        """Delegate the quantized plane's bin-alignment source to the
        winner (None when the winning family has no binning — the
        quantizer then uses affine fit-range codes)."""
        thr_fn = getattr(self.best_model, "fused_bin_thresholds", None)
        return thr_fn() if thr_fn is not None else None

    def get_arrays(self):
        return {f"best__{k}": v for k, v in self.best_model.get_arrays().items()}

    def get_params(self):
        return {
            "best_model_class": type(self.best_model).__name__,
            "best_model_params": self.best_model.get_params(),
            "summary": self.metadata.get("modelSelectorSummary", {}),
        }

    @classmethod
    def from_params(cls, params, arrays):
        from ..workflow.persistence import construct_stage

        inner_arrays = {
            k[len("best__"):]: v
            for k, v in arrays.items()
            if k.startswith("best__")
        }
        inner = construct_stage(
            params["best_model_class"], params["best_model_params"], inner_arrays
        )
        return cls(inner, params.get("summary", {}))

    @property
    def summary(self) -> dict[str, Any]:
        return self.metadata["modelSelectorSummary"]

    def evaluate_holdout(self, x: np.ndarray, y: np.ndarray, evaluator: Evaluator):
        pred, prob, _ = self.predict_arrays(x)
        metrics = evaluator.evaluate_arrays(y, pred, prob)
        self.metadata["modelSelectorSummary"]["holdoutEvaluation"] = metrics
        return metrics


def keep_rows(x, y, keep):
    """``(x, y)`` at the rows the boolean ``keep`` keeps, and the bytes
    copied to get them. A mask that keeps every row (the product path's:
    ``PredictorEstimator.fit_model``) returns ``x`` and ``y`` themselves:
    no index, no copy, the caller's layout. A mask that drops rows gathers
    them into new row-major arrays: tree fit's thresholds are quantiles of
    the KEPT rows."""
    if keep.all():
        return x, y, 0
    idx = np.nonzero(keep)[0]
    xk, yk = x[idx], y[idx]
    return xk, yk, int(xk.nbytes + yk.nbytes)


class ModelSelector(PredictorEstimator):
    """Estimator[(RealNN, OPVector)] -> Prediction that finds, refits, and
    wraps the best model family × grid point."""

    def __init__(
        self,
        validator: Validator,
        splitter: DataSplitter | None,
        models: Sequence[tuple[PredictorEstimator, dict[str, Sequence[Any]]]],
        evaluator: Evaluator,
        extra_evaluators: Sequence[Evaluator] = (),
        problem_kind: str = "unknown",
        uid: str | None = None,
    ):
        super().__init__("modelSelector", uid=uid)
        self.validator = validator
        self.splitter = splitter
        self.models = list(models)
        self.evaluator = evaluator
        self.extra_evaluators = list(extra_evaluators)
        self.problem_kind = problem_kind
        #: set by workflow-level CV (workflow/cv.py): validation already ran
        #: with per-fold DAG refits, so fit skips the internal validator
        self.precomputed_results: list | None = None
        #: set by Workflow.train(checkpoint_dir=...): a resilience
        #: CheckpointManager; the validator checkpoints per-candidate sweep
        #: results there so a resumed selection re-runs only unfinished ones
        #: (_checkpoint_resume gates CONSUMING them — writes always happen)
        self._checkpoint = None
        self._checkpoint_resume = False

    def get_params(self):
        return {
            "problem_kind": self.problem_kind,
            "evaluator": self.evaluator.name,
            "validator": type(self.validator).__name__,
            "splitter": type(self.splitter).__name__ if self.splitter else None,
        }

    def fit_arrays(self, x, y, row_mask) -> SelectedModel:
        """One sweep over ``x``'s rows that ``row_mask`` keeps.

        Where the mask (and a ``DataCutter``) keeps every row, the
        families receive ``x`` and ``y`` THEMSELVES, in the layout the
        caller gave them (``keep_rows``), so tree fit's bin cache
        (``gbdt._TreeEstimator._binned``) reaches this method's callers:
        it is keyed on ``x``'s buffer (address, shape, strides) and holds a
        strong reference to it, so a second call on the same unmutated
        array takes the first call's thresholds and bin codes, and a
        caller that writes into ``x`` between calls must hand in a new
        array instead. No estimator, validator or evaluator writes into
        ``x`` or ``y``."""
        # one trace per sweep: every span below (and the fits the candidate
        # pool runs on its threads) carries this root's id as ``trace``
        with _tspans.span(
            "selector/sweep", rows=int(x.shape[0]), cols=int(x.shape[1]),
            families=len(self.models),
        ) as root:
            return self._sweep(x, y, row_mask, root)

    def _sweep(self, x, y, row_mask, root) -> SelectedModel:
        from ..compiler import stats as cstats
        from ..featurize import stats as fstats

        # compile-plane and featurize-plane ledgers for THIS selection
        # (programs compiled / cache + dedup hits / warmup overlap; rows
        # featurized / pool utilization / fallback kernels) — the deltas
        # land in the summary next to the retry and failover ledgers
        compile_baseline = cstats.snapshot()
        featurize_baseline = fstats.snapshot()
        with _tspans.span("selector/row_select", rows_in=len(row_mask)) as sp:
            xt, yt, copied = keep_rows(x, y, np.asarray(row_mask) > 0)

            # pre-validation prepare (DataCutter removes rare labels up front)
            if isinstance(self.splitter, DataCutter):
                xt, yt, cut = keep_rows(xt, yt, self.splitter.prepare(yt))
                copied += cut

            # validation prepare (balancing / down-sampling) is a
            # deterministic seeded function of yt, so the refit mask is
            # computable BEFORE validation — it rides the candidate sweep as
            # an extra fit lane of the same batched program, so the winner's
            # refit model is already trained when validation returns (no
            # separate refit program)
            final_mask = np.ones(len(yt), dtype=np.float32)
            if self.splitter is not None and not isinstance(
                self.splitter, DataCutter
            ):
                final_mask = self.splitter.prepare(yt).astype(np.float32)
            sp.attrs.update(rows_out=len(yt), bytes_copied=int(copied))

        attempt_info: list = []
        if self.precomputed_results is not None:
            # consume-once: stale fold metrics must not leak into a later
            # re-train on different data
            results = self.precomputed_results
            self.precomputed_results = None
            prefit = {}
        else:
            extra_masks = [final_mask]
            with _tspans.span(
                "selector/validate", extra_masks=len(extra_masks)
            ) as sp:
                results = self.validator.validate(
                    self.models, xt, yt, self.evaluator,
                    extra_masks=extra_masks,
                    checkpoint=self._checkpoint,
                    resume=self._checkpoint_resume,
                )
                sp.attrs["folds"] = max(
                    len(r.metric_values) for r in results
                )
            prefit = getattr(self.validator, "last_extra_models", {})
            attempt_info = list(
                getattr(self.validator, "last_attempt_info", [])
            )
            # every grid point is fitted once per fold and once on the
            # refit mask
            root.attrs["lanes"] = len(results) * (
                sp.attrs["folds"] + len(extra_masks)
            )
        root.attrs["points"] = len(results)
        with _tspans.span("selector/refit") as sp:
            best = Validator.best(results, self.evaluator)
            log.info(
                "ModelSelector best: %s %s (%s=%.4f over %d candidates)",
                best.model_name,
                best.grid,
                self.evaluator.default_metric,
                best.metric_mean,
                len(results),
            )

            family = next(
                est for est, _ in self.models if est.uid == best.model_uid
            )
            final_est = family.with_params(**best.grid)

            splitter_summary = None
            if self.splitter is not None and self.splitter.summary is not None:
                splitter_summary = self.splitter.summary.to_json()

            # the winner's refit model usually already exists as the extra
            # sweep lane fitted on final_mask (validate(extra_masks=...));
            # families without the batched hook (or the workflow-CV path)
            # refit directly — batched when possible so the program comes from
            # the AOT executable bank
            best_model = None
            refit_raw = None
            if best.model_uid in prefit:
                points, extra_rows = prefit[best.model_uid]
                if best.grid in points and extra_rows:
                    best_model = extra_rows[0][points.index(best.grid)]
                    # the refit lane's raw outputs on xt were computed by the
                    # fit program itself — grab them BEFORE detach frees the
                    # stack, so train evaluation needs no re-predict
                    stack = getattr(best_model, "_sweep_stack", None)
                    if stack is not None and hasattr(
                        best_model, "sweep_lane_outputs"
                    ):
                        # a family whose lanes' outputs stay on the device
                        # hands over the refit lane's alone
                        refit_raw = best_model.sweep_lane_outputs()
                    elif (
                        stack is not None
                        and stack.get("outputs") is not None
                        and hasattr(best_model, "predictions_from_sweep")
                    ):
                        refit_raw = await_stack_outputs(stack)[
                            best_model._sweep_lane
                        ]
                    # free the sweep stacks: keep only the winner's own lane
                    detach = getattr(best_model, "detach_from_sweep", None)
                    if detach is not None:
                        detach()
            getattr(self.validator, "last_extra_models", {}).clear()
            sp.attrs["prefit"] = best_model is not None
            if best_model is None:
                batched = getattr(final_est, "fit_arrays_batched_masks", None)
                if batched is not None:
                    best_model = batched(
                        xt, yt, [final_mask], [dict(best.grid)]
                    )[0][0]
                else:
                    best_model = final_est.fit_arrays(xt, yt, final_mask)

            t0 = _tspans.clock()
            if refit_raw is not None:
                pred, prob, _ = best_model.predictions_from_sweep(refit_raw)
            else:
                pred, prob, _ = best_model.predict_arrays(xt)
            sp.attrs["predict_seconds"] = _tspans.clock() - t0
            # the train evaluation goes whole into the summary
            with _tspans.span(
                "selector/evaluate", lanes=1, rows=len(yt),
                classes=0 if prob is None else int(np.shape(prob)[1]),
                bytes=0 if refit_raw is None else int(refit_raw.nbytes),
                reads="all",
            ):
                train_metrics = self.evaluator.evaluate_arrays(yt, pred, prob)
                extra_train = {
                    ev.name: ev.evaluate_arrays(yt, pred, prob)
                    for ev in self.extra_evaluators
                }

            summary = {
                "problemKind": self.problem_kind,
                "validationType": type(self.validator).__name__,
                "evaluationMetric": self.evaluator.default_metric,
                "bestModelName": f"{best.model_name}_{best.model_uid}",
                "bestModelType": best.model_name,
                "bestGrid": best.grid,
                "validationResults": [r.to_json() for r in results],
                "candidateAttempts": attempt_info,
                "trainEvaluation": train_metrics,
                "extraTrainEvaluations": extra_train,
                "holdoutEvaluation": None,
                "splitterSummary": splitter_summary,
                "compileStats": cstats.delta(compile_baseline),
                "featurizeStats": fstats.delta(featurize_baseline),
            }
            self.metadata["modelSelectorSummary"] = summary
            return SelectedModel(best_model, summary)


def BinaryClassificationModelSelector(
    validator: Validator | None = None,
    splitter: DataSplitter | None = None,
    models: Sequence[tuple[PredictorEstimator, dict[str, Sequence[Any]]]] | None = None,
    evaluator: Evaluator | None = None,
    num_folds: int = 3,
    seed: int = 42,
) -> ModelSelector:
    """CV binary selector (BinaryClassificationModelSelector.scala; default
    3-fold CV, DataBalancer, AuPR metric; default candidates LR + RF + XGB
    per modelTypesToUse :61-63)."""
    if models is None:
        models = [
            (LogisticRegression(), _lr_grid()),
            (RandomForestClassifier(), _rf_grid()),
            (XGBoostClassifier(), _xgb_binary_grid()),
        ]
    return ModelSelector(
        validator=validator or CrossValidator(num_folds=num_folds, seed=seed),
        splitter=splitter if splitter is not None else DataBalancer(seed=seed),
        models=models,
        evaluator=evaluator or BinaryClassificationEvaluator(),
        extra_evaluators=(),
        problem_kind="BinaryClassification",
    )


def MultiClassificationModelSelector(
    validator: Validator | None = None,
    splitter: DataSplitter | None = None,
    models: Sequence[tuple[PredictorEstimator, dict[str, Sequence[Any]]]] | None = None,
    evaluator: Evaluator | None = None,
    num_folds: int = 3,
    seed: int = 42,
) -> ModelSelector:
    """Multiclass selector (MultiClassificationModelSelector.scala; default
    candidates LR + RF (:61-63), DataCutter, weighted F1)."""
    if models is None:
        models = [
            (LogisticRegression(), _lr_grid()),
            (RandomForestClassifier(), _rf_grid()),
        ]
    return ModelSelector(
        validator=validator or CrossValidator(num_folds=num_folds, seed=seed),
        splitter=splitter if splitter is not None else DataCutter(seed=seed),
        models=models,
        evaluator=evaluator or MultiClassificationEvaluator(),
        problem_kind="MultiClassification",
    )


def RegressionModelSelector(
    validator: Validator | None = None,
    splitter: DataSplitter | None = None,
    models: Sequence[tuple[PredictorEstimator, dict[str, Sequence[Any]]]] | None = None,
    evaluator: Evaluator | None = None,
    seed: int = 42,
) -> ModelSelector:
    """Regression selector (RegressionModelSelector.scala; default
    train/validation split .75, DataSplitter, RMSE; default candidates
    LinearRegression + RF + GBT per :61-63)."""
    if models is None:
        models = [
            (
                LinearRegression(),
                {
                    "fit_intercept": FIT_INTERCEPT,
                    "elastic_net_param": ELASTIC_NET,
                    "max_iter": MAX_ITER_LIN,
                    "reg_param": REGULARIZATION,
                },
            ),
            (RandomForestRegressor(), _rf_grid()),
            (GBTRegressor(), _gbt_grid()),
        ]
    return ModelSelector(
        validator=validator or TrainValidationSplit(seed=seed),
        splitter=splitter if splitter is not None else DataSplitter(seed=seed),
        models=models,
        evaluator=evaluator or RegressionEvaluator(),
        problem_kind="Regression",
    )
