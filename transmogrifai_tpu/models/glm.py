"""Generalized linear regression (IRLS).

Reference: core/.../stages/impl/regression/OpGeneralizedLinearRegression.scala
wraps Spark GeneralizedLinearRegression (families gaussian/binomial/poisson/
gamma, canonical + explicit links, L2 regParam, maxIter, tol). The learner
is Spark's (``GLR_IRLS``): gaussian/identity is one WeightedLeastSquares
solve; every other pair starts from FamilyAndLink.initialize and runs
IterativelyReweightedLeastSquares until the coefficients move less than
``tol``. A sweep's lanes (folds × grid points, the refit lane) are ONE
program on the shared plane (``solvers.fit_glr_irls_batched``): the
family and the link are lane data, the weighted Grams stream the plane in
row blocks. Its lanes are scored from the fitted means the program
computed on the device: only the fold lanes' validation rows and the refit
winner's lane come to the host.
"""
from __future__ import annotations

from collections import Counter

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import spans as _tspans
from . import solvers
from .base import PredictorEstimator, PredictorModel, score_sweep_stacks
from .logistic import _GLM_STATS, _await, solver_precision
from .solvers import (
    GLM_DEFAULT_LINK,
    GLM_FAMILIES,
    GLM_LINKS,
    GLR_EPS,
    fit_glr_irls,
    fit_glr_irls_batched,
)

#: How this estimator fits, stated as ``logistic.LOGISTIC_MULTINOMIAL``
#: states the multinomial fit's: Spark's learner, the family and link of
#: every lane as data of one program.
GLR_IRLS = (
    "Spark IRLS: gaussian/identity one WLS solve, other pairs from "
    "FamilyAndLink.initialize, Cholesky WLS, stop at tol"
)


def _linkinv_np(eta: np.ndarray, link: str) -> np.ndarray:
    if link == "identity":
        return eta
    if link == "log":
        return np.exp(eta)
    if link == "logit":
        return 1.0 / (1.0 + np.exp(-eta))
    if link == "inverse":
        safe = np.where(np.abs(eta) > 1e-7, eta, 1e-7)
        return 1.0 / safe
    if link == "sqrt":
        return eta * eta
    raise ValueError(f"unknown link {link}")


def _project_np(mu: np.ndarray, family: str) -> np.ndarray:
    """Spark's ``project`` of the family (``solvers._glr_project``)."""
    big = np.finfo(np.float64).max
    if family == "gaussian":
        return np.clip(mu, -big, big)
    if family == "binomial":
        return np.clip(mu, GLR_EPS, 1.0 - GLR_EPS)
    return np.clip(mu, GLR_EPS, big)


@jax.jit
def _lane_rows(mu, lanes, rows):
    """[L, V]: lanes ``lanes`` of ``mu`` [K, N] at rows ``rows``."""
    return jnp.take(jnp.take(mu, lanes, axis=0), rows, axis=1)


class _IRLSSweep:
    """One IRLS program's outputs for a sweep: ``k`` real lanes of
    ``solvers.GLRFit`` (the padded ones after them), over ``rows`` real
    rows; ``closed`` [k] marks the gaussian/identity lanes. The fitted
    means stay on the device; what is scored comes to the host by lane
    and row."""

    def __init__(self, fit, k: int, rows: int, closed: np.ndarray):
        self.fit, self.k, self.rows, self.closed = fit, k, rows, closed

    def await_params(self):
        """(weights [k, D], intercept [k]) on the host; the read also lands
        the program's counts, on the span and the ``glm`` ledger."""
        f, k = self.fit, self.k

        def counts(out):
            iters, stol, sfloor, proj, nonpos, t = out[2:]
            iters = iters[:k]
            stopped = (stol | sfloor)[:k]
            got = dict(
                iters_needed=int(iters.sum()), iters_run=k * int(t),
                iters_longest=int(iters.max()), lanes=k,
                lanes_irls=int((~self.closed).sum()),
                lanes_stopped=int(stopped.sum()),
                lanes_stopped_tol=int(stol[:k].sum()),
                lanes_stopped_floor=int(sfloor[:k].sum()),
                rows_projected=int(proj[:k].sum()),
                rows_nonpositive=int(nonpos[:k].sum()),
            )
            _GLM_STATS.record_irls(got["iters_needed"], got["iters_run"],
                                   got["lanes_stopped"],
                                   got["rows_projected"])
            return got

        w, b, *_ = _await(
            (f.weights, f.intercept, f.iters, f.stop_tol, f.stop_floor,
             f.rows_projected, f.rows_nonpositive, f.iterations), counts)
        return w[:k], b[:k]

    def lane_rows(self, lanes, rows) -> np.ndarray:
        """[L, V] host fitted means of ``lanes`` at ``rows``."""
        from ..utils.aot import aot_call

        return _await(aot_call(
            "glr_lane_rows", _lane_rows,
            (self.fit.mu, jnp.asarray(np.asarray(lanes, dtype=np.int32)),
             jnp.asarray(np.asarray(rows, dtype=np.int32))), {}))


class GeneralizedLinearRegressionModel(PredictorModel):
    def __init__(self, weights, intercept, family: str, link: str, uid=None):
        super().__init__("glm", uid=uid)
        self.weights = np.asarray(weights, dtype=np.float64)
        self.intercept = float(np.asarray(intercept))
        self.family = family
        self.link = link

    def get_arrays(self):
        return {"weights": self.weights, "intercept": np.asarray(self.intercept)}

    def get_params(self):
        return {"family": self.family, "link": self.link}

    @classmethod
    def from_params(cls, params, arrays):
        return cls(arrays["weights"], arrays["intercept"],
                   params["family"], params["link"])

    def predict_arrays(self, x: np.ndarray):
        return self.predictions_from_core(x @ self.weights + self.intercept)

    def predictions_from_core(self, core: np.ndarray):
        """Host epilogue shared by staged predict and the fused graph:
        the fitted mean project(g⁻¹(η)) over the downloaded linear
        predictor η (Spark's ``fitted``)."""
        eta = np.asarray(core, dtype=np.float64)
        mu = _project_np(_linkinv_np(eta, self.link), self.family)
        return mu.astype(np.float64), None, None

    # ---- sweep protocol (validators._sweep_family, the selector's refit):
    # a sweep's lanes keep ``_sweep_stack`` (the program's ``_IRLSSweep``)
    # and ``_sweep_lane``
    def predictions_from_sweep(self, mu):
        """(pred, None, None) from one lane's fitted means as the fit
        program computed them: [N]."""
        return np.asarray(mu, dtype=np.float64), None, None

    def sweep_lane_outputs(self) -> np.ndarray:
        """This lane's [N] fitted means on every training row, fetched
        alone (the refit winner's train evaluation)."""
        stack = self._sweep_stack
        return stack.lane_rows([self._sweep_lane], np.arange(stack.rows))[0]

    def detach_from_sweep(self) -> None:
        """Drop the reference to the sweep's device means."""
        for attr in ("_sweep_stack", "_sweep_lane"):
            if hasattr(self, attr):
                delattr(self, attr)

    def fused_predict_spec(self):
        from ..compiler.fused import PredictorPlan

        params = {
            "w": np.asarray(self.weights, dtype=np.float32),
            "b": np.float32(self.intercept),
        }

        def core(plane, p):
            return plane @ p["w"] + p["b"]

        return PredictorPlan(
            stage=self, in_dim=int(self.weights.shape[0]), params=params,
            core=core, epilogue=self.predictions_from_core,
            descriptor=f"glm:{self.family}:{self.link}",
        )


class GeneralizedLinearRegression(PredictorEstimator):
    """Spark defaults: family='gaussian', link=canonical, regParam=0,
    maxIter=25, tol=1e-6, fitIntercept=true
    (OpGeneralizedLinearRegression.scala)."""

    model_type = "OpGeneralizedLinearRegression"

    def __init__(self, family: str = "gaussian", link: str | None = None,
                 reg_param: float = 0.0, max_iter: int = 25,
                 fit_intercept: bool = True, tol: float = 1e-6,
                 uid: str | None = None):
        super().__init__("glm", uid=uid)
        if family not in GLM_FAMILIES:
            raise ValueError(f"unknown family {family}")
        link = link or GLM_DEFAULT_LINK[family]
        if link not in GLM_LINKS:
            raise ValueError(f"unknown link {link}")
        self.family = family
        self.link = link
        self.reg_param = reg_param
        self.max_iter = max_iter
        self.fit_intercept = fit_intercept
        self.tol = tol

    def get_params(self):
        return {
            "family": self.family,
            "link": self.link,
            "reg_param": self.reg_param,
            "max_iter": self.max_iter,
            "fit_intercept": self.fit_intercept,
            "tol": self.tol,
        }

    def with_params(self, **params):
        # grid points that change the family without naming a link must get
        # the new family's canonical link, not this instance's resolved one
        if "family" in params and "link" not in params:
            params = {**params, "link": GLM_DEFAULT_LINK[params["family"]]}
        return super().with_params(**params)

    def fit_arrays(self, x, y, row_mask):
        fit = fit_glr_irls(
            jnp.asarray(np.asarray(x, dtype=np.float32)),
            jnp.asarray(np.asarray(y, dtype=np.float32)),
            np.asarray(row_mask, dtype=np.float32), float(self.reg_param),
            family=GLM_FAMILIES[self.family], link=GLM_LINKS[self.link],
            max_iter=int(self.max_iter), tol=float(self.tol),
            fit_intercept=bool(self.fit_intercept),
        )
        return GeneralizedLinearRegressionModel(
            np.asarray(fit.weights[0]), np.asarray(fit.intercept[0]),
            self.family, self.link,
        )

    # ---- batched sweeps: every lane of a static group in ONE program ----

    _KNOWN_KEYS = frozenset(
        ("family", "link", "reg_param", "max_iter", "fit_intercept", "tol")
    )

    #: GLM lanes pad onto shape buckets; the pipelined fold schedule
    #: (workflow/cv.py) overlaps tree fits with these dispatches
    lane_family = "glm"

    def _lane_of(self, point) -> tuple[str, str, float, float]:
        """(family, link, λ, tol) of a grid point, its link resolved as
        ``with_params`` resolves it."""
        family = point.get("family", self.family)
        if "link" in point:
            link = point["link"]
        elif "family" in point:
            link = GLM_DEFAULT_LINK[family]
        else:
            link = self.link
        if family not in GLM_FAMILIES or link not in GLM_LINKS:
            raise ValueError(f"unknown family/link {family}/{link}")
        return (family, link, float(point.get("reg_param", self.reg_param)),
                float(point.get("tol", self.tol)))

    def _irls_fit(self, x, y, masks, lanes, lane_mask, statics, rows):
        """Every lane of one static group in ONE program on the shared
        plane (``solvers.fit_glr_irls_batched``), dispatched
        asynchronously: lanes padded onto a shape bucket, the plane
        through ``dispatch.device_f32``, the program through the
        executable bank."""
        from ..compiler import bucketing, dispatch
        from ..utils.aot import aot_call

        fit_intercept, max_iter = statics
        fam = np.asarray([GLM_FAMILIES[f] for f, _l, _r, _t in lanes],
                         dtype=np.int32)
        lnk = np.asarray([GLM_LINKS[l] for _f, l, _r, _t in lanes],
                         dtype=np.int32)
        regs = np.asarray([r for *_fl, r, _t in lanes], dtype=np.float32)
        tols = np.asarray([t for *_flr, t in lanes], dtype=np.float32)
        k, (lm, fam_p, lnk_p, regs, tols) = bucketing.bucket_sweep_lanes(
            np.asarray(lane_mask, dtype=np.int32), fam, lnk, regs, tols)
        pairs = Counter(f"{f}:{l}" for f, l, _r, _t in lanes)
        with _tspans.span(
            "glm/fit_dispatch", lanes=k, rows=int(x.shape[0]),
            cols=int(x.shape[1]), solver="irls",
            pairs=" ".join(f"{p}={c}" for p, c in sorted(pairs.items())),
            max_iter=max_iter,
            tol=" ".join(repr(t) for t in sorted({t for *_flr, t in lanes})),
            ulp_floor=solvers.GLR_ULP_FLOOR, row_block=solvers.GLR_ROW_BLOCK,
            precision=solver_precision(),
        ):
            fit = aot_call(
                "glr_irls_batched", fit_glr_irls_batched,
                (
                    dispatch.device_f32(x),
                    jnp.asarray(np.asarray(y, dtype=np.float32)),
                    jnp.asarray(np.stack(masks)), jnp.asarray(lm),
                    jnp.asarray(fam_p), jnp.asarray(lnk_p),
                    jnp.asarray(regs), jnp.asarray(tols),
                ),
                dict(max_iter=max_iter, fit_intercept=fit_intercept),
            )
        closed = (fam == GLM_FAMILIES["gaussian"]) & (
            lnk == GLM_LINKS["identity"])
        return _IRLSSweep(fit, k, rows, closed)

    def sweep_dispatch_masks(self, x, y, masks, grid_points):
        """Dispatch the folds × grid sweep, return a collector closure.

        Each same-(fit_intercept, max_iter) group is ONE program over
        every (row mask, grid point) lane, the family, link, λ and tol of
        each lane its data; points with unknown params fall back to
        sequential fits inside the collector. Dispatch is async; the
        closure materializes the models, so tree-family fits can overlap
        (the pipelined lane schedule in workflow/cv.py)."""
        from .base import group_grid_by_statics

        masks = [np.asarray(m, dtype=np.float32) for m in masks]
        n_masks = len(masks)
        groups, sequential = group_grid_by_statics(
            grid_points, self._KNOWN_KEYS,
            lambda p: (bool(p.get("fit_intercept", self.fit_intercept)),
                       int(p.get("max_iter", self.max_iter))),
        )
        stacked: list[tuple[list[int], int, _IRLSSweep]] = []
        for statics, idxs in groups.items():
            points = [self._lane_of(grid_points[i]) for i in idxs]
            # mask-major lanes: lane = mask index × points + point index
            lanes = points * n_masks
            lane_mask = np.repeat(np.arange(n_masks), len(points))
            stacked.append((idxs, len(points), self._irls_fit(
                x, y, masks, lanes, lane_mask, statics, rows=len(y))))

        def collect() -> list[list]:
            models: list[list] = [[None] * len(grid_points) for _ in masks]
            for idxs, n_pts, sweep in stacked:
                w, b = sweep.await_params()
                for mi in range(n_masks):
                    for j, i in enumerate(idxs):
                        lane = mi * n_pts + j
                        family, link, _reg, _tol = self._lane_of(
                            grid_points[i])
                        model = GeneralizedLinearRegressionModel(
                            w[lane], b[lane], family, link)
                        model._sweep_stack = sweep
                        model._sweep_lane = lane
                        models[mi][i] = model
            for i in sequential:
                est = self.with_params(**grid_points[i])
                for mi, m in enumerate(masks):
                    models[mi][i] = est.fit_arrays(x, y, m)
            return models

        return collect

    def fit_arrays_batched_masks(self, x, y, masks, grid_points):
        """Folds × grid in as few programs as the grid's static params
        allow — dispatch + immediate collect of
        :meth:`sweep_dispatch_masks`."""
        return self.sweep_dispatch_masks(x, y, masks, grid_points)()

    def sweep_eval_batched(self, models_by_fold, x, y, folds, evaluator):
        """Validator hook: every fold lane scored from the fitted means its
        program computed on the device, only the validation rows of a
        fold's lanes downloaded (one gather a fold). None where a lane has
        no such means (points fitted one by one): the validator then
        scores by ``predict_arrays``."""
        return score_sweep_stacks(models_by_fold, y, folds, evaluator)
