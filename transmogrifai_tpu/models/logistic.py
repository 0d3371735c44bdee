"""Logistic regression (binary + multinomial).

Reference: core/.../stages/impl/classification/OpLogisticRegression.scala —
wraps Spark LR (L-BFGS/OWL-QN over native BLAS). Here training is the pure
XLA solver in models/solvers.py; gradients over a sharded batch reduce with
``psum`` when the data axis is sharded over a mesh.

Over more than two classes the learner is Spark's multinomial family
(``LOGISTIC_MULTINOMIAL``): ONE softmax model a fit over all K classes, no
pivot class, by the binary fit's L-BFGS/OWL-QN for ``max_iter`` iterations
(1:1), a sweep's lanes batched on the shared plane
(``solvers.fit_logistic_multinomial_batched``). Its lanes are scored from
the class logits the fit program computed on the device: only the fold
lanes' validation rows and the refit winner's lane come to the host.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import metrics as _tm
from ..telemetry import runlog as _runlog
from ..telemetry import spans as _tspans
from . import solvers
from .base import PredictorEstimator, PredictorModel, score_sweep_stacks
from .solvers import (
    fit_logistic_binary,
    fit_logistic_binary_batched,
    fit_logistic_multinomial,
    fit_logistic_multinomial_batched,
)

#: How this estimator fits a label of more than two classes, stated as
#: ``gbdt.FOREST_MULTICLASS`` states the forest's: one softmax fit a lane
#: over the K classes (Spark's multinomial family), by L-BFGS/OWL-QN for
#: ``max_iter`` iterations.
LOGISTIC_MULTINOMIAL = (
    "one softmax fit over K classes: L-BFGS/OWL-QN, maxIter 1:1"
)


def solver_precision() -> str:
    """The matmul precision every GLM product runs at
    (``solvers.GLM_PRECISION``), in lower case: ``highest``."""
    return solvers.GLM_PRECISION.name.lower()


class GLMStats(_tm.LedgerCore):
    """The ``glm`` ledger: what the GLM fit programs report once their
    outputs land (``glm/await_outputs``), cumulative. From the multinomial
    fits: iterations in which a lane moved, iterations scanned (lanes ×
    ``max_iter``: the scan runs every iteration, a frozen lane too) and
    lanes whose gradient test held at the end. From the IRLS fits
    (``models/glm.py``): the iterations each lane took to its stop, the
    lane-iterations the programs ran, the lanes that stopped before
    ``max_iter`` and the lane rows whose mean was projected."""

    KEYS = ("itersMoved", "itersScanned", "lanesConverged",
            "irlsItersNeeded", "irlsItersRun", "irlsLanesStopped",
            "irlsRowsProjected")

    def __init__(self) -> None:
        super().__init__(self.KEYS)

    def record(self, moved: int, scanned: int, converged: int) -> None:
        with self._lock:
            self._counts["itersMoved"] += moved
            self._counts["itersScanned"] += scanned
            self._counts["lanesConverged"] += converged

    def record_irls(self, needed: int, run: int, stopped: int,
                    projected: int) -> None:
        with self._lock:
            self._counts["irlsItersNeeded"] += needed
            self._counts["irlsItersRun"] += run
            self._counts["irlsLanesStopped"] += stopped
            self._counts["irlsRowsProjected"] += projected

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._counts)


_GLM_STATS = GLMStats()
_tm.REGISTRY.register_source("glm", _GLM_STATS.snapshot)


@jax.jit
def _lane_rows(logits, lanes, rows):
    """[L, C, V]: lanes ``lanes`` of ``logits`` [K, C, N] at rows ``rows``."""
    return jnp.take(jnp.take(logits, lanes, axis=0), rows, axis=2)


def _await(value, counts=None):
    """``value`` (device arrays) on the host under a ``glm/await_outputs``
    span: how long the host waited for the fit program, and one download on
    the run ledger's census. ``counts(out)`` gives the attributes of the
    program's own counts once ``out`` has landed."""
    with _tspans.span("glm/await_outputs") as sp:
        t0 = _tspans.clock()
        out = jax.tree.map(np.asarray, value)
        nbytes = sum(int(a.nbytes) for a in jax.tree.leaves(out))
        sp.attrs["bytes"] = nbytes
        _runlog.record_download(nbytes, _tspans.clock() - t0)
        if counts is not None and _tspans.enabled():
            sp.attrs.update(counts(out))
    return out


class _MultinomialSweep:
    """One multinomial fit program's outputs for a sweep: ``k`` real lanes
    of ``solvers.MultinomialFit`` (the padded ones after them), over
    ``rows`` real rows. The class logits stay on the device; what is
    scored comes to the host by lane and row."""

    def __init__(self, fit, k: int, rows: int, num_iters: int):
        self.fit, self.k, self.rows, self.num_iters = fit, k, rows, num_iters

    def await_params(self):
        """(weights [k, D, C], intercept [k, C]) on the host; the read also
        lands the program's counts of iterations, on the span and the
        ``glm`` ledger."""
        f, k = self.fit, self.k

        def counts(out):
            moved, conv = out[2][:k], out[3][:k]
            got = dict(iters_moved=int(moved.sum()),
                       iters_scanned=k * self.num_iters,
                       lanes_converged=int(conv.sum()))
            _GLM_STATS.record(got["iters_moved"], got["iters_scanned"],
                              got["lanes_converged"])
            return got

        w, b, _, _ = _await(
            (f.weights, f.intercept, f.iters_moved, f.converged), counts)
        return w[:k], b[:k]

    def lane_rows(self, lanes, rows) -> np.ndarray:
        """[L, C, V] host logits of ``lanes`` at ``rows``."""
        from ..utils.aot import aot_call

        return _await(aot_call(
            "glm_lane_rows", _lane_rows,
            (self.fit.logits, jnp.asarray(np.asarray(lanes, dtype=np.int32)),
             jnp.asarray(np.asarray(rows, dtype=np.int32))), {}))


class LogisticRegressionModel(PredictorModel):
    def __init__(
        self,
        weights: np.ndarray,       # [D] binary or [D, C] multinomial
        intercept: np.ndarray,     # scalar or [C]
        num_classes: int,
        uid: str | None = None,
    ):
        super().__init__("logreg", uid=uid)
        self.weights = np.asarray(weights, dtype=np.float64)
        self.intercept = np.asarray(intercept, dtype=np.float64)
        self.num_classes = num_classes

    def get_arrays(self):
        return {"weights": self.weights, "intercept": self.intercept}

    def get_params(self):
        return {"num_classes": self.num_classes}

    @classmethod
    def from_params(cls, params, arrays):
        return cls(arrays["weights"], arrays["intercept"], params["num_classes"])

    def predict_arrays(self, x: np.ndarray):
        return self.predictions_from_core(x @ self.weights + self.intercept)

    def predictions_from_core(self, core: np.ndarray):
        """(pred, prob, raw) from the linear core (binary margin [N] or
        multinomial logits [N, C]) — the HOST epilogue shared by the
        staged predict and the fused graph's downloaded core."""
        core = np.asarray(core, dtype=np.float64)
        if self.num_classes == 2:
            margin = core
            p1 = 1.0 / (1.0 + np.exp(-margin))
            prob = np.stack([1.0 - p1, p1], axis=1)
            raw = np.stack([-margin, margin], axis=1)
        else:
            logits = core - core.max(axis=1, keepdims=True)
            e = np.exp(logits)
            prob = e / e.sum(axis=1, keepdims=True)
            raw = logits
        pred = prob.argmax(axis=1).astype(np.float64)
        return pred, prob, raw

    # ---- sweep protocol (validators._sweep_family, the selector's refit):
    # a multinomial sweep's lanes keep ``_sweep_stack`` (the program's
    # ``_MultinomialSweep``) and ``_sweep_lane``
    def predictions_from_sweep(self, logits):
        """(pred, prob, raw) from one lane's class logits as the fit
        program computed them, the rows last: [C, N]."""
        return self.predictions_from_core(np.asarray(logits).T)

    def sweep_lane_outputs(self) -> np.ndarray:
        """This lane's [C, N] logits on every training row, fetched
        alone (the refit winner's train evaluation)."""
        stack = self._sweep_stack
        return stack.lane_rows([self._sweep_lane], np.arange(stack.rows))[0]

    def detach_from_sweep(self) -> None:
        """Drop the reference to the sweep's device logits."""
        for attr in ("_sweep_stack", "_sweep_lane"):
            if hasattr(self, attr):
                delattr(self, attr)

    def fused_predict_spec(self):
        """Device core for the fused scoring graph: ``plane @ w + b`` in
        f32 (predictions within 1e-6 of the staged f64 host matmul)."""
        from ..compiler.fused import PredictorPlan

        params = {
            "w": np.asarray(self.weights, dtype=np.float32),
            "b": np.asarray(self.intercept, dtype=np.float32),
        }

        def core(plane, p):
            return plane @ p["w"] + p["b"]

        return PredictorPlan(
            stage=self, in_dim=int(self.weights.shape[0]), params=params,
            core=core, epilogue=self.predictions_from_core,
            descriptor=f"logreg:{self.num_classes}",
        )


class LogisticRegression(PredictorEstimator):
    """Params mirror Spark LR defaults (regParam=0, elasticNetParam=0,
    maxIter=100, standardization=true, fitIntercept=true)."""

    model_type = "OpLogisticRegression"

    def __init__(
        self,
        reg_param: float = 0.0,
        elastic_net_param: float = 0.0,
        max_iter: int = 100,
        fit_intercept: bool = True,
        standardization: bool = True,
        uid: str | None = None,
    ):
        super().__init__("logreg", uid=uid)
        self.reg_param = reg_param
        self.elastic_net_param = elastic_net_param
        self.max_iter = max_iter
        self.fit_intercept = fit_intercept
        self.standardization = standardization

    def get_params(self):
        return {
            "reg_param": self.reg_param,
            "elastic_net_param": self.elastic_net_param,
            "max_iter": self.max_iter,
            "fit_intercept": self.fit_intercept,
            "standardization": self.standardization,
        }

    @staticmethod
    def _mesh_rows(x, y, masks):
        """Pad rows to the execution-mesh multiple (mask-0 padding is inert
        in the mask-weighted solvers) and shard x over the data axis;
        identity when no mesh is active. ``masks`` pads on its LAST axis
        (handles both [N] and [K, N])."""
        from ..parallel.mesh import data_row_multiple, shard_rows_if_active

        x = np.asarray(x, dtype=np.float32)
        y = np.asarray(y, dtype=np.float32)
        masks = np.asarray(masks, dtype=np.float32)
        pad = (-x.shape[0]) % data_row_multiple()
        if pad:
            x = np.pad(x, ((0, pad), (0, 0)))
            y = np.pad(y, (0, pad))
            widths = [(0, 0)] * (masks.ndim - 1) + [(0, pad)]
            masks = np.pad(masks, widths)
        return shard_rows_if_active(x), y, masks

    def fit_arrays(self, x, y, row_mask):
        present = y[row_mask > 0]
        num_classes = max(int(present.max()) + 1 if len(present) else 2, 2)
        x, y, row_mask = self._mesh_rows(x, y, row_mask)
        # both run quasi-Newton; maxIter is the Spark-semantic knob, 1:1
        if num_classes == 2:
            params = fit_logistic_binary(
                x,
                y,
                row_mask,
                float(self.reg_param),
                float(self.elastic_net_param),
                num_iters=self.max_iter,
                fit_intercept=self.fit_intercept,
                standardization=self.standardization,
            )
        else:
            params = fit_logistic_multinomial(
                x,
                y,
                row_mask,
                float(self.reg_param),
                float(self.elastic_net_param),
                num_classes=num_classes,
                num_iters=self.max_iter,
                fit_intercept=self.fit_intercept,
                standardization=self.standardization,
            )
        return LogisticRegressionModel(
            np.asarray(params.weights), np.asarray(params.intercept), num_classes
        )

    # ---- batched sweeps (SURVEY.md §2.6: the reference's driver thread
    # pool becomes a batch axis of one compiled program) -------------------

    _KNOWN_KEYS = frozenset(
        ("reg_param", "elastic_net_param", "fit_intercept", "max_iter",
         "standardization")
    )

    def _static_groups(self, points) -> tuple[dict, list[int]]:
        """Group point indices by their STATIC params (fit_intercept,
        max_iter, standardization) — reg/elastic-net vary freely inside a
        group and batch as GEMM lanes. (Round 1 compared statics against
        the estimator's ctor defaults, so the default grid's max_iter=50
        vs ctor 100 silently disabled batching — every default sweep ran
        24 sequential fits.)"""
        from .base import group_grid_by_statics

        return group_grid_by_statics(
            points, self._KNOWN_KEYS,
            lambda p: (
                bool(p.get("fit_intercept", self.fit_intercept)),
                int(p.get("max_iter", self.max_iter)),
                bool(p.get("standardization", self.standardization)),
            ),
        )

    def _grid_values(self, points) -> tuple[np.ndarray, np.ndarray]:
        regs = np.asarray(
            [p.get("reg_param", self.reg_param) for p in points],
            dtype=np.float32,
        )
        ens = np.asarray(
            [p.get("elastic_net_param", self.elastic_net_param) for p in points],
            dtype=np.float32,
        )
        return regs, ens

    @staticmethod
    def _num_classes(y, any_mask) -> int:
        present = y[any_mask > 0]
        return max(int(present.max()) + 1 if len(present) else 2, 2)

    #: GLM lanes pad onto shape buckets and shard over the mesh's model
    #: axis; the pipelined fold schedule (workflow/cv.py) overlaps tree
    #: fits with these dispatches
    lane_family = "glm"

    def fit_arrays_batched(self, x, y, row_mask, grid_points):
        """One mask, many grid points — same-static groups batch into one
        program each; points with unknown params fit sequentially."""
        return self.fit_arrays_batched_masks(x, y, [row_mask], grid_points)[0]

    def _batched_fit(self, xp, yp, rm, regs, ens, statics, mesh=None):
        """A binary static group's program (sharded under ``mesh``)."""
        fit_intercept, max_iter, standardization = statics
        from ..compiler import bucketing, dispatch
        from ..utils.aot import aot_call

        statics_kw = dict(
            num_iters=max_iter,
            fit_intercept=fit_intercept,
            standardization=standardization,
        )
        if mesh is not None:
            # the sharded sweep: lanes over MODEL_AXIS, rows over
            # DATA_AXIS, on the explicit SweepLayout PartitionSpecs,
            # with fold-level buffer donation (parallel/fit.py)
            from ..parallel.fit import sweep_parallel_fit

            return sweep_parallel_fit(
                fit_logistic_binary_batched,
                "sweep_logistic_binary_sharded", mesh,
                xp, yp, rm, regs, ens, **statics_kw,
            )
        # cross-candidate dedup: every lane of this sweep shares ONE
        # program, and the lane count pads onto a shape bucket so a
        # near-miss sweep (one more grid point, one more fold) reuses
        # the same banked executable instead of compiling its own
        k, (rm, regs, ens) = bucketing.bucket_sweep_lanes(rm, regs, ens)
        # shared-x GEMM sweep (see fit_logistic_binary_batched); the x
        # upload reuses the transfer the DAG fit prefetched, when one
        # is in flight (compiler.dispatch)
        fit_fn = dispatch.donating(
            "logistic_binary_batched", fit_logistic_binary_batched,
            donate_argnums=(3, 4),
            static_argnames=(
                "num_iters", "fit_intercept", "standardization"
            ),
        )
        out = aot_call(
            "logistic_binary_batched", fit_fn,
            (
                dispatch.device_f32(xp), jnp.asarray(yp),
                jnp.asarray(rm), jnp.asarray(regs), jnp.asarray(ens),
            ),
            statics_kw,
        )
        if rm.shape[0] > k:
            from .solvers import GLMParams

            out = GLMParams(
                weights=out.weights[:k], intercept=out.intercept[:k]
            )
        return out

    @staticmethod
    def _multinomial_fit(xp, yp, rm, regs, ens, num_classes, statics, rows):
        """Every lane of one static group in ONE program on the shared
        plane (``solvers.fit_logistic_multinomial_batched``), dispatched
        asynchronously: lanes padded onto a shape bucket, the plane
        through ``dispatch.device_f32``, the program through the
        executable bank. ``rows``: the real rows (``xp`` may be padded
        onto a mesh's row multiple)."""
        from ..compiler import bucketing, dispatch
        from ..utils.aot import aot_call

        fit_intercept, max_iter, standardization = statics
        k, (rm, regs, ens) = bucketing.bucket_sweep_lanes(rm, regs, ens)
        with _tspans.span(
            "glm/fit_dispatch", lanes=k, classes=num_classes,
            rows=int(xp.shape[0]), cols=int(xp.shape[1]), num_iters=max_iter,
            precision=solver_precision(), history=solvers._LBFGS_M,
            ls_steps=len(solvers._LS_STEPS),
        ):
            fit = aot_call(
                "logistic_multinomial_batched",
                fit_logistic_multinomial_batched,
                (
                    dispatch.device_f32(xp), jnp.asarray(yp),
                    jnp.asarray(rm), jnp.asarray(regs), jnp.asarray(ens),
                ),
                dict(num_classes=num_classes, num_iters=max_iter,
                     fit_intercept=fit_intercept,
                     standardization=standardization),
            )
        return _MultinomialSweep(fit, k, rows, max_iter)

    def sweep_eval_batched(self, models_by_fold, x, y, folds, evaluator):
        """Validator hook: every fold lane scored from the class logits
        its fit program computed on the device, only the validation rows of
        a fold's lanes downloaded (one gather a fold). None where a lane
        has no such logits (binary lanes, points fitted one by one): the
        validator then scores by ``predict_arrays``."""
        return score_sweep_stacks(models_by_fold, y, folds, evaluator)

    def sweep_dispatch_masks(self, x, y, masks, grid_points):
        """Dispatch the folds × grid sweep, return a collector closure.

        Each same-(fit_intercept, max_iter, standardization) group batches
        (fold-mask, reg, elastic-net) triples onto the fit axis (shared-x
        GEMM L-BFGS/OWL-QN); points with unknown params fall back to
        sequential fits inside the collector. Binary groups under an
        active execution mesh route through the pjit'd SweepLayout path —
        explicit per-axis PartitionSpecs, donated fold buffers. Dispatch
        is async; the closure materializes the models, so tree-family
        fits can overlap (the pipelined lane schedule in workflow/cv.py)."""
        masks = [np.asarray(m, dtype=np.float32) for m in masks]
        groups, sequential = self._static_groups(grid_points)
        num_classes = self._num_classes(y, np.max(np.stack(masks), axis=0))
        n_masks = len(masks)
        stacked_groups: list[tuple[tuple, list[int], object]] = []
        if groups:
            from ..parallel.mesh import execution_mesh

            mesh = execution_mesh() if num_classes == 2 else None
            if mesh is not None:
                # the sharded path pads + places rows itself — handing it
                # raw host arrays keeps the donated buffers private to
                # one dispatch (a shared pre-sharded x could be consumed
                # out from under the next static group)
                xp, yp, masksp = (
                    np.asarray(x, dtype=np.float32),
                    np.asarray(y, dtype=np.float32),
                    np.stack(masks),
                )
            else:
                xp, yp, masksp = self._mesh_rows(x, y, np.stack(masks))
            for statics, idxs in groups.items():
                pts = [grid_points[i] for i in idxs]
                regs, ens = self._grid_values(pts * n_masks)
                rm = np.repeat(
                    masksp, len(pts), axis=0
                )  # [K, N], mask-major to match regs/ens tiling
                if num_classes == 2:
                    stacked = self._batched_fit(
                        xp, yp, rm, regs, ens, statics, mesh=mesh
                    )
                else:
                    stacked = self._multinomial_fit(
                        xp, yp, rm, regs, ens, num_classes, statics,
                        rows=len(y),
                    )
                stacked_groups.append((idxs, len(pts), stacked))

        def collect() -> list[list]:
            models: list[list] = [
                [None] * len(grid_points) for _ in masks
            ]
            for idxs, n_pts, stacked in stacked_groups:
                sweep = isinstance(stacked, _MultinomialSweep)
                if sweep:
                    w, b = stacked.await_params()
                else:
                    w = np.asarray(stacked.weights)
                    b = np.asarray(stacked.intercept)
                for mi in range(n_masks):
                    for j, i in enumerate(idxs):
                        lane = mi * n_pts + j
                        model = LogisticRegressionModel(
                            w[lane], b[lane], num_classes,
                        )
                        if sweep:
                            model._sweep_stack = stacked
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
