"""The sharded CV candidate sweep (parallel/sweep.py + workflow/cv.py).

Tier-1 legs run on any device count (degenerate 1x1 mesh); the mesh legs
need the forced 8-device CPU mesh the CI ``sweep`` job provides
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and skip
elsewhere.
"""
import numpy as np
import pytest

import jax

import transmogrifai_tpu.types as T
from transmogrifai_tpu.compiler import bucketing
from transmogrifai_tpu.dataset import Dataset
from transmogrifai_tpu.evaluators.base import Evaluator
from transmogrifai_tpu.features import from_dataset
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.models.solvers import (
    fit_linear_batched,
    fit_logistic_binary_batched,
)
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.parallel.fit import sweep_parallel_fit
from transmogrifai_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    make_mesh,
    use_execution_mesh,
)
from transmogrifai_tpu.parallel.sweep import SweepLayout, mesh_lane_capacity
from transmogrifai_tpu.resilience.distributed import HostLostError
from transmogrifai_tpu.selector import BinaryClassificationModelSelector
from transmogrifai_tpu.types.columns import column_from_values
from transmogrifai_tpu.workflow import cv as cv_mod
from transmogrifai_tpu.workflow.cv import workflow_cv_results

EIGHT = len(jax.devices()) >= 8
needs_mesh = pytest.mark.skipif(
    not EIGHT, reason="needs the forced 8-device CPU mesh (sweep CI job)"
)


def _sweep_data(rows=48, dim=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, dim)).astype(np.float32)
    w = rng.normal(size=dim)
    y_lin = (x @ w + 0.1 * rng.normal(size=rows)).astype(np.float32)
    y_log = (x @ w > 0).astype(np.float32)
    return x, y_lin, y_log


def _lanes(k, rows, seed=1):
    rng = np.random.default_rng(seed)
    masks = (rng.random((k, rows)) > 0.25).astype(np.float32)
    # floor at 0.01: an unregularized logistic lane on separable labels
    # diverges, and divergence amplifies fp-ordering noise past any
    # bit-parity contract — parity is only meaningful on well-posed lanes
    regs = np.linspace(0.01, 0.3, k).astype(np.float32)
    ens = np.zeros(k, dtype=np.float32)
    return masks, regs, ens


# ==========================================================================
# bucketing: the mesh-aware lane bucket
# ==========================================================================
def test_mesh_lane_bucket_divisible_by_mesh():
    # plain pow2 ladder when the mesh axis divides it already
    assert bucketing.mesh_lane_bucket(5, 1) == bucketing.lane_bucket(5)
    assert bucketing.mesh_lane_bucket(5, 8) == 8
    assert bucketing.mesh_lane_bucket(9, 8) == 16
    assert bucketing.mesh_lane_bucket(64, 8) == 64
    # past the pow2 ladder the 32-multiples stay divisible by 8
    b = bucketing.mesh_lane_bucket(65, 8)
    assert b >= 65 and b % 8 == 0
    # the invariant that lets SweepLayout shard the lane axis evenly
    for k in range(1, 130):
        for m in (1, 2, 4, 8):
            b = bucketing.mesh_lane_bucket(k, m)
            assert b >= k and b % m == 0, (k, m, b)


def test_mesh_lane_bucket_when_bucketing_disabled(monkeypatch):
    monkeypatch.setenv("TPTPU_LANE_BUCKETS", "0")
    # degrades to ceil-to-multiple: no pow2 padding, still shardable
    assert bucketing.mesh_lane_bucket(5, 8) == 8
    assert bucketing.mesh_lane_bucket(9, 8) == 16
    assert bucketing.mesh_lane_bucket(7, 1) == 7


# ==========================================================================
# SweepLayout: the explicit per-axis PartitionSpecs
# ==========================================================================
def test_sweep_layout_partition_specs():
    from jax.sharding import PartitionSpec as P

    layout = SweepLayout()
    # plane/target: rows over the data axis, replicated over model
    assert layout.plane_spec() == P(DATA_AXIS, None)
    assert layout.target_spec() == P(DATA_AXIS)
    # lane tensors: candidate lanes over the model axis
    assert layout.lane_mask_spec() == P(MODEL_AXIS, DATA_AXIS)
    assert layout.lane_spec() == P(MODEL_AXIS)
    # fold outputs come back lane-sharded, gather-free
    assert layout.out_weights_spec() == P(MODEL_AXIS, None)
    assert layout.out_lane_spec() == P(MODEL_AXIS)


def test_mesh_lane_capacity():
    assert mesh_lane_capacity(None) == 1
    mesh = make_mesh(n_data=1, n_model=1)
    assert mesh_lane_capacity(mesh) == 1


# ==========================================================================
# sharded-vs-single parity (degenerate 1x1 mesh; any device count)
# ==========================================================================
def test_sweep_parallel_fit_parity_single_device():
    x, y_lin, y_log = _sweep_data()
    masks, regs, ens = _lanes(3, len(y_lin))
    mesh = make_mesh(n_data=1, n_model=1)
    statics = dict(num_iters=60, fit_intercept=True)

    out = sweep_parallel_fit(
        fit_linear_batched, "t_sweep_lin_1x1", mesh,
        x, y_lin, masks, regs, ens, **statics,
    )
    ref = fit_linear_batched(x, y_lin, masks, regs, ens, **statics)
    assert out.weights.shape == (3, x.shape[1])
    np.testing.assert_allclose(
        np.asarray(out.weights), np.asarray(ref.weights), atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(out.intercept), np.asarray(ref.intercept), atol=1e-6
    )

    out = sweep_parallel_fit(
        fit_logistic_binary_batched, "t_sweep_log_1x1", mesh,
        x, y_log, masks, regs, ens, standardization=True, **statics,
    )
    ref = fit_logistic_binary_batched(
        x, y_log, masks, regs, ens, standardization=True, **statics
    )
    np.testing.assert_allclose(
        np.asarray(out.weights), np.asarray(ref.weights), atol=1e-6
    )


# ==========================================================================
# TPJ003: fold-level donation proven in the lowered sweep program
# ==========================================================================
def test_sweep_programs_pass_tpj_donation_gate():
    from transmogrifai_tpu.analysis.program import audit_programs

    rep = audit_programs(
        names=["sweep_linear_sharded", "sweep_logistic_binary_sharded"],
        include_ast=False,
    )
    findings = [f.render() for f in rep.findings]
    assert not any("TPJ003" in f for f in findings), findings


def test_sweep_lowering_carries_donation_aliasing():
    # the contract behind the TPJ003 gate, checked directly: the lowered
    # StableHLO of the sharded sweep marks input->output buffer aliases
    from transmogrifai_tpu.parallel.sweep import program_trace_specs

    for spec in program_trace_specs():
        args, statics = spec["build"](spec["buckets"][0])
        text = spec["fn"].lower(*args, **statics).as_text()
        assert (
            "tf.aliasing_output" in text or "jax.buffer_donor" in text
        ), f"{spec['name']}: no aliasing in lowered IR"


# ==========================================================================
# lane-granular failure isolation (satellite: no O(families x points)
# rebuild; surviving lanes keep their results)
# ==========================================================================
class _BoomModel:
    def predict_arrays(self, x):
        raise RuntimeError("boom lane")


class _OkModel:
    def __init__(self, v):
        self.v = v

    def predict_arrays(self, x):
        return np.full(len(x), self.v), None, None


class _Eval(Evaluator):
    is_larger_better = False

    def evaluate_arrays(self, y, pred, prob):
        return {"err": float(np.mean(np.abs(y - pred)))}

    def metric_of(self, m):
        return m["err"]


class _Est:
    def __init__(self, uid):
        self.uid = uid


def test_eval_lanes_isolates_one_bad_lane():
    est = _Est("estA")
    points = [{"p": i} for i in range(3)]
    models = [_OkModel(0.0), _BoomModel(), _OkModel(1.0)]
    per_candidate: dict = {}
    failed_lanes: set = set()
    xv = np.zeros((4, 2), np.float32)
    yv = np.zeros(4)
    cv_mod._eval_lanes(
        est, points, models, xv, yv, _Eval(), per_candidate, failed_lanes
    )
    # the bad lane lost ONLY its own entry; neighbors kept theirs
    assert ("estA", 1) not in per_candidate
    assert failed_lanes == {("estA", 1)}
    assert per_candidate[("estA", 0)].metric_values == [0.0]
    assert per_candidate[("estA", 2)].metric_values == [1.0]
    # later folds skip the poisoned lane instead of re-raising
    cv_mod._eval_lanes(
        est, points, models, xv, yv, _Eval(), per_candidate, failed_lanes
    )
    assert len(per_candidate[("estA", 0)].metric_values) == 2
    assert ("estA", 1) not in per_candidate


def test_drop_family_pops_only_its_own_lanes():
    from transmogrifai_tpu.selector.validators import CandidateResult

    per_candidate = {
        (uid, gi): CandidateResult(
            model_name="m", model_uid=uid, grid={}, metric_values=[0.1]
        )
        for uid in ("a", "b")
        for gi in range(4)
    }
    failed: set = set()
    cv_mod._drop_family(
        _Est("a"), [{}] * 4, RuntimeError("x"), per_candidate, failed,
        None, 0, 0.0, 10,
    )
    assert failed == {"a"}
    assert set(per_candidate) == {("b", gi) for gi in range(4)}


def test_validator_sweep_scores_nan_for_failed_lane():
    """validators._sweep_family: one lane's scoring failure is a NaN
    metric (filtered by ``best``), not a family exclusion."""
    from transmogrifai_tpu.selector.validators import CrossValidator

    class _FlakyPredictEst(LogisticRegression):
        # no batched hooks: force the per-model predict loop
        sweep_dispatch_masks = None
        fit_arrays_batched_masks = None
        fit_arrays_batched = None

        def fit_arrays(self, x, y, row_mask):
            model = super().fit_arrays(x, y, row_mask)
            if self.reg_param and self.reg_param > 0.2:
                model.predict_arrays = _BoomModel().predict_arrays
            return model

    x, _, y = _sweep_data(rows=64)
    v = CrossValidator(num_folds=2, seed=0)
    folds = v.split_masks(y.astype(np.float64))
    from transmogrifai_tpu.evaluators import BinaryClassificationEvaluator

    results = v._sweep_family(
        _FlakyPredictEst(),
        [{"reg_param": 0.0}, {"reg_param": 0.3}],
        folds, x, y.astype(np.float64),
        BinaryClassificationEvaluator(),
    )
    assert len(results) == 2
    assert np.isfinite(results[0].metric_mean)
    assert np.isnan(results[1].metric_mean)  # poisoned lane, isolated
    best = v.best(results, BinaryClassificationEvaluator())
    assert best.grid == {"reg_param": 0.0}


# ==========================================================================
# fold-resume stash: < 1 fold of rework after a mid-sweep host loss
# ==========================================================================
def _mini_binary_graph(n=120, seed=0):
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    label = (x1 + 0.5 * x2 + 0.3 * rng.normal(size=n) > 0).astype(float)
    ds = Dataset.of({
        "label": column_from_values(T.RealNN, label),
        "x1": column_from_values(T.Real, x1),
        "x2": column_from_values(T.Real, x2),
    })
    resp, preds = from_dataset(ds, response="label")
    vec = transmogrify(list(preds))
    return ds, resp, vec


def _flaky_family(calls):
    class Flaky(LogisticRegression):
        # plain host-side family: fold-count bookkeeping stays exact
        sweep_dispatch_masks = None
        fit_arrays_batched_masks = None

        def fit_arrays_batched(self, x, y, row_mask, grid_points):
            calls["folds"] += 1
            if calls["folds"] == 2 and not calls["raised"]:
                calls["raised"] = True
                raise HostLostError(host=1, reason="injected mid-sweep")
            return [
                LogisticRegression(**{
                    **self.get_params(), **p
                }).fit_arrays(x, y, row_mask)
                for p in grid_points
            ]

    return Flaky


def test_host_loss_resumes_with_less_than_one_fold_rework():
    ds, resp, vec = _mini_binary_graph()
    calls = {"folds": 0, "raised": False}
    selector = BinaryClassificationModelSelector(
        models=[(_flaky_family(calls)(), {"reg_param": [0.0, 0.1]})],
        num_folds=2, seed=3,
    )
    selector.set_input(resp, vec)

    with pytest.raises(HostLostError):
        workflow_cv_results(selector, ds)
    assert calls["folds"] == 2  # fold 0 done, fold 1 died mid-sweep

    # the failover-loop re-entry (workflow/workflow.py): fold 0 is NOT
    # re-dispatched — the stash replays it, fold 1 alone re-runs
    results = workflow_cv_results(selector, ds)
    assert calls["folds"] == 3  # < 1 fold of rework
    assert len(results) == 2
    assert all(len(r.metric_values) == 2 for r in results)
    # normal completion drops the stash: a fresh run starts at fold 0
    assert not any(
        key[0] == selector.uid for key in cv_mod._RESUME
    )


def test_non_host_loss_clears_stash():
    ds, resp, vec = _mini_binary_graph(seed=1)
    calls = {"folds": 0}

    class Dies(LogisticRegression):
        sweep_dispatch_masks = None
        fit_arrays_batched_masks = None

        def fit_arrays_batched(self, x, y, row_mask, grid_points):
            calls["folds"] += 1
            if calls["folds"] == 2:
                # fold 0 is already stashed by now — a non-host-loss
                # unwind (BaseException included) must drop that stash
                raise KeyboardInterrupt
            return [
                LogisticRegression(**{
                    **self.get_params(), **p
                }).fit_arrays(x, y, row_mask)
                for p in grid_points
            ]

    selector = BinaryClassificationModelSelector(
        models=[(Dies(), {"reg_param": [0.0]})], num_folds=2, seed=3,
    )
    selector.set_input(resp, vec)
    with pytest.raises(KeyboardInterrupt):
        workflow_cv_results(selector, ds)
    assert calls["folds"] == 2
    assert not any(key[0] == selector.uid for key in cv_mod._RESUME)


# ==========================================================================
# mesh legs: the forced 8-device CPU mesh (sweep CI job)
# ==========================================================================
@needs_mesh
def test_sharded_parity_across_lane_bucket_boundary():
    """Bit-parity twins across the pow2 bucket edge: k=63 pads to the
    64-lane bucket, k=64 lands exact — both must match the single-device
    sweep (logistic bit-exact; linear within GEMM-tiling tolerance)."""
    x, y_lin, y_log = _sweep_data(rows=64, dim=5)
    mesh = make_mesh(n_data=1, n_model=8)
    statics = dict(num_iters=40, fit_intercept=True)
    for k in (63, 64):  # padded twin / unpadded twin
        masks, regs, ens = _lanes(k, len(y_lin), seed=k)
        sh = sweep_parallel_fit(
            fit_logistic_binary_batched, f"t_sweep_log_8_{k}", mesh,
            x, y_log, masks, regs, ens, standardization=True, **statics,
        )
        ref = fit_logistic_binary_batched(
            x, y_log, masks, regs, ens, standardization=True, **statics
        )
        assert np.asarray(sh.weights).shape == (k, 5)
        assert np.array_equal(
            np.asarray(sh.weights), np.asarray(ref.weights)
        ), f"logistic k={k}: sharded sweep not bit-identical"
        assert np.array_equal(
            np.asarray(sh.intercept), np.asarray(ref.intercept)
        )

        sh = sweep_parallel_fit(
            fit_linear_batched, f"t_sweep_lin_8_{k}", mesh,
            x, y_lin, masks, regs, ens, **statics,
        )
        ref = fit_linear_batched(x, y_lin, masks, regs, ens, **statics)
        np.testing.assert_allclose(
            np.asarray(sh.weights), np.asarray(ref.weights),
            atol=2e-6, rtol=1e-5,
        )


@needs_mesh
def test_estimator_sweep_sharded_vs_single_parity():
    """The full estimator path (sweep_dispatch_masks -> SweepLayout pjit)
    against the mesh-free path, via the A/B parity lever."""
    x, _, y = _sweep_data(rows=80, dim=4)
    masks = [
        np.ones(80, np.float32),
        (np.arange(80) % 2).astype(np.float32),
    ]
    pts = [{"reg_param": float(r)} for r in np.linspace(0.0, 0.2, 5)]
    mesh = make_mesh(n_data=1, n_model=8)
    with use_execution_mesh(mesh):
        sharded = LogisticRegression().fit_arrays_batched_masks(
            x, y.astype(np.float64), masks, pts
        )
    with use_execution_mesh(None):
        single = LogisticRegression().fit_arrays_batched_masks(
            x, y.astype(np.float64), masks, pts
        )
    for mi in range(2):
        for gi in range(5):
            assert np.array_equal(
                sharded[mi][gi].weights, single[mi][gi].weights
            ), f"mask {mi} point {gi} diverged"


@needs_mesh
def test_host_loss_mid_sharded_sweep_failover():
    """Seeded host loss during the SHARDED fold loop: the controller
    declares the host dead, the workflow-style failover loop re-enters,
    and the stash holds rework under one fold — with the collective
    tapes reconciling clean afterwards."""
    from transmogrifai_tpu.analysis import spmd as SP
    from transmogrifai_tpu.parallel import guarded as G
    from transmogrifai_tpu.resilience.distributed import (
        FailoverController,
        HeartbeatConfig,
        installed_controller,
    )

    ds, resp, vec = _mini_binary_graph(seed=2)
    calls = {"folds": 0, "raised": False}
    selector = BinaryClassificationModelSelector(
        models=[
            (_flaky_family(calls)(), {"reg_param": [0.0, 0.1]}),
            (LogisticRegression(), {"reg_param": [0.0, 0.05, 0.1]}),
        ],
        num_folds=2, seed=3,
    )
    selector.set_input(resp, vec)
    mesh = make_mesh(n_data=1, n_model=8)
    ctrl = FailoverController(
        n_hosts=4, config=HeartbeatConfig(clock=lambda: 0.0)
    ).bind(mesh)

    G.set_tracing(True)
    try:
        with installed_controller(ctrl), use_execution_mesh(mesh):
            results = None
            while results is None:
                try:
                    results = workflow_cv_results(selector, ds)
                except HostLostError as e:
                    ctrl.failover(e)
    finally:
        G.set_tracing(False)

    # < 1 fold of rework: fold 0 (2 dispatches incl. the killed fold-1
    # attempt) + ONLY fold 1 again on re-entry
    assert calls["folds"] == 3
    assert ctrl.counters["hostsLost"] == 1
    assert len(results) == 5
    # per-host collective tapes reconcile against the static seam census
    static = SP.audit_spmd()
    seams: dict = {}
    for rel, names in (static.data.get("spmdSeams") or {}).items():
        for name, linenos in names.items():
            seams.setdefault(name, []).extend(
                f"{rel}:{ln}" for ln in linenos
            )
    recon = SP.reconcile_collective_orders(G.collective_tapes(), seams)
    rec_data = recon.data["reconciliation"]
    assert rec_data["tapesAgree"] and rec_data["explained"], rec_data
