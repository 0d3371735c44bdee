"""Spark's multinomial logistic regression as the batched L-BFGS/OWL-QN
program runs it (``solvers.fit_logistic_multinomial_batched``), against the
plain float32 reference of the benchmark's check (one lane at a time on an
explicitly standardized plane), and the sweep that scores its lanes from
the logits the program computed."""
import hashlib
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.checks import logistic_multiclass_winner as ref  # noqa: E402
from transmogrifai_tpu.models import logistic, solvers  # noqa: E402

GRID = [(reg, en) for reg in (0.001, 0.01, 0.1, 0.2) for en in (0.1, 0.5)]
SEEDS = (0, 1, 2)
CLASSES = 4
#: iterations a lane runs: enough for every lane of these tables to stop
#: moving, so two float32 trajectories of the learner meet at its optimum
ITERS = 200


def _data(seed, n=1200, d=10):
    """A softmax label of shifted, scaled columns; column 6 constant."""
    rng = np.random.default_rng(seed)
    scale = rng.uniform(0.5, 4.0, d)
    x = (rng.normal(size=(n, d)) * scale + rng.uniform(-3, 3, d))
    x = x.astype(np.float32)
    x[:, 6] = 1.25
    w = rng.normal(size=(d, CLASSES)) / scale[:, None]
    y = np.argmax(x @ w + rng.gumbel(size=(n, CLASSES)), axis=1)
    masks = np.stack([rng.random(n) < 0.75, rng.random(n) < 0.9])
    return x, y.astype(np.float32), masks.astype(np.float32)


_FITS: dict = {}


def _softmax(z):
    e = np.exp(z - z.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def _batched(seed):
    """Every grid point on both masks: 16 lanes in one program."""
    if seed not in _FITS:
        x, y, masks = _data(seed)
        rm = np.repeat(masks, len(GRID), axis=0)
        regs = np.asarray([g[0] for g in GRID] * 2, np.float32)
        ens = np.asarray([g[1] for g in GRID] * 2, np.float32)
        _FITS[seed] = solvers.fit_logistic_multinomial_batched(
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(rm),
            jnp.asarray(regs), jnp.asarray(ens), num_classes=CLASSES,
            num_iters=ITERS)
    return _FITS[seed]


@pytest.mark.parametrize("point", range(len(GRID)))
@pytest.mark.parametrize("seed", SEEDS)
def test_batched_lane_matches_the_plain_reference(seed, point):
    """Each lane (its own mask and grid point) reaches the objective the
    reference's own fit of the same learner reaches, predicts the same
    classes, centres its intercepts and pins the constant column."""
    x, y, masks = _data(seed)
    fit = _batched(seed)
    mask_i = point % 2
    lane = mask_i * len(GRID) + point
    reg, en = GRID[point]
    params = {"reg_param": reg, "elastic_net_param": en, "max_iter": ITERS}
    own = ref.lane(x, y.astype(np.int64), masks[mask_i], params, CLASSES)
    w = np.asarray(fit.weights[lane], np.float64)
    b = np.asarray(fit.intercept[lane], np.float64)
    beta, b_std = ref.standardized_point(own, w, b)
    f_prog = ref.objective(own, y.astype(np.int64), masks[mask_i], beta, b_std)
    # both stop where float32 finds no lower objective: within 3e-6 of
    # each other's objective on these tables. At the weakest penalty the
    # objective is flat along some directions, where the coefficients and
    # intercepts part by up to 5e-2 at the same objective, so the model is
    # compared by what it predicts
    assert abs(f_prog - own["F"]) <= 1e-5 * abs(own["F"])
    assert not w[6].any() and abs(b.sum()) < 1e-5
    z_prog = x @ w + b
    z_ref = x @ own["weights"] + own["intercept"]
    assert (z_prog.argmax(1) == z_ref.argmax(1)).mean() > 0.99
    assert np.abs(_softmax(z_prog) - _softmax(z_ref)).max() < 2e-2
    # the program's own logits are the same model's, up to a constant a row
    z_dev = np.asarray(fit.logits[lane], np.float64).T
    assert np.abs((z_dev - z_dev.mean(1, keepdims=True))
                  - (z_prog - z_prog.mean(1, keepdims=True))).max() < 1e-3
    assert 0 < int(fit.iters_moved[lane]) < ITERS


def test_single_fit_is_the_one_lane_program():
    """``LogisticRegression.fit_arrays`` over more than two classes is the
    K = 1 lane of the batched program: the same learner, the same budget."""
    x, y, masks = _data(5)
    est = logistic.LogisticRegression(
        reg_param=0.01, elastic_net_param=0.5, max_iter=50)
    model = est.fit_arrays(x, y, masks[0])
    one = solvers.fit_logistic_multinomial_batched(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(masks[:1]),
        jnp.asarray([0.01], jnp.float32), jnp.asarray([0.5], jnp.float32),
        num_classes=CLASSES, num_iters=50)
    np.testing.assert_allclose(model.weights, np.asarray(one.weights[0]),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(model.intercept, np.asarray(one.intercept[0]),
                               rtol=1e-6, atol=1e-7)


#: sha256 (first 32 hex) of the binary fit's float32 outputs on fixed seeds
#: (``_binary_digest``), as the program gave them before the multinomial
#: learner shared its moment code: the binary fit must not move a bit
BINARY_DIGESTS = {
    (0, True, True): "d169098ec7ac74f631162b36bb9b1031",
    (0, False, True): "4a236408f24c34628a3332609d2883dd",
    (0, True, False): "620f890b59fc1086b4d7029b603dc45e",
    (1, True, True): "349ecd3adaa8f785299f6e21c57b8012",
    (1, False, True): "4ab359a7c9c57b25bd84958fe0439e04",
    (1, True, False): "4153e5d8fe4b52309fd7a71d0daa5fa8",
    (2, True, True): "5ec0526b5b155c5b027c8bd25023075c",
    (2, False, True): "4a80fa3b458d6a3e31c85dd85b5de63f",
    (2, True, False): "1903340d011c9ac6625173ea4e3867a9",
}


def _binary_digest(seed, fit_intercept, standardization):
    rng = np.random.default_rng(seed)
    n, d = 96, 6
    x = rng.normal(size=(n, d)).astype(np.float32) * np.array(
        [1, 3, 0.5, 10, 1, 1], np.float32) + 2.0
    x[:, 4] = 1.5
    y = (rng.random(n) < 1 / (1 + np.exp(
        -(x[:, 0] - x[:, 1] + 0.2 * x[:, 3] - 2)))).astype(np.float32)
    masks = np.stack([rng.random(n) < 0.7, rng.random(n) < 0.8,
                      np.ones(n, bool)]).astype(np.float32)
    regs = np.array([0.01, 0.1, 0.001], np.float32)
    ens = np.array([0.0, 0.5, 1.0], np.float32)
    out = solvers.fit_logistic_binary_batched(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(masks), jnp.asarray(regs),
        jnp.asarray(ens), num_iters=30, fit_intercept=fit_intercept,
        standardization=standardization)
    one = solvers.fit_logistic_binary(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(masks[0]), 0.01, 0.0,
        num_iters=30, fit_intercept=fit_intercept,
        standardization=standardization)
    h = hashlib.sha256()
    for a in (out.weights, out.intercept, one.weights, one.intercept):
        h.update(np.asarray(a, np.float32).tobytes())
    return h.hexdigest()[:32]


@pytest.mark.parametrize("case", sorted(BINARY_DIGESTS))
def test_binary_fit_unchanged_bit_for_bit(case):
    assert _binary_digest(*case) == BINARY_DIGESTS[case]


def _selector_data(n=2400, d=9, seed=11):
    rng = np.random.default_rng(seed)
    x = np.asfortranarray(
        (rng.normal(size=(n, d)) * 2 + 1).astype(np.float32))
    w = rng.normal(size=(d, 5))
    y = np.argmax(x @ w + rng.gumbel(size=(n, 5)), axis=1).astype(np.float32)
    return x, y


def _select(x, y):
    from transmogrifai_tpu.selector import validators
    from transmogrifai_tpu.selector.model_selector import (
        MultiClassificationModelSelector, make_candidates,
    )

    sel = MultiClassificationModelSelector(
        seed=3, models=make_candidates(
            "MultiClassification", ["OpLogisticRegression"]),
        validator=validators.TrainValidationSplit(0.75, seed=3))
    return sel.fit_arrays(x, y, np.ones(len(y), np.float32))


def test_sweep_scores_lanes_from_device_logits(monkeypatch, tmp_path):
    """The multiclass selector's default LR grid runs as ONE program on the
    shared plane, every lane is scored from the program's logits (no host
    ``predict_arrays``, no ``x[val_idx]``), the refit's train evaluation
    takes the winner's lane, and the metrics are those the host's float64
    predict gives, to the rows whose arg max sits on a float32 tie."""
    from transmogrifai_tpu.telemetry import metrics, spans

    # the sweep's programs bank where this test puts them, not in the
    # checkout's cache
    monkeypatch.setenv("TPTPU_COMPILE_CACHE", str(tmp_path))
    x, y = _selector_data()
    before = dict(metrics.REGISTRY.source_snapshots()["glm"])

    def no_host_predict(self, xv):
        raise AssertionError("a GLM lane was scored on the host")

    with monkeypatch.context() as m:
        m.setattr(logistic.LogisticRegressionModel, "predict_arrays",
                  no_host_predict)
        got = _select(x, y)
    events = [e for e in spans.snapshot_events()
              if e["name"].startswith("glm/")]
    dispatches = [e for e in events if e["name"] == "glm/fit_dispatch"]
    assert len(dispatches) >= 1
    last = dispatches[-1]["args"]
    assert last["lanes"] == 16 and last["classes"] == 5
    assert last["precision"] == "highest" and last["num_iters"] == 50
    counted = [e["args"] for e in events if e["name"] == "glm/await_outputs"
               and "iters_scanned" in e.get("args", {})]
    assert counted and counted[-1]["iters_scanned"] == 16 * 50
    after = metrics.REGISTRY.source_snapshots()["glm"]
    assert after["itersScanned"] - before["itersScanned"] >= 16 * 50
    assert 0 < after["itersMoved"] - before["itersMoved"] <= 16 * 50
    # the same sweep with the lanes scored by the host's float64 predict
    with monkeypatch.context() as m:
        m.setattr(logistic.LogisticRegression, "sweep_eval_batched",
                  lambda self, *a: None)
        host = _select(x, y)
    ours = [r["metricValues"][0] for r in got.summary["validationResults"]]
    theirs = [r["metricValues"][0] for r in host.summary["validationResults"]]
    np.testing.assert_allclose(ours, theirs, atol=2e-3)
    assert got.summary["bestGrid"] == host.summary["bestGrid"]
    assert got.summary["trainEvaluation"]["F1"] == pytest.approx(
        host.summary["trainEvaluation"]["F1"], abs=2e-3)
    assert not hasattr(got.best_model, "_sweep_stack")
