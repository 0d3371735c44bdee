"""Spark's generalized linear regression as one K-lane IRLS program
(``solvers.fit_glr_irls_batched``) against a plain float64 numpy IRLS of
the same learner, on seeded data with a column constant on every row and a
column whose mean is 1e3: each (family, link) pair of the regression
selector's grid at two penalties, Spark's closed form and start, frozen
lanes, lanes independent of their neighbours, and the selector's refit
taken from the sweep's stack."""
import numpy as np
import pytest

import jax.numpy as jnp

from transmogrifai_tpu.models import glm, solvers
from transmogrifai_tpu.models.glm import GeneralizedLinearRegression
from transmogrifai_tpu.telemetry import spans as tspans

PAIRS = [("gaussian", "identity"), ("gaussian", "log"),
         ("poisson", "identity"), ("poisson", "log")]
LAMS = (0.001, 0.1)


# -------------------------------------------- the learner in float64 numpy
def _unlink(eta, link):
    return eta if link == "identity" else np.exp(eta)


def _link(mu, link):
    return mu if link == "identity" else np.log(mu)


def _wls(x, z, w, lam, standardize):
    """Spark's WeightedLeastSquares with an intercept, in float64."""
    sw = w.sum()
    m = w @ x / sw
    zbar = w @ z / sw
    xc = x - m
    cov = (xc * w[:, None]).T @ xc / sw
    cz = (xc * w[:, None]).T @ (z - zbar) / sw
    const = np.ptp(x, axis=0) == 0
    safe = np.where(const, 1.0, np.sqrt(np.diag(cov)))
    zstd = np.sqrt(w @ (z - zbar) ** 2 / sw)
    pen = lam / zstd if standardize else lam / safe ** 2
    a = np.where(const[:, None] | const[None, :], 0.0,
                 cov / np.outer(safe, safe))
    a += np.diag(np.where(const, 1.0, pen))
    gamma = np.linalg.solve(a, np.where(const, 0.0, cz / safe))
    beta = np.where(const, 0.0, gamma / safe)
    return beta, zbar - m @ beta


def _irls(x, y, family, link, lam, max_iter=50, tol=1e-6):
    """(β, b, iterations) by the learner, Spark's stop at tol exactly."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    beta, b = _wls(x, _link(y, link), np.ones(len(y)), lam, True)
    if (family, link) == ("gaussian", "identity"):
        return beta, b, 0
    for it in range(1, max_iter + 1):
        eta = x @ beta + b
        mu = _unlink(eta, link)
        if family == "poisson":
            mu = np.maximum(mu, solvers.GLR_EPS)
        gp = np.ones_like(mu) if link == "identity" else 1.0 / mu
        var = np.ones_like(mu) if family == "gaussian" else mu
        nbeta, nb = _wls(x, eta + (y - mu) * gp, 1.0 / (gp * gp * var), lam,
                         False)
        step = max(np.abs(nbeta - beta).max(), abs(nb - b))
        beta, b = nbeta, nb
        if step < tol:
            return beta, b, it
    return beta, b, max_iter


# ------------------------------------------------------------------ data
@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(42)
    n, d = 2048, 12
    x = rng.normal(size=(n, d))
    x[:, 3] = 2.5                                  # constant on every row
    x[:, 5] = 1e3 + rng.normal(size=n)             # mean 1e3
    x[:, 7] = rng.random(n) < 0.3
    w = rng.normal(size=d) * 0.3
    w[3] = 0.0
    mu = 20.0 + (x - x.mean(axis=0)) @ w
    y = mu * rng.gamma(4.0, 0.25, size=n)
    masks = np.stack([np.ones(n), rng.random(n) < 0.75]).astype(np.float32)
    return x.astype(np.float32), y.astype(np.float32), masks


def _program(data, lanes, max_iter=50, **kw):
    """``lanes``: [(family, link, λ, mask index)]."""
    x, y, masks = data
    fam = [solvers.GLM_FAMILIES[f] for f, _l, _r, _m in lanes]
    lnk = [solvers.GLM_LINKS[l] for _f, l, _r, _m in lanes]
    return solvers.fit_glr_irls_batched(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(masks),
        jnp.asarray([m for *_flr, m in lanes], jnp.int32),
        jnp.asarray(fam, jnp.int32), jnp.asarray(lnk, jnp.int32),
        jnp.asarray([r for _f, _l, r, _m in lanes], jnp.float32),
        jnp.full(len(lanes), 1e-6, jnp.float32), max_iter=max_iter, **kw)


LANES = [(f, l, lam, m) for f, l in PAIRS for lam in LAMS for m in (0, 1)]


@pytest.fixture(scope="module")
def sweep(data):
    return _program(data, LANES)


def _close(fit, k, beta, b, scale=1.0):
    np.testing.assert_allclose(np.asarray(fit.weights[k]), beta,
                               atol=3e-5 * scale)
    assert abs(float(fit.intercept[k]) - b) <= 2e-5 * max(1.0, abs(b))


@pytest.mark.parametrize("lam", LAMS)
@pytest.mark.parametrize("family,link", PAIRS)
def test_a_lane_is_the_float64_learner(data, sweep, family, link, lam):
    x, y, masks = data
    k = LANES.index((family, link, lam, 1))
    rows = masks[1] > 0
    beta, b, iters = _irls(x[rows], y[rows], family, link, lam)
    _close(sweep, k, beta, b)
    got = int(sweep.iters[k])
    if (family, link) == ("gaussian", "identity"):
        assert got == 0 and not sweep.stop_tol[k] and not sweep.stop_floor[k]
    else:
        # the float32 floor may stop a lane a few iterations before
        # Spark's absolute tol would
        assert 1 <= got <= iters and iters - got <= 4
        assert bool(sweep.stop_tol[k]) or bool(sweep.stop_floor[k])
    assert int(sweep.rows_projected[k]) == 0
    assert int(sweep.rows_nonpositive[k]) == 0
    mu = np.asarray(sweep.mu[k])
    np.testing.assert_allclose(
        mu, _unlink(x.astype(np.float64) @ beta + b, link), rtol=2e-5)


def test_gaussian_identity_is_the_closed_form_normal_equations(data, sweep):
    """Spark's closed form: one solve of the ridge normal equations on the
    standardized columns and label, the constant column at 0."""
    x, y, masks = data
    xd, yd = x.astype(np.float64), y.astype(np.float64)
    mu, sd = xd.mean(axis=0), xd.std(axis=0)
    keep = sd > 0
    xs = (xd[:, keep] - mu[keep]) / sd[keep]
    lam = 0.1
    a = xs.T @ xs / len(yd) + lam / yd.std() * np.eye(keep.sum())
    gamma = np.linalg.solve(a, xs.T @ (yd - yd.mean()) / len(yd))
    beta = np.zeros(x.shape[1])
    beta[keep] = gamma / sd[keep]
    k = LANES.index(("gaussian", "identity", lam, 0))
    _close(sweep, k, beta, yd.mean() - mu @ beta)
    assert float(sweep.weights[k][3]) == 0.0


def test_the_start_is_sparks(data):
    """With no iteration every lane is FamilyAndLink.initialize's start:
    one standardized solve of g(y) at unit weights."""
    x, y, masks = data
    fit = _program(data, LANES, max_iter=0)
    assert int(fit.iterations) == 0 and not np.asarray(fit.iters).any()
    for k, (family, link, lam, m) in enumerate(LANES):
        rows = masks[m] > 0
        beta, b = _wls(x[rows].astype(np.float64),
                       _link(y[rows].astype(np.float64), link),
                       np.ones(rows.sum()), lam, True)
        _close(fit, k, beta, b)


def test_a_stopped_lane_stays_frozen(data, sweep):
    """Lanes stop at different iterations; a lane that stopped keeps its
    answer while the program runs on, and gives what it gives alone."""
    iters = np.asarray(sweep.iters)
    assert iters.max() == int(sweep.iterations) > iters[iters > 0].min()
    k = int(np.argmin(np.where(iters > 0, iters, 99)))
    alone = _program(data, [LANES[k]])
    assert int(alone.iters[0]) == int(alone.iterations) == iters[k]
    np.testing.assert_allclose(np.asarray(alone.weights[0]),
                               np.asarray(sweep.weights[k]), atol=1e-6)


def test_a_lane_does_not_depend_on_its_neighbours(data, sweep):
    """The same lane among fifteen others and among two others: the same
    iterations and the same answer to float32 rounding."""
    k = LANES.index(("poisson", "log", 0.001, 1))
    few = _program(data, [LANES[0], LANES[k], LANES[5]])
    assert int(few.iters[1]) == int(sweep.iters[k])
    np.testing.assert_allclose(np.asarray(few.weights[1]),
                               np.asarray(sweep.weights[k]), atol=1e-6)
    assert abs(float(few.intercept[1]) - float(sweep.intercept[k])) < 1e-4


def test_projection_is_counted(data):
    """A poisson/identity lane whose linear mean crosses 0 on some rows has
    them projected to ε, and counts them."""
    x, y, masks = data
    y_low = np.where(x[:, 0] > 1.0, 0.05, y).astype(np.float32)
    fit = _program((x, y_low, masks), [("poisson", "identity", 0.001, 0)],
                   max_iter=3)
    assert int(fit.rows_projected[0]) > 0
    assert np.isfinite(np.asarray(fit.weights)).all()


def test_fit_arrays_is_the_one_lane_program(data, sweep):
    x, y, masks = data
    est = GeneralizedLinearRegression(family="poisson", link="log",
                                      reg_param=0.1, max_iter=50)
    model = est.fit_arrays(x, y, masks[1])
    k = LANES.index(("poisson", "log", 0.1, 1))
    np.testing.assert_allclose(model.weights, np.asarray(sweep.weights[k]),
                               atol=1e-6)
    mu, _, _ = model.predict_arrays(x[:5])
    np.testing.assert_allclose(mu, np.asarray(sweep.mu[k][:5]), rtol=1e-5)


def test_default_grid_is_the_regression_selectors_glr_grid():
    from transmogrifai_tpu.selector.model_selector import _default_grid_for
    from transmogrifai_tpu.selector.validators import expand_grid

    grid = _default_grid_for(GeneralizedLinearRegression)
    assert grid == {
        "fit_intercept": [True], "family": ["gaussian", "poisson"],
        "link": ["identity", "log"], "max_iter": [50],
        "reg_param": [0.001, 0.01, 0.1, 0.2], "tol": [1e-6],
    }
    assert len(expand_grid(grid)) == 16


def test_the_selector_refit_takes_the_winners_lane(data, monkeypatch,
                                                   tmp_path):
    """One program for the 16 points × (split + refit); the fold lanes are
    scored from the program's fitted means, the winner's refit is its
    refit lane, and the spans and the ``glm`` ledger carry the counts."""
    from transmogrifai_tpu.selector import RegressionModelSelector
    from transmogrifai_tpu.selector.model_selector import make_candidates
    from transmogrifai_tpu.selector.validators import TrainValidationSplit
    from transmogrifai_tpu.telemetry import metrics as tm

    monkeypatch.setenv("TPTPU_COMPILE_CACHE", str(tmp_path))
    x, y, _masks = data
    selector = RegressionModelSelector(
        models=make_candidates("Regression",
                               ["OpGeneralizedLinearRegression"]),
        validator=TrainValidationSplit(0.75, seed=3), seed=3)
    before = tm.REGISTRY.source_snapshots()["glm"]
    tspans.reset_for_tests()
    out = selector.fit_arrays(x, y, np.ones(len(y), np.float32))
    events = tspans.snapshot_events()
    after = tm.REGISTRY.source_snapshots()["glm"]
    dispatch = [e for e in events if e["name"] == "glm/fit_dispatch"]
    assert len(dispatch) == 1 and dispatch[0]["args"]["lanes"] == 32
    assert dispatch[0]["args"]["solver"] == "irls"
    assert dispatch[0]["args"]["pairs"] == (
        "gaussian:identity=8 gaussian:log=8 poisson:identity=8 "
        "poisson:log=8")
    counts = [e["args"] for e in events if e["name"] == "glm/await_outputs"
              and "iters_needed" in e["args"]][0]
    assert counts["lanes_irls"] == 24 and counts["lanes_stopped"] == 24
    assert 0 < counts["iters_needed"] <= counts["iters_run"]
    assert counts["rows_projected"] == 0
    assert after["irlsItersNeeded"] - before["irlsItersNeeded"] == \
        counts["iters_needed"]
    refit = [e for e in events if e["name"] == "selector/refit"]
    assert refit[0]["args"]["prefit"] is True
    best = out.best_model
    assert not hasattr(best, "_sweep_stack")
    # the winner's refit is its lane fitted alone on the refit rows
    grid = out.summary["bestGrid"]
    alone = GeneralizedLinearRegression().with_params(**grid).fit_arrays(
        x, y, np.ones(len(y), np.float32))
    np.testing.assert_allclose(best.weights, alone.weights, atol=1e-5)
    assert out.summary["evaluationMetric"] == "RMSE"
    assert glm.GLR_IRLS and "Spark IRLS" in glm.GLR_IRLS
