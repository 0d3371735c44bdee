"""The generalized-linear-regression cell's comparison fails what it has to
fail.

* The controls: ``checks/glr_winner.py``'s plain reference put in the
  program's place (``reference.stand_in``) and read by the same
  ``reference.compare`` that reads the program, in the check's coded form:
  under every limit at the float32 the configuration states, over at least
  one with every product at one bfloat16 pass, or with a bfloat16 plane.
* The faults of this family, each planted in the program before the rest of
  a run (``run.py --rehearsal`` in a child): every GLM product over the
  plane at one bfloat16 pass while the program still states ``highest``;
  the learner's
  solve as the program had it before (LU of the raw normal equations, a
  ridge on the coefficients' original scale and a small one on the
  intercept too); an L2 penalty on standardized coefficients inside the
  IRLS steps; log-link lanes fitted with the identity link. Each prints
  ``correct`` false.
* The gate: a program that states no IRLS learner, or another, is not run.
* The table: ONE table whatever the run's seed, its label's mean above the
  floor on every row of the cell's table.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import datagen, datagen_regression, reference  # noqa: E402

CELL = "flagship_glr.fit"


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "flagship_glr.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small_ref():
    """What ``reference.build`` gives, for a plane the reference makes
    alone: every raw column through its default vectorizer, an eighth of
    the hash buckets, the amount label; the coded configuration."""
    from benchmarks.checks import glr_winner

    n, seed = 6144, 2147483783
    table = datagen_regression.regression_table(n, seed)
    null = reference.NULL
    columns = []
    for kind, count in (("real", 10), ("int", 5), ("bin", 3)):
        for j in range(count):
            columns += [(f"{kind}_{j}", None, None), (f"{kind}_{j}", null, None)]
    for j, levels in enumerate(datagen.PICK_LEVELS):
        columns += [(f"pick_{j}", f"P{j}{c}", None) for c in range(levels)]
        columns.append((f"pick_{j}", null, None))
    columns += [("text_0", None, f"hash_{b}") for b in range(0, 512, 8)]
    columns.append(("text_0", null, None))
    cfg = glr_winner.coded_config(_config())
    cfg["splitter"] = {**cfg["splitter"], "max_training_sample": 5000}
    return cfg, reference.build(cfg, table, columns, seed)


@pytest.mark.parametrize("plane,fit,over", [
    ("f32", "f32", set()),
    ("f32", "bf16", {"objective_gap", "pred_gap"}),
    ("bf16", "f32", {"plane_gap"}),
])
def test_stand_in_reads_under_the_limits_only_as_stated(small_ref, plane, fit, over):
    cfg, ref = small_ref
    product = reference.stand_in(cfg, ref, {"plane": plane, "fit": fit})
    compared = reference.compare(cfg, ref, product)
    assert {c["name"] for c in compared} == set(cfg["limits"])
    assert set(cfg["limits_why"]) == set(cfg["limits"])
    failed = {c["name"] for c in compared if not c["ok"]}
    assert over <= failed and bool(failed) == bool(over), compared


def test_the_reference_learner(small_ref):
    """The reference's gaussian/identity lane is the closed-form ridge of
    the learner's statement in float64; an IRLS lane stops by Spark's rule
    before ``max_iter``, its μ fits the label, and the objective of its
    last solve is least at its own point."""
    from benchmarks.checks import glr_winner as gw

    cfg, ref = small_ref
    x, y = ref["x"], ref["y"]
    mask = ref["folds"][0][0].astype(np.float32)
    data = gw.lane_data(x, mask)
    point = {"family": "gaussian", "link": "identity", "reg_param": 0.01,
             "tol": 1e-6, "max_iter": 50}
    fit = gw.lane(data, y, point)
    rows = mask > 0
    xm, ym = x[rows].astype(np.float64), y[rows].astype(np.float64)
    mu, sd = xm.mean(0), xm.std(0)
    const = np.ptp(xm, axis=0) == 0
    safe = np.where(const, 1.0, sd)
    xs = np.where(const, 0.0, (xm - mu) / safe)
    a = xs.T @ xs / len(ym) + np.diag(np.where(const, 1.0, 0.01 / ym.std()))
    gamma = np.linalg.solve(a, xs.T @ (ym - ym.mean()) / len(ym))
    assert fit["iters"] == 0
    # the same fitted values (the one-hot groups' collinear directions,
    # which the penalty alone fixes, take float32 rounding)
    np.testing.assert_allclose(xs @ (fit["beta"] * safe), xs @ gamma,
                               rtol=0, atol=1e-4 * ym.std())
    poisson = gw.lane(data, y, {**point, "family": "poisson", "link": "log"})
    assert 0 < poisson["iters"] < 50 and poisson["projected"] == 0
    val = ref["folds"][0][1]
    pred = gw.predict(poisson, x[val], poisson["beta"], poisson["b"])
    assert gw.rmse(y[val], pred) < y[val].std()
    best = gw.objective(poisson["parts"], poisson["beta"], poisson["b"])
    assert gw.objective(poisson["parts"], poisson["beta"] * 1.01,
                        poisson["b"]) > best


def test_the_coded_form_names_the_same_points():
    from benchmarks.checks import glr_winner as gw

    cfg = _config()
    coded = gw.coded_config(cfg)
    named = [gw._named_point(coded, p) for p in reference.grid_points(coded)]
    assert named == reference.grid_points(cfg)
    assert len(named) == 16
    summary = {"bestGrid": named[3], "validationResults": [
        {"grid": p, "metricValues": [float(i)]} for i, p in enumerate(named)]}
    out = gw.coded_summary(coded, summary)
    assert reference.result_of(out, gw._coded_point(coded, named[5])) == \
        out["validationResults"][5]
    assert out["validationResults"][5]["metricValues"] == [-5.0]


# ------------------------------------------------------------------ faults
def _one_bfloat16_pass():
    """Every GLM product over the plane (``solvers._mm``: η, the Grams and
    their right-hand sides, the scores) at one bfloat16 pass (operands
    rounded, float32 sums), the program still stating ``highest``; the
    solve's small products (``_bmm``) stay as they are."""
    import jax.numpy as jnp

    from transmogrifai_tpu.models import solvers

    solvers._mm = lambda a, b: jnp.matmul(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)


def _parent_lu_solve():
    """The solve the program had before: LU of the raw (uncentred,
    unstandardized) normal equations of [x, 1], λ on the coefficients'
    original scale and 1e-7 on every diagonal entry, the intercept's too."""
    import jax.numpy as jnp

    from transmogrifai_tpu.models import solvers

    def lu(sw, s1, gram, sz, szz, r, zshift, const, lam, gshift,
           standardize, fit_intercept):
        n = jnp.maximum(sw, 1e-30)
        m = s1 / n[:, None] + gshift
        xc_m = s1 / n[:, None]
        second = (gram / n[:, None, None] + xc_m[:, :, None] * gshift
                  + gshift[:, None] * m[:, None, :])
        zbar = sz / n + zshift
        xz = r / n[:, None] + zshift[:, None] * xc_m + gshift * zbar[:, None]
        d = s1.shape[1]
        top = jnp.concatenate([second, m[:, :, None]], axis=2)
        bottom = jnp.concatenate([m, jnp.ones_like(n)[:, None]], axis=1)
        a = jnp.concatenate([top, bottom[:, None, :]], axis=1)
        ridge = jnp.concatenate([jnp.ones(d), jnp.zeros(1)])
        a = a + lam[:, None, None] * jnp.diag(ridge) + 1e-7 * jnp.eye(d + 1)
        rhs = jnp.concatenate([xz, zbar[:, None]], axis=1)
        sol = jnp.linalg.solve(a, rhs[..., None])[..., 0]
        return sol[:, :d], sol[:, d]

    solvers._glr_wls = lu


def _standardized_penalty_in_steps():
    """λ / σ_z on standardized coefficients in every solve, the IRLS
    steps' too (Spark's steps take it on the original scale)."""
    from transmogrifai_tpu.models import solvers

    sound = solvers._glr_wls

    def always(*args):
        return sound(*args[:10], True, args[11])

    solvers._glr_wls = always


def _log_fitted_as_identity():
    """Log-link lanes fitted with the identity link (the estimator still
    names their link log)."""
    from transmogrifai_tpu.models import glm

    glm.GLM_LINKS = {**glm.GLM_LINKS, "log": glm.GLM_LINKS["identity"]}


def _states_nothing():
    from transmogrifai_tpu.models import glm

    del glm.GLR_IRLS


def _states_another():
    from transmogrifai_tpu.models import glm

    glm.GLR_IRLS = "IRLS with a fixed number of iterations"


FAULTS = {"none": lambda: None,
          "one_bfloat16_pass": _one_bfloat16_pass,
          "parent_lu_solve": _parent_lu_solve,
          "standardized_penalty_in_steps": _standardized_penalty_in_steps,
          "log_fitted_as_identity": _log_fitted_as_identity,
          "states_nothing": _states_nothing,
          "states_another": _states_another}
#: the number each fault has to read over its limit (others may too)
CAUGHT_BY = {"one_bfloat16_pass": "objective_gap",
             "parent_lu_solve": "fold_metric_gap",
             "standardized_penalty_in_steps": "fold_metric_gap",
             "log_fitted_as_identity": "fold_metric_gap"}


def _child(fault, tmp_path, seed="2147483777", trace="0"):
    """``run.py --rehearsal`` after ``fault``."""
    flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    # the patched program must be traced and compiled here: the executable
    # bank would hand back the sound program's executable
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags,
           "JAX_ENABLE_COMPILATION_CACHE": "false",
           "TPTPU_COMPILE_CACHE": str(tmp_path)}
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), fault, "--workload", CELL,
         "--seed", seed, "--seconds", "1", "--trace", trace, "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )


@pytest.mark.parametrize("fault", sorted(CAUGHT_BY))
def test_fault_reads_not_correct(fault, tmp_path):
    done = _child(fault, tmp_path)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, line["compared"]
    read = line["compared"][CAUGHT_BY[fault]]
    value = float(read["value"])  # "inf" where a shape differs
    assert not value <= read["limit"], line["compared"]


@pytest.mark.parametrize("fault", ["states_another", "states_nothing"])
def test_a_program_that_states_another_learner_is_not_run(fault, tmp_path):
    """The driver reads the statement before it makes the table: a program
    without Spark's IRLS learner ends in seconds, with no result line."""
    done = _child(fault, tmp_path)
    assert done.returncode != 0
    assert not done.stdout.strip()
    assert "not run" in done.stderr


def test_the_work_count_by_hand():
    """One sweep at the cell's shapes: the weighted Grams of the iterations
    the lanes needed are the count's largest term, and the plane is read
    once an iteration of the slowest lane."""
    from benchmarks.work import glr_irls_work as work

    n, d = 1002701, 357
    flops, nbytes = work.glr_fit_work(n, d, 32, 0.75 * n, 1e6, 100, 8)
    grams = 100 * 2.0 * 0.75 * n * (d + 1) ** 2
    assert grams < flops < 1.1 * grams + 2 * 2.0 * 1.76 * n * (d + 1) ** 2
    assert nbytes == (2 + 8) * 4.0 * n * d + 4.0 * 32 * n
    more, _ = work.glr_fit_work(n, d, 32, 0.75 * n, 1e6, 101, 8)
    assert more > flops
    # a program that counts no iteration gives the least there is
    least = work.sweep_work(_config(), {
        "plane_shape": (n, d), "lanes": 32, "window": {"sweeps": 1}})
    assert least == work.glr_fit_work(n, d, 32, 0.75 * n, 1e6, 0.0, 0.0)


def test_one_table_whatever_the_run_seed():
    """The label is drawn from the table's seed alone, and its mean stays
    above the floor on every row of the cell's table."""
    cfg = _config()
    a = datagen_regression.regression_table(4096, cfg["table_seed"])
    b = datagen_regression.regression_table(4096, cfg["table_seed"])
    assert np.array_equal(a["label"], b["label"]) and (a["label"] > 0).all()
    assert "nothing" in cfg["seed_draws"]
    full = datagen_regression.regression_table(int(cfg["rows"]),
                                               int(cfg["table_seed"]))
    assert datagen_regression.floor_rows(full) == 0


if __name__ == "__main__":
    # child of the tests above, or a control on the chip: break the
    # program, then drive the rest of a run
    if "--rehearsal" in sys.argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[sys.argv[1]]()
    from benchmarks import run

    sys.exit(run.main(sys.argv[2:]))
