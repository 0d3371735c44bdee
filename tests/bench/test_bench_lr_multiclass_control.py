"""The multinomial logistic-regression cell's comparison fails what it has to
fail.

* The controls: ``checks/logistic_multiclass_winner.py``'s plain reference
  put in the program's place (``reference.stand_in``) and read by the same
  ``reference.compare`` that reads the program: under every limit at the
  float32 the configuration states, over at least one with every product at
  one bfloat16 pass, or with a bfloat16 plane.
* The faults of this family, each planted in the program before the rest of
  a run (``run.py --rehearsal`` in a child): the learner the program had
  before (FISTA at four times ``max_iter``, one lane at a time), intercepts
  under the penalty, the L1 term on the coefficients' original scale,
  K one-vs-rest binary fits where the configuration guarantees one softmax
  fit, and every GLM product at one bfloat16 pass while the program still
  states ``highest``; each prints ``correct`` false.
* The gate: a program that states no multinomial learner, or another, is
  not run at all.
"""
import json
import os
import subprocess
import sys
from functools import partial

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import datagen, datagen_multiclass, reference  # noqa: E402

CELL = "flagship_lr_multiclass.fit"


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "flagship_lr_multiclass.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small_ref():
    """What ``reference.build`` gives, for a plane the reference makes
    alone: every raw column through its default vectorizer, an eighth of
    the hash buckets, the seven-class label."""
    n, seed = 6144, 2147483783
    table = datagen_multiclass.multiclass_table(n, seed)
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
    return reference.build(_config(), table, columns, seed)


@pytest.mark.parametrize("plane,fit,over", [
    ("f32", "f32", set()),
    ("f32", "bf16", {"objective_gap", "coef_gap"}),
    ("bf16", "f32", {"plane_gap"}),
])
def test_stand_in_reads_under_the_limits_only_as_stated(small_ref, plane, fit, over):
    cfg = _config()
    product = reference.stand_in(cfg, small_ref, {"plane": plane, "fit": fit})
    compared = reference.compare(cfg, small_ref, product)
    assert {c["name"] for c in compared} == set(cfg["limits"])
    assert set(cfg["limits_why"]) == set(cfg["limits"])
    failed = {c["name"] for c in compared if not c["ok"]}
    assert over <= failed and bool(failed) == bool(over), compared


def test_the_reference_learner_fits_the_label_it_was_drawn_from(small_ref):
    """At the grid's weakest penalty the reference's softmax fit beats the
    largest class's share by a wide margin, its intercepts are centred, its
    coefficients of a column constant on the lane's rows are 0, and the
    objective it reports is the one ``objective`` computes at its point."""
    from benchmarks.checks import logistic_multiclass_winner as lw

    y = small_ref["y"].astype(np.int64)
    x = small_ref["x"].copy()
    x[:, 3] = 2.5                       # a constant column
    mask = small_ref["folds"][0][0].astype(np.float32)
    params = {"reg_param": 0.001, "elastic_net_param": 0.1, "max_iter": 50}
    fit = lw.lane(x, y, mask, params, 7)
    val = small_ref["folds"][0][1]
    pred = lw.logits(fit, fit["beta"], fit["b"])[val].argmax(axis=1)
    assert (pred == y[val]).mean() > np.bincount(y[val]).max() / val.sum() + 0.1
    assert abs(fit["intercept"].sum()) < 1e-5
    assert fit["const"][3] and not fit["weights"][3].any()
    assert 0 < fit["moved"] <= 50
    again = lw.objective(fit, y, mask, fit["beta"], fit["b"])
    assert again == pytest.approx(fit["F"], rel=1e-6)
    # a point the learner did not reach reads a higher objective
    worse = lw.objective(fit, y, mask, fit["beta"] * 0.9, fit["b"])
    assert worse > fit["F"]


def test_the_reference_standardizes_once_a_row_mask(small_ref, monkeypatch):
    """Every grid point on one row mask shares one standardized plane (a
    fold's training rows; every training row for the refits), and a lane
    fitted on the shared plane is the lane fitted alone, bit for bit."""
    from benchmarks.checks import logistic_multiclass_winner as lw
    from benchmarks.checks.forest_multiclass_winner import weighted_f1

    cfg = _config()
    cfg["default_grid"]["max_iter"] = [5]
    ref = {k: small_ref[k] for k in ("x", "y", "folds", "seed")}
    made = []
    plane_of = lw.lane_plane
    monkeypatch.setattr(
        lw, "lane_plane", lambda x, mask: made.append(1) or plane_of(x, mask))
    points, values = lw._fold_metrics(cfg, ref, ref["x"], "f32")
    refits = [lw._refit(cfg, ref, ref["x"], p, "f32") for p in points[:2]]
    assert len(made) == len(ref["folds"]) + 1
    y = ref["y"].astype(np.int64)
    train, val = ref["folds"][0]
    params = {**cfg["estimator_defaults"], **points[-1]}
    alone = lw.lane(ref["x"], y, train.astype(np.float32), params, 7)
    z = lw.logits(alone, alone["beta"], alone["b"])
    assert values[-1][0] == weighted_f1(y[val], z[val].argmax(axis=1))
    params = {**cfg["estimator_defaults"], **points[1]}
    alone = lw.lane(ref["x"], y, np.ones(len(y), np.float32), params, 7)
    assert np.array_equal(alone["beta"], refits[1]["beta"])
    assert alone["F"] == refits[1]["F"]


def test_the_reference_row_blocks_on_threads_sum_as_one_thread(small_ref, monkeypatch):
    """The float64 passes over the plane's row blocks give the same bits on
    one thread and on several: each block's sums are added in block order."""
    from benchmarks.checks import logistic_multiclass_winner as lw

    monkeypatch.setattr(lw, "ROW_BLOCK", 1000)  # several blocks, one ragged
    x = small_ref["x"]
    mask = small_ref["folds"][0][0].astype(np.float32)
    mask[:1000] = 0.0                            # a block with no lane row
    got = {}
    for workers in (1, 4):
        monkeypatch.setattr(lw, "WORKERS", workers)
        mu, s, const = lw.standardization(x, mask)
        got[workers] = (mu, s, const, lw.standardized_plane(x, mu, s, const))
    for one, many in zip(got[1], got[4]):
        assert np.array_equal(one, many)
    rows = mask > 0
    assert np.allclose(got[1][0], x[rows].astype(np.float64).mean(axis=0))


# ------------------------------------------------------------------ faults
def _fista_four_times():
    """The learner the program had before: one lane at a time (vmapped), an
    explicitly standardized copy of the plane a lane, FISTA with a fixed
    step for four times ``max_iter`` iterations, intercepts from 0."""
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.models import logistic, solvers

    def one(x, y, mask, reg, en, classes, iters):
        mask = mask.astype(x.dtype)
        n = jnp.maximum(mask.sum(), 1.0)
        xs, mean, std, _ = solvers._standardize(x, mask)
        y1h = jax.nn.one_hot(y.astype(jnp.int32), classes, dtype=x.dtype)
        d = x.shape[1]

        def unpack(p):
            return p[: d * classes].reshape(d, classes), p[d * classes:]

        def grad(p):
            w, b = unpack(p)
            prob = jax.nn.softmax(xs @ w + b, axis=-1)
            r = (prob - y1h) * mask[:, None]
            gw = xs.T @ r / n + reg * (1.0 - en) * w
            return jnp.concatenate([gw.reshape(-1), r.sum(0) / n])

        def prox(p, step):
            w, b = unpack(p)
            return jnp.concatenate(
                [solvers._soft_threshold(w, step * reg * en).reshape(-1), b])

        lip = 0.5 * ((xs * xs).sum(0) / n).sum() + reg * (1.0 - en)
        p = solvers._fista(grad, prox, jnp.zeros(d * classes + classes),
                           1.0 / jnp.maximum(lip, 1e-6), iters)
        w_std, b_std = unpack(p)
        return (w_std / std[:, None],
                b_std - (w_std * (mean / std)[:, None]).sum(0))

    @partial(jax.jit, static_argnames=(
        "num_classes", "num_iters", "fit_intercept", "standardization"))
    def fista(x, y, row_masks, regs, ens, num_classes, num_iters=100,
              fit_intercept=True, standardization=True):
        w, b = jax.vmap(lambda r, e, m: one(
            x, y, m, r, e, num_classes, num_iters * 4))(regs, ens, row_masks)
        k = row_masks.shape[0]
        logits = jnp.einsum("nd,kdc->kcn", x, w) + b[:, :, None]
        return solvers.MultinomialFit(
            w, b, logits, jnp.full(k, num_iters, jnp.int32),
            jnp.zeros(k, bool))

    logistic.fit_logistic_multinomial_batched = fista


def _intercepts_penalised():
    """The elastic net over the intercepts too."""
    import jax.numpy as jnp

    from transmogrifai_tpu.models import solvers

    def penalised(reg_params, elastic_nets, safe, classes):
        k, d = safe.shape
        every = jnp.ones((k, d * classes + classes), dtype=safe.dtype)
        return ((reg_params * elastic_nets)[:, None] * every,
                (reg_params * (1.0 - elastic_nets))[:, None] * every)

    solvers._multinomial_penalty_weights = penalised


def _l1_on_original_scale():
    """λα|β / s|: the L1 term on the coefficients of the raw plane."""
    import jax.numpy as jnp

    from transmogrifai_tpu.models import solvers

    sound = solvers._multinomial_penalty_weights

    def raw(reg_params, elastic_nets, safe, classes):
        l1, l2 = sound(reg_params, elastic_nets, safe, classes)
        scale = jnp.concatenate(
            [jnp.repeat(1.0 / safe, classes, axis=1),
             jnp.ones((safe.shape[0], classes), dtype=safe.dtype)], axis=1)
        return l1 * scale, l2

    solvers._multinomial_penalty_weights = raw


def _one_vs_rest():
    """K binary fits a lane, one a class (it still STATES one softmax
    fit)."""
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.models import logistic, solvers

    @partial(jax.jit, static_argnames=(
        "num_classes", "num_iters", "fit_intercept", "standardization"))
    def ovr(x, y, row_masks, regs, ens, num_classes, num_iters=100,
            fit_intercept=True, standardization=True):
        fits = [solvers.fit_logistic_binary_batched(
            x, (y == c).astype(x.dtype), row_masks, regs, ens,
            num_iters=num_iters, fit_intercept=fit_intercept,
            standardization=standardization) for c in range(num_classes)]
        w = jnp.stack([f.weights for f in fits], axis=-1)
        b = jnp.stack([f.intercept for f in fits], axis=-1)
        b = b - b.mean(axis=1, keepdims=True)
        k = row_masks.shape[0]
        logits = jnp.einsum("nd,kdc->kcn", x, w) + b[:, :, None]
        return solvers.MultinomialFit(
            w, b, logits, jnp.full(k, num_iters, jnp.int32),
            jnp.zeros(k, bool))

    logistic.fit_logistic_multinomial_batched = ovr


def _one_bfloat16_pass():
    """Every GLM product at one bfloat16 pass (operands rounded, float32
    sums), the program still stating ``highest``: the control at the
    rehearsal's size, on any backend."""
    import jax.numpy as jnp

    from transmogrifai_tpu.models import solvers

    solvers._mm = lambda a, b: jnp.matmul(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)


def _states_nothing():
    from transmogrifai_tpu.models import logistic

    del logistic.LOGISTIC_MULTINOMIAL


def _states_one_vs_rest():
    from transmogrifai_tpu.models import logistic

    logistic.LOGISTIC_MULTINOMIAL = "one-vs-rest: K binary fits"


FAULTS = {"none": lambda: None,
          "fista_four_times": _fista_four_times,
          "intercepts_penalised": _intercepts_penalised,
          "l1_on_original_scale": _l1_on_original_scale,
          "one_vs_rest": _one_vs_rest,
          "one_bfloat16_pass": _one_bfloat16_pass,
          "states_nothing": _states_nothing,
          "states_one_vs_rest": _states_one_vs_rest}
#: the number each fault has to read over its limit (others may too)
CAUGHT_BY = {"fista_four_times": "objective_gap",
             "intercepts_penalised": "intercept_gap",
             "l1_on_original_scale": "coef_gap",
             "one_vs_rest": "objective_gap",
             "one_bfloat16_pass": "objective_gap"}


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


@pytest.mark.parametrize("fault", ["states_one_vs_rest", "states_nothing"])
def test_a_program_that_states_another_learner_is_not_run(fault, tmp_path):
    """The driver reads the statement before it makes the table: a program
    without the multinomial learner ends in seconds, with no result line."""
    done = _child(fault, tmp_path)
    assert done.returncode != 0
    assert not done.stdout.strip()
    assert "not run" in done.stderr


def test_the_work_count_by_hand():
    """One sweep at the cell's shapes: the line search's product is the
    count's largest term, and the count grows with every iteration."""
    from benchmarks.work import glm_multiclass_work as work

    flops, nbytes = work.sweep_work(
        _config(), {"plane_shape": (1002701, 357), "lanes": 16})
    n, d = 1002701, 357
    line_search = 50 * 2.0 * n * d * (7 * 16 * 7)
    assert line_search < flops < 1.5 * line_search
    assert nbytes > 50 * 3 * 4.0 * n * d
    more, _ = work.glm_fit_work(n, d, 16, 7, 51, 7)
    assert more > flops


if __name__ == "__main__":
    # child of the tests above: break the program, then drive the rest of
    # a run
    os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[sys.argv[1]]()
    from benchmarks import run

    sys.exit(run.main(sys.argv[2:]))
