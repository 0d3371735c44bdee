"""The check of a generalized-linear-regression sweep (Spark ML's
GeneralizedLinearRegression as TransmogrifAI's regression selector runs it
under its GLR grid), with its plain reference. A configuration names this
file under ``"check"``. Nothing here imports the program.

The learner (``cfg["learner"]`` states the same), for one lane: grid point
(family, link, λ, tol) on row mask m, label y > 0, F columns::

    V(μ)   1 (gaussian), μ (poisson);   g(μ) = μ (identity), log μ (log)
    project(μ)   poisson: max(μ, ε), ε = ``EPS``
    WLS(z, w; s)   argmin (1 / 2Σ m·w) Σ m_i w_i (z_i - x_i β - b)²
                   + ½ Σ_j pen_j (β_j σ_j)²
                   σ_j the weighted std of column j (at least NOISE_FLOOR
                   times its weighted root mean square about the shift);
                   pen_j = λ / σ_z with s (standardized features and
                   label: the start and the closed form), λ / σ_j² without
                   (an IRLS step); σ_z the weighted std of z; b never
                   penalised; a column constant on the lane's rows has
                   β_j = 0
    gaussian/identity   (β, b) = WLS(y, 1; s), no iteration
    other pairs   start: μ⁰ = y, (β, b) = WLS(g(μ⁰), 1; s); then while
                  t < max_iter: η = xβ + b, μ = project(g⁻¹(η)),
                  z = η + (y - μ) g'(μ), w = 1 / (g'(μ)² V(μ)),
                  (β', b') = WLS(z, w; no s); stop once
                  max(|β' - β|, |b' - b|) < tol (Spark's rule)
    score   μ = project(g⁻¹(xβ + b)); the validation metric is the RMSE

The reference fits each lane ONE AT A TIME: every product over the plane
(η, the weighted Gram and its right-hand side) in float32 at ``highest`` on
the device, in row blocks of ``ROW_BLOCK``; each block's sums come to the
host and are added in float64 in block order; the system is solved in
float64 (Cholesky), and the stop is Spark's at ``tol`` exactly. Each row
mask's columns are shifted by their own float64 mean over its rows before
the float32 products, and z by the lane's η at the shifted origin, so that
no float32 sum cancels.

It reads, of the LAST timed sweep:

* every fold lane: its own fit of each grid point on each fold's training
  rows, scored by the RMSE in float64 on the fold's validation rows, the
  widest relative gap to the RMSE the program reported
  (``fold_metric_gap``);
* the winner's refit against its own refit of the same point on the
  refit rows (the ``DataSplitter``'s sample of the training rows):
  ``coef_gap`` (standardized coefficients β_j σ_j, the widest gap over the
  largest), ``intercept_gap`` (relative), ``objective_gap`` (the last
  WLS objective of its own fit at the program's point against at its own,
  relative) and ``pred_gap`` (μ on the first fold's validation rows from
  both points, the widest relative gap);
* what the program states of itself: ``glr_learner_other`` (1 unless it
  states the configuration's ``glr_irls``) and ``precision_other`` (1
  unless its GLM products state the configuration's ``solver_precision``).

``lib/reference.compare`` reads every grid value as a number and a LARGER
mean metric as the better: ``coded_config`` and ``coded_summary`` hand it
the family and the link by their index in the configuration's grid and the
RMSE negated; ``compare`` here reads that coded form.

``precision="bf16"`` computes every product of the reference at one
bfloat16 pass (operands rounded, float32 sums): the control, through
``stand_in``.
"""
from __future__ import annotations

import numpy as np

from benchmarks.lib.reference import entry, grid_points, result_of

#: rows a block of every product over the plane
ROW_BLOCK = 65536
EPS = 2.0 ** -24
#: Tweedie's δ: a poisson lane's start μ⁰ where its label is 0
DELTA = 0.1
NOISE_FLOOR = 2e-3
CODED = ("family", "link")
FOUND = ("coef_gap", "intercept_gap", "objective_gap", "pred_gap")


def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def _dot(a, b, precision):
    """``a @ b`` at ``highest`` ("f32") or at one bfloat16 pass ("bf16")."""
    jax, jnp = _jnp()
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


# ------------------------------------------------------- the coded grid
def coded_config(cfg: dict) -> dict:
    """``cfg`` with the family and link values of its grid named by their
    index (``codes`` keeps the names)."""
    codes = {k: list(cfg["default_grid"][k]) for k in CODED}
    grid = {k: (list(range(len(v))) if k in CODED else v)
            for k, v in cfg["default_grid"].items()}
    cut = {k: ([codes[k].index(n) for n in v] if k in CODED else v)
           for k, v in cfg.get("grid", {}).items()}
    return {**cfg, "default_grid": grid, "grid": cut, "codes": codes}


def _coded_point(cfg: dict, point: dict) -> dict:
    return {k: (cfg["codes"][k].index(v) if k in CODED else v)
            for k, v in point.items()}


def _named_point(cfg: dict, point: dict) -> dict:
    return {k: (cfg["codes"][k][int(v)] if k in CODED else v)
            for k, v in point.items()}


def coded_summary(cfg: dict, summary: dict) -> dict:
    """A sweep's summary as ``coded_config(cfg)`` reads it: grid values
    coded, every fold metric (RMSE, smaller is better) negated."""
    return {
        **summary,
        "bestGrid": _coded_point(cfg, summary["bestGrid"]),
        "validationResults": [
            {**r, "grid": _coded_point(cfg, r["grid"]),
             "metricValues": [-float(v) for v in r["metricValues"]]}
            for r in summary["validationResults"]],
    }


# ------------------------------------------------------------ one lane
def refit_rows(n: int, seed: int, cap: int) -> np.ndarray:
    """The rows the default DataSplitter keeps for the refit: every row,
    or a seeded sample of ``cap`` without replacement."""
    keep = np.ones(n, bool)
    if n > cap:
        keep[:] = False
        keep[np.random.default_rng(seed).choice(n, cap, replace=False)] = True
    return keep


def _unlink(eta, link):
    return eta if link == "identity" else np.exp(eta)


def _project(mu, family):
    return np.maximum(mu, EPS) if family == "poisson" else mu


def _blocks(a):
    """[nblk, rows, ...]: ``a`` padded with 0 to whole blocks of
    ``ROW_BLOCK`` rows (one block of all the rows, to a multiple of 8,
    where there are fewer)."""
    _, jnp = _jnp()
    n = a.shape[0]
    rows = min(ROW_BLOCK, -(-n // 8) * 8)
    pad = -n % rows
    a = jnp.pad(jnp.asarray(a), [(0, pad)] + [(0, 0)] * (a.ndim - 1))
    return a.reshape(-1, rows, *a.shape[1:])


def _sums(xb, yb, mb, shift, beta, off, family, link, start, precision):
    """Per row block, in float32: the weighted Gram of the shifted plane
    and its right-hand side at the lane's point (β, η at the shifted
    origin ``off``); ``start``: the start's unit weights and label."""
    jax, jnp = _jnp()

    def one(blk):
        x, y, m = blk
        xs = x - shift
        inside = m > 0
        if start:
            mu0 = jnp.where(y == 0.0, DELTA, y) if family == "poisson" else y
            z = (mu0 if link == "identity" else jnp.log(mu0)) - off
            v = m
            proj = jnp.zeros((), jnp.int32)
        else:
            lin = _dot(xs, beta, precision)
            eta = lin + off
            raw = eta if link == "identity" else jnp.exp(eta)
            mu = jnp.maximum(raw, EPS) if family == "poisson" else raw
            gp = 1.0 if link == "identity" else 1.0 / mu
            var = 1.0 if family == "gaussian" else mu
            z = lin + (y - mu) * gp
            v = m / (gp * gp * var)
            proj = (inside & (mu != raw)).sum()
        v = jnp.where(inside, v, 0.0)
        z = jnp.where(inside, z, 0.0)
        vz = v * z
        return (_dot((xs * v[:, None]).T, xs, precision), _dot(v, xs, precision),
                v.sum(), _dot(vz, xs, precision), vz.sum(), (vz * z).sum(),
                proj)

    return jax.lax.map(one, (xb, yb, mb))


_PROGRAMS: dict = {}


def _pass(*args, **statics):
    if not _PROGRAMS:
        jax, _ = _jnp()
        _PROGRAMS["sums"] = jax.jit(_sums, static_argnames=(
            "family", "link", "start", "precision"))
    out = _PROGRAMS["sums"](*args, **statics)
    # each block's sums added in float64, in block order
    return [np.asarray(a, np.float64).sum(axis=0) for a in out]


def wls(sums, shift, const, lam, zshift, standardize):
    """Spark's WeightedLeastSquares in float64 from a pass's sums: the
    original-scale (β, b), and the pieces of its objective."""
    g, s1, sw, r, sz, szz, _proj = sums
    n = max(sw, 1e-300)
    m = s1 / n
    zdev = sz / n
    second = g / n
    cov = second - np.outer(m, m)
    cz = r / n - m * zdev
    sigma = np.sqrt(np.maximum(np.diag(cov), 0.0))
    rms = np.sqrt(np.maximum(np.diag(second), 0.0))
    safe = np.where(const, 1.0, np.maximum(sigma, NOISE_FLOOR * rms + 1e-12))
    zstd = np.sqrt(max(szz / n - zdev * zdev, 0.0))
    zscale = zstd if zstd > 0 else abs(zdev + zshift)
    pen = lam / zscale * (np.ones_like(safe) if standardize
                          else zscale / (safe * safe))
    pen = np.where(const, 1.0, pen)
    a = np.where(const[:, None] | const[None, :], 0.0,
                 cov / np.outer(safe, safe)) + np.diag(pen)
    c = np.where(const, 0.0, cz / safe)
    try:
        chol = np.linalg.cholesky(a)
    except np.linalg.LinAlgError:
        chol = np.linalg.cholesky(a + 1e-6 * np.eye(len(a)))
    gamma = np.linalg.solve(chol.T, np.linalg.solve(chol, c))
    beta = np.where(const, 0.0, gamma / safe)
    b = zdev + zshift - beta @ (m + shift)
    return beta, b, {"second": second, "m": m, "cz": cz, "zdev": zdev,
                     "zvar": zstd * zstd, "pen": pen, "safe": safe,
                     "zshift": zshift, "shift": shift}


def objective(parts, beta, b) -> float:
    """The WLS objective of ``parts`` (a solve's pieces) at (β, b), in the
    shifted coordinates it was summed in: ½ E_v[(z - xβ - b)²] + penalty."""
    m, second = parts["m"], parts["second"]
    c = b + beta @ parts["shift"] - parts["zshift"]     # z′ - xs β - c
    mean_res = parts["zdev"] - beta @ m - c
    # E[(z′ - zbar′) - (xs - m) β]² + (mean residual)²
    cov = second - np.outer(m, m)
    var_res = parts["zvar"] - 2 * beta @ parts["cz"] + beta @ cov @ beta
    gamma = beta * parts["safe"]
    return float(0.5 * (var_res + mean_res ** 2)
                 + 0.5 * (parts["pen"] * gamma * gamma).sum())


def _moments(x, rows):
    """(mean, std, const) of each column over ``rows``, in float64, by row
    blocks (no float64 copy of the plane)."""
    f = x.shape[1]
    tot, tot2 = np.zeros(f), np.zeros(f)
    lo, hi = np.full(f, np.inf), np.full(f, -np.inf)
    for i in range(0, x.shape[0], ROW_BLOCK):
        xm = np.asarray(x[i:i + ROW_BLOCK][rows[i:i + ROW_BLOCK]],
                        np.float64)
        if len(xm):
            tot += xm.sum(axis=0)
            tot2 += (xm * xm).sum(axis=0)
            lo, hi = np.minimum(lo, xm.min(axis=0)), np.maximum(
                hi, xm.max(axis=0))
    n = max(int(rows.sum()), 1)
    mean = tot / n
    return mean, np.sqrt(np.maximum(tot2 / n - mean * mean, 0.0)), hi <= lo


def lane_data(x, mask, ref=None) -> dict:
    """What every grid point on one row mask shares: the shift (the
    columns' float64 means over its rows), their stds, the columns
    constant there (max = min), and the plane, label and mask in row
    blocks on the device (the plane's and the label's kept on ``ref``)."""
    rows = np.asarray(mask) > 0
    shift, std, const = _moments(x, rows)
    held = {} if ref is None else ref.setdefault("_device", {})
    if held.get("x") is not x:
        held.update(x=x, xb=_blocks(np.asarray(x, np.float32)), yb=None)
    return {"shift": shift, "const": const, "std": std, "xb": held["xb"],
            "held": held, "mb": _blocks(np.asarray(mask, np.float32)),
            "rows": rows}


def lane(data, y, point, precision="f32", max_iter=None) -> dict:
    """One lane's fit by the learner: its (β, b), its iterations, the
    pieces of its last solve."""
    _, jnp = _jnp()
    family, link = point["family"], point["link"]
    lam, tol = float(point["reg_param"]), float(point["tol"])
    iters_max = int(point["max_iter"] if max_iter is None else max_iter)
    held = data["held"]
    if held.get("yb") is None:
        held["yb"] = _blocks(np.asarray(y, np.float32))
    yb = held["yb"]
    shift32 = jnp.asarray(data["shift"], jnp.float32)
    mu0 = np.asarray(y, np.float64)[data["rows"]]
    if family == "poisson":
        mu0 = np.where(mu0 == 0.0, DELTA, mu0)
    z0 = mu0 if link == "identity" else np.log(mu0)
    zshift = float(np.float32(z0.mean()))
    sums = _pass(data["xb"], yb, data["mb"], shift32,
                 jnp.zeros(len(shift32), jnp.float32), jnp.float32(zshift),
                 family=family, link=link, start=True, precision=precision)
    beta, b, parts = wls(sums, data["shift"], data["const"], lam, zshift,
                         True)
    it, projected = 0, 0
    while (family, link) != ("gaussian", "identity") and it < iters_max:
        off = b + beta @ data["shift"]
        sums = _pass(data["xb"], yb, data["mb"], shift32,
                     jnp.asarray(beta, jnp.float32), jnp.float32(off),
                     family=family, link=link, start=False,
                     precision=precision)
        projected += int(sums[-1])
        nbeta, nb, parts = wls(sums, data["shift"], data["const"], lam,
                               float(np.float32(off)), False)
        step = max(np.abs(nbeta - beta).max(), abs(nb - b))
        beta, b, it = nbeta, nb, it + 1
        if step < tol:
            break
    return {"beta": beta, "b": b, "iters": it, "parts": parts,
            "projected": projected, "family": family, "link": link,
            "std": data["std"]}


def predict(fit_or_point, x, beta, b) -> np.ndarray:
    """μ = project(g⁻¹(xβ + b)) in float64."""
    eta = np.asarray(x, np.float64) @ np.asarray(beta, np.float64) + b
    return _project(_unlink(eta, fit_or_point["link"]),
                    fit_or_point["family"])


def rmse(y, mu) -> float:
    return float(np.sqrt(np.mean((np.asarray(y, np.float64) - mu) ** 2)))


# ----------------------------------------------------------- the comparison
def _params(cfg, point):
    return {**cfg.get("estimator_defaults", {}), **_named_point(cfg, point)}


def _fold_metrics(cfg, ref, x, precision):
    """[point][fold] RMSE of the reference's own fit of every fold lane;
    those of the reference's own plane are kept on ``ref``."""
    points = grid_points(cfg)
    own = x is ref["x"]
    if own and precision in ref.get("_fold_metrics", {}):
        return points, ref["_fold_metrics"][precision]
    y = ref["y"]
    values = [[0.0] * len(ref["folds"]) for _ in points]
    for j, (train, val) in enumerate(ref["folds"]):
        data = lane_data(x, train.astype(np.float32), ref)
        x_val = np.asarray(x[val], np.float64)
        for i, point in enumerate(points):
            fit = lane(data, y, _params(cfg, point), precision)
            values[i][j] = rmse(y[val], predict(fit, x_val, fit["beta"],
                                                fit["b"]))
        del data, x_val
    if own:
        ref.setdefault("_fold_metrics", {})[precision] = values
    return points, values


def _refit(cfg, ref, x, point, precision):
    """The reference's own refit of ``point`` on the refit rows; that of
    its own plane at float32 is kept on ``ref``."""
    key = tuple(sorted((k, str(v)) for k, v in point.items()))
    own = x is ref["x"] and precision == "f32"
    if own and key in ref.get("_refits", {}):
        return ref["_refits"][key]
    split = cfg["splitter"]
    mask = refit_rows(len(ref["y"]), ref["seed"],
                      int(split["max_training_sample"])).astype(np.float32)
    fit = lane(lane_data(x, mask, ref), ref["y"], _params(cfg, point),
               precision)
    if own:
        ref.setdefault("_refits", {})[key] = fit
    return fit


def compare(cfg, ref, product) -> list[dict]:
    """The family's numbers; ``cfg`` and ``product["summary"]`` in the
    coded form (``coded_config``, ``coded_summary``)."""
    limits, winner = cfg["limits"], product["winner"]
    out = []
    points, mine = _fold_metrics(cfg, ref, ref["x"], "f32")
    fgap = 0.0
    for point, row in zip(points, mine):
        r = result_of(product["summary"], point)
        if r is None or len(r["metricValues"]) != len(row):
            fgap = np.inf
            continue
        theirs = -np.asarray(r["metricValues"], np.float64)
        fgap = max(fgap, float((np.abs(theirs - row) / np.asarray(row)).max()))
    out.append(entry("fold_metric_gap", fgap, limits["fold_metric_gap"]))
    point = {k: v for k, v in _coded_point(cfg, winner["grid"]).items()
             if k in points[0]}
    arrays = winner["arrays"]
    w = np.asarray(arrays.get("weights", np.zeros(0)), np.float64)
    b = np.asarray(arrays.get("intercept", np.zeros(0)), np.float64)
    found = dict.fromkeys(FOUND, np.inf)
    if w.shape == (ref["x"].shape[1],) and b.size == 1:
        b = float(b)
        fit = _refit(cfg, ref, ref["x"], point, "f32")
        g_prog, g_ref = w * fit["std"], fit["beta"] * fit["std"]
        val = ref["folds"][0][1]
        mu_prog = predict(fit, ref["x"][val], w, b)
        mu_ref = predict(fit, ref["x"][val], fit["beta"], fit["b"])
        f_ref = objective(fit["parts"], fit["beta"], fit["b"])
        f_prog = objective(fit["parts"], w, b)
        found = {
            "coef_gap": float(np.abs(g_prog - g_ref).max()
                              / max(np.abs(g_ref).max(), 1e-30)),
            "intercept_gap": abs(b - fit["b"]) / max(abs(fit["b"]), 1e-30),
            "objective_gap": abs(f_prog - f_ref) / max(abs(f_ref), 1e-30),
            "pred_gap": float((np.abs(mu_prog - mu_ref)
                               / np.abs(mu_ref)).max()),
        }
    for name in FOUND:
        out.append(entry(name, found[name], limits[name]))
    states = product["states"]
    out.append(entry("glr_learner_other",
                     int(states.get("glr_irls") != cfg["glr_irls"]),
                     limits["glr_learner_other"]))
    out.append(entry(
        "precision_other",
        int(states.get("glm_precision") != cfg["solver_precision"]),
        limits["precision_other"]))
    return out


def stand_in(cfg, ref, x, precision) -> dict:
    """The reference's own sweep at ``precision`` on the plane ``x``: every
    fold lane fitted and scored, the best grid point (least mean RMSE)
    refitted on the refit rows; in the coded form."""
    points, values = _fold_metrics(cfg, ref, x, precision)
    best = int(np.argmin([np.mean(v) for v in values]))
    fit = _refit(cfg, ref, x, points[best], precision)
    named = [_named_point(cfg, p) for p in points]
    summary = coded_summary(cfg, {
        "bestModelType": "OpGeneralizedLinearRegression",
        "bestGrid": named[best],
        "validationResults": [
            {"grid": p, "metricValues": list(v)}
            for p, v in zip(named, values)],
        "candidateAttempts": [],
    })
    return {
        "summary": summary,
        "winner": {"grid": named[best],
                   "arrays": {"weights": fit["beta"],
                              "intercept": np.asarray(fit["b"])},
                   "thresholds": None},
        "states": {"glr_irls": cfg["glr_irls"],
                   "glm_precision": cfg["solver_precision"]},
    }
