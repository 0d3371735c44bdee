"""The check of a multinomial logistic-regression sweep (Spark ML's
LogisticRegression, multinomial family, as TransmogrifAI's multiclass
selector runs it by default), with its plain reference: float32
``jax.numpy`` at ``highest`` matmul precision, one lane at a time, on an
EXPLICITLY standardized plane, every product in row blocks. A configuration
names this file under ``"check"``. Nothing here imports the program.

The learner (``cfg["learner"]`` states the same), for one lane: grid point
(λ = reg_param, α = elastic_net_param) on row mask m over n rows, label y in
{0, …, K - 1}, F columns::

    μ_j, σ_j      mean and std (÷ n) of column j over the lane's rows
    s_j           max(σ_j, 2e-3 · sqrt(mean over the lane's rows of
                  (x_j - ḡ_j)²)), ḡ the plane's column means
    xs_ij         (x_ij - μ_j) / s_j; 0 for a column constant on the lane's
                  rows (max = min), whose coefficients are pinned at 0
    z_i           xs_i β + b            β [F, K] standardized, b [K]
    F(β, b)       (1/n) Σ_i m_i (logsumexp(z_i) - z_i,y_i)
                  + ½ λ(1 - α) Σ β² + λα Σ |β|     (no pivot class, b free)
    start         β = 0, b_k = log(1 + n_k) - mean_k log(1 + n_k)
    optimizer     L-BFGS (OWL-QN where λα > 0), max_iter iterations,
                  ``HISTORY`` pairs, every candidate w + t·d of ``STEPS``
                  evaluated, the largest t that passes Armijo (c1 = 1e-4)
                  else the least F; a lane moves iff that F is lower and
                  max|pg| > 1e-7 · max(1, |F|); a lane that does not move
                  resets its history and its scale to
                  1 / (½ · #columns not constant + λ(1 - α))
    end           β / s, b - Σ_j β_j μ_j / s_j, b centred to mean 0 (and β
                  too at λ = 0)

It reads, of the LAST timed sweep:

* every fold lane: its own fit of each grid point on each fold's training
  rows, the fold's validation rows scored by the weighted F1 in float64,
  the widest gap to the metric the program reported (``fold_metric_gap``);
* the winner's refit against its own refit of the same grid point on every
  training row: ``coef_gap`` (the standardized coefficients, the widest
  absolute gap), ``intercept_gap``, ``objective_gap`` (F at the program's
  point against F at its own, relative) and ``class_prob_gap`` (the
  ``[rows, K]`` probabilities on the first fold's validation rows);
* what the program states of itself: ``glm_learner_other`` (1 unless it
  states the configuration's ``logistic_multinomial``: one softmax fit a
  lane) and ``precision_other`` (1 unless its GLM products state the
  configuration's ``solver_precision``).

``precision="bf16"`` computes every product of the reference at one
bfloat16 pass (operands rounded, float32 sums): the control, through
``stand_in``.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.checks.forest_multiclass_winner import weighted_f1
from benchmarks.lib.reference import entry, grid_points, result_of

#: rows a block of every product over the plane
ROW_BLOCK = 65536
#: host threads of the float64 passes over the plane's row blocks
WORKERS = 4
STEPS = (1.0, 0.5, 0.25, 0.1, 0.03, 0.01, 0.003)
HISTORY = 8
ARMIJO = 1e-4
GTOL = 1e-7
NOISE_FLOOR = 2e-3
FOUND = ("coef_gap", "intercept_gap", "objective_gap", "class_prob_gap")


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


# ------------------------------------------------------------ one lane
def _chunks(n: int):
    return [slice(i, min(i + ROW_BLOCK, n)) for i in range(0, n, ROW_BLOCK)]


def _each_chunk(fn, n: int) -> list:
    """``fn`` of every row block, in block order, on ``WORKERS`` threads
    (numpy lets go of the GIL inside each block's passes)."""
    with ThreadPoolExecutor(max_workers=WORKERS) as pool:
        return list(pool.map(fn, _chunks(n)))


def standardization(x: np.ndarray, mask: np.ndarray):
    """(μ, s, const) of a lane, summed in float64 over its rows by row
    blocks, the blocks' sums added in block order."""
    rows = np.asarray(mask) > 0
    n, f = x.shape
    tot, tot2, allsum = np.zeros(f), np.zeros(f), np.zeros(f)
    lo, hi = np.full(f, np.inf), np.full(f, -np.inf)

    def block(c):
        xc = np.asarray(x[c], np.float64)
        xm = xc[rows[c]]
        if not len(xm):
            return xc.sum(axis=0), None
        return xc.sum(axis=0), (xm.sum(axis=0), (xm * xm).sum(axis=0),
                                xm.min(axis=0), xm.max(axis=0))

    for every, kept in _each_chunk(block, n):
        allsum += every
        if kept is not None:
            tot += kept[0]
            tot2 += kept[1]
            lo = np.minimum(lo, kept[2])
            hi = np.maximum(hi, kept[3])
    m = max(int(rows.sum()), 1)
    mu = tot / m
    sigma = np.sqrt(np.maximum(tot2 / m - mu * mu, 0.0))
    g = allsum / n
    # mean over the lane's rows of (x - g)^2 = sigma^2 + (mu - g)^2
    rms = np.sqrt(sigma * sigma + (mu - g) ** 2)
    const = hi <= lo
    s = np.where(const, 1.0, np.maximum(sigma, NOISE_FLOOR * rms + 1e-12))
    return mu, s, const


def standardized_plane(x: np.ndarray, mu, s, const) -> np.ndarray:
    """float32 xs = (x - μ) / s (0 on constant columns), by row blocks."""
    out = np.empty(x.shape, np.float32)

    def block(c):
        out[c] = np.where(const, 0.0, (np.asarray(x[c], np.float64) - mu) / s)

    _each_chunk(block, x.shape[0])
    return out


def _blocks(a, fill=0.0):
    """[nblk, rows, ...]: ``a`` padded with ``fill`` to whole blocks of
    ``ROW_BLOCK`` rows (one block of all the rows, to a multiple of 8,
    where there are fewer)."""
    _, jnp = _jnp()
    n = a.shape[0]
    rows = min(ROW_BLOCK, -(-n // 8) * 8)
    pad = -n % rows
    a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1), constant_values=fill)
    return a.reshape(-1, rows, *a.shape[1:])


def _objective_fn(xs_b, y_b, m_b, n, l1, l2, precision):
    """F [T] and, for one point, its smooth gradient, by row blocks.
    ``beta`` [T, F, K], ``b`` [T, K]."""
    jax, jnp = _jnp()

    def value(beta, b, grad=False):
        t, f, k = beta.shape
        flat = jnp.moveaxis(beta, 0, 1).reshape(f, t * k)

        def body(acc, blk):
            xb, yb, mb = blk
            z = _dot(xb, flat, precision).reshape(-1, t, k) + b[None]
            lse = jax.nn.logsumexp(z, axis=-1)
            # the label's logit picked by a one-hot product, not a gather:
            # the same number (z_y plus zeros), and the TPU gathers slowly
            hot = jax.nn.one_hot(yb, k, dtype=z.dtype)
            zy = (z * hot[:, None, :]).sum(-1)
            loss = acc[0] + ((lse - zy) * mb[:, None]).sum(0)
            if not grad:
                return (loss,), None
            r = (jax.nn.softmax(z[:, 0], axis=-1) - hot) * mb[:, None]
            return (loss, acc[1] + _dot(xb.T, r, precision),
                    acc[2] + r.sum(0)), None

        acc0 = (jnp.zeros(t, jnp.float32),)
        if grad:
            acc0 += (jnp.zeros((f, k), jnp.float32), jnp.zeros(k, jnp.float32))
        acc, _ = jax.lax.scan(body, acc0, (xs_b, y_b, m_b))
        pen = 0.5 * l2 * (beta * beta).sum((1, 2)) + l1 * jnp.abs(beta).sum(
            (1, 2))
        if not grad:
            return acc[0] / n + pen
        return acc[0] / n + pen, acc[1] / n + l2 * beta[0], acc[2] / n

    return value


def _fit_program(xs, y, mask, const, lam, alpha, num_iters, classes,
                 precision):
    """The learner's L-BFGS / OWL-QN on one lane's standardized plane."""
    jax, jnp = _jnp()
    f = xs.shape[1]
    k = classes
    p = f * k + k
    m = mask.astype(jnp.float32)
    n = jnp.maximum(m.sum(), 1.0)
    xs_b, y_b, m_b = _blocks(xs), _blocks(y, 0), _blocks(m)
    l1, l2 = lam * alpha, lam * (1.0 - alpha)
    value = _objective_fn(xs_b, y_b, m_b, n, l1, l2, precision)
    pinned = jnp.concatenate(
        [jnp.repeat(const, k), jnp.zeros(k, bool)])
    l1v = jnp.concatenate([jnp.full(f * k, l1), jnp.zeros(k)])
    ts = jnp.asarray(STEPS, jnp.float32)

    def split(w):
        return w[..., : f * k].reshape(*w.shape[:-1], f, k), w[..., f * k:]

    def f_and_g(w):
        beta, b = split(w)
        fv, gb_, gi = value(beta[None], b[None], grad=True)
        g = jnp.concatenate([gb_.reshape(-1), gi])
        return fv[0], jnp.where(pinned, 0.0, g)

    def pseudo(w, g):
        gp, gm = g + l1v, g - l1v
        at0 = jnp.where(gm > 0, gm, jnp.where(gp < 0, gp, 0.0))
        return jnp.where(w > 0, gp, jnp.where(w < 0, gm, at0))

    gamma_reset = 1.0 / jnp.maximum(
        0.5 * (~const).sum().astype(jnp.float32) + l2, 1e-6)

    def step(carry, _):
        w, fw, g, S, Y, rho, gamma, moved = carry
        pg = pseudo(w, g)
        q, alphas = pg, []
        for i in range(HISTORY - 1, -1, -1):
            a = rho[i] * (S[i] * q).sum()
            q = q - a * Y[i]
            alphas.append(a)
        r = gamma * q
        for i in range(HISTORY):
            r = r + S[i] * (alphas[HISTORY - 1 - i] - rho[i] * (Y[i] * r).sum())
        d = -r
        d = jnp.where((l1v > 0) & (d * pg >= 0), 0.0, d)
        orth = jnp.where(w != 0, jnp.sign(w), jnp.sign(-pg))
        cand = w[None] + ts[:, None] * d[None]
        cand = jnp.where((l1v > 0) & (cand * orth < 0), 0.0, cand)
        beta, b = split(cand)
        fc = value(beta, b)
        ok = fc <= fw + ARMIJO * ((cand - w[None]) * pg[None]).sum(-1)
        idx = jnp.where(ok.any(), jnp.argmax(ok), jnp.argmin(fc))
        done = jnp.abs(pg).max() <= GTOL * jnp.maximum(1.0, jnp.abs(fw))
        move = (fc[idx] < fw) & ~done
        w2 = jnp.where(move, cand[idx], w)
        f2, g2 = f_and_g(w2)
        f2 = jnp.where(move, f2, fw)
        s, yv = w2 - w, g2 - g
        sy = (s * yv).sum()
        good = move & (sy > 1e-8 * jnp.sqrt((s * s).sum() * (yv * yv).sum())
                       + 1e-20)
        S2 = jnp.where(good, jnp.concatenate([S[1:], s[None]]), S)
        Y2 = jnp.where(good, jnp.concatenate([Y[1:], yv[None]]), Y)
        rho2 = jnp.where(good, jnp.concatenate([rho[1:], (1.0 / jnp.where(
            good, sy, 1.0))[None]]), rho)
        gamma2 = jnp.where(good, sy / jnp.maximum((yv * yv).sum(), 1e-20),
                           gamma)
        reset = ~move & ~done
        S2 = jnp.where(reset, 0.0, S2)
        Y2 = jnp.where(reset, 0.0, Y2)
        rho2 = jnp.where(reset, 0.0, rho2)
        gamma2 = jnp.where(reset, gamma_reset, gamma2)
        return (w2, f2, g2, S2, Y2, rho2, gamma2,
                moved + move.astype(jnp.int32)), None

    counts = jnp.zeros(k).at[y].add(m)
    prior = jnp.log1p(counts)
    w0 = jnp.concatenate([jnp.zeros(f * k), prior - prior.mean()])
    f0, g0 = f_and_g(w0)
    carry = (w0, f0, g0, jnp.zeros((HISTORY, p)), jnp.zeros((HISTORY, p)),
             jnp.zeros(HISTORY), gamma_reset, jnp.int32(0))
    (w, fw, *_, moved), _ = jax.lax.scan(step, carry, None, length=num_iters)
    beta, b = split(w)
    return beta, b, fw, moved


_PROGRAMS: dict = {}


def _programs():
    if not _PROGRAMS:
        jax, _ = _jnp()
        _PROGRAMS.update(
            fit=jax.jit(_fit_program, static_argnames=(
                "num_iters", "classes", "precision")),
            objective=jax.jit(_objective_at, static_argnames=("precision",)),
            logits=jax.jit(_logits, static_argnames=("precision",)),
        )
    return _PROGRAMS


def _objective_at(xs, y, mask, beta, b, lam, alpha, precision):
    _, jnp = _jnp()
    m = mask.astype(jnp.float32)
    value = _objective_fn(_blocks(xs), _blocks(y, 0), _blocks(m),
                          jnp.maximum(m.sum(), 1.0), lam * alpha,
                          lam * (1.0 - alpha), precision)
    return value(beta[None], b[None])[0]


def _logits(xs, beta, b, precision):
    _, jnp = _jnp()
    z = [_dot(blk, beta, precision) for blk in _blocks(xs)]
    return jnp.concatenate(z)[: xs.shape[0]] + b[None]


def lane_plane(x, mask) -> dict:
    """What every grid point on one row mask shares: the standardization
    (``mu``, ``s``, ``const``) and the standardized plane ``xs`` (on the
    device)."""
    _, jnp = _jnp()
    mu, s, const = standardization(x, mask)
    return {"mu": mu, "s": s, "const": const,
            "xs": jnp.asarray(standardized_plane(x, mu, s, const))}


def lane(x, y, mask, params, classes, precision="f32", plane=None):
    """One lane's fit: a dict of its standardization (``mu``, ``s``,
    ``const``), its standardized plane ``xs`` (on the device), its
    standardized ``beta`` [F, K] and ``b`` [K], the original-scale
    ``weights`` and centred ``intercept``, its objective ``F`` and the
    iterations in which it moved. ``plane`` is ``lane_plane(x, mask)``
    where the caller has it."""
    _, jnp = _jnp()
    if plane is None:
        plane = lane_plane(x, mask)
    mu, s, const = plane["mu"], plane["s"], plane["const"]
    lam = float(params["reg_param"])
    alpha = float(params["elastic_net_param"])
    beta, b, fw, moved = _programs()["fit"](
        plane["xs"], jnp.asarray(y, jnp.int32), jnp.asarray(mask, jnp.float32),
        jnp.asarray(const), jnp.float32(lam), jnp.float32(alpha),
        num_iters=int(params["max_iter"]), classes=classes,
        precision=precision)
    beta, b = np.asarray(beta, np.float64), np.asarray(b, np.float64)
    weights = np.where(const[:, None], 0.0, beta / s[:, None])
    intercept = b - (beta * (mu / s)[:, None]).sum(axis=0)
    intercept = intercept - intercept.mean()
    if lam == 0.0:
        weights = weights - weights.mean()
    return {**plane, "beta": beta, "b": b, "weights": weights,
            "intercept": intercept, "F": float(fw), "moved": int(moved),
            "lam": lam, "alpha": alpha}


def standardized_point(fit: dict, weights, intercept):
    """An original-scale (weights [F, K], intercept [K]) in ``fit``'s
    standardized coordinates: the same logits on every row."""
    w = np.asarray(weights, np.float64)
    beta = w * fit["s"][:, None]
    b = np.asarray(intercept, np.float64) + (w * fit["mu"][:, None]).sum(0)
    return beta, b


def logits(fit: dict, beta, b, precision="f32") -> np.ndarray:
    _, jnp = _jnp()
    return np.asarray(_programs()["logits"](
        fit["xs"], jnp.asarray(beta, jnp.float32), jnp.asarray(b, jnp.float32),
        precision=precision))


def objective(fit: dict, y, mask, beta, b) -> float:
    _, jnp = _jnp()
    return float(_programs()["objective"](
        fit["xs"], jnp.asarray(y, jnp.int32), jnp.asarray(mask, jnp.float32),
        jnp.asarray(beta, jnp.float32), jnp.asarray(b, jnp.float32),
        jnp.float32(fit["lam"]), jnp.float32(fit["alpha"]), precision="f32"))


def _softmax(z: np.ndarray) -> np.ndarray:
    z = np.asarray(z, np.float64)
    e = np.exp(z - z.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


# ----------------------------------------------------------- the comparison
def _params(cfg, point):
    return {**cfg.get("estimator_defaults", {}), **point}


def _fold_metrics(cfg, ref, x, precision):
    """[point][fold] weighted F1 of the reference's own fit of every fold
    lane; those of the reference's own plane are kept on ``ref``."""
    points = grid_points(cfg)
    own = x is ref["x"]
    if own and precision in ref.get("_fold_metrics", {}):
        return points, ref["_fold_metrics"][precision]
    classes = int(cfg["classes"])
    y = ref["y"].astype(np.int64)
    values = [[0.0] * len(ref["folds"]) for _ in points]
    for j, (train, val) in enumerate(ref["folds"]):
        mask = train.astype(np.float32)
        plane = lane_plane(x, mask)
        for i, point in enumerate(points):
            fit = lane(x, y, mask, _params(cfg, point), classes, precision,
                       plane)
            z = logits(fit, fit["beta"], fit["b"], precision)
            # ties go to the lowest class, as numpy's arg max takes them
            values[i][j] = weighted_f1(y[val], z[val].argmax(axis=1))
        del plane, fit
    if own:
        ref.setdefault("_fold_metrics", {})[precision] = values
    return points, values


def _refit(cfg, ref, x, point, precision):
    """The reference's own refit of ``point`` on every training row; that
    of its own plane at float32 is kept on ``ref``."""
    key = tuple(sorted((k, float(v)) for k, v in point.items()))
    own = x is ref["x"] and precision == "f32"
    if own and key in ref.get("_refits", {}):
        return ref["_refits"][key]
    mask = np.ones(len(ref["y"]), np.float32)
    # one standardized plane of every training row for all the refits
    plane = ref.get("_refit_plane") if x is ref["x"] else None
    if plane is None:
        plane = lane_plane(x, mask)
        if x is ref["x"]:
            ref["_refit_plane"] = plane
    fit = lane(x, ref["y"].astype(np.int64), mask, _params(cfg, point),
               int(cfg["classes"]), precision, plane)
    if own:
        ref.setdefault("_refits", {})[key] = fit
    return fit


def compare(cfg, ref, product) -> list[dict]:
    limits, winner = cfg["limits"], product["winner"]
    classes = int(cfg["classes"])
    out = []
    points, mine = _fold_metrics(cfg, ref, ref["x"], "f32")
    fgap = 0.0
    for point, row in zip(points, mine):
        r = result_of(product["summary"], point)
        if r is None or len(r["metricValues"]) != len(row):
            fgap = np.inf
            continue
        fgap = max(fgap, float(np.abs(np.asarray(r["metricValues"]) - row).max()))
    out.append(entry("fold_metric_gap", fgap, limits["fold_metric_gap"]))
    point = {k: v for k, v in winner["grid"].items() if k in points[0]}
    arrays = winner["arrays"]
    w = np.asarray(arrays.get("weights", np.zeros(0)), np.float64)
    b = np.asarray(arrays.get("intercept", np.zeros(0)), np.float64)
    found = dict.fromkeys(FOUND, np.inf)
    if w.shape == (ref["x"].shape[1], classes) and b.shape == (classes,):
        fit = _refit(cfg, ref, ref["x"], point, "f32")
        beta, b_std = standardized_point(fit, w, b)
        y = ref["y"].astype(np.int64)
        f_prog = objective(fit, y, np.ones(len(y), np.float32), beta, b_std)
        val = ref["folds"][0][1]
        p_prog = _softmax(logits(fit, beta, b_std)[val])
        p_ref = _softmax(logits(fit, fit["beta"], fit["b"])[val])
        found = {
            "coef_gap": float(np.abs(beta - fit["beta"]).max()),
            "intercept_gap": float(np.abs(b - fit["intercept"]).max()),
            "objective_gap": abs(f_prog - fit["F"]) / max(abs(fit["F"]),
                                                          1e-30),
            "class_prob_gap": float(np.abs(p_prog - p_ref).max()),
        }
    for name in FOUND:
        out.append(entry(name, found[name], limits[name]))
    states = product["states"]
    out.append(entry(
        "glm_learner_other",
        int(states.get("logistic_multinomial") != cfg["logistic_multinomial"]),
        limits["glm_learner_other"]))
    out.append(entry(
        "precision_other",
        int(states.get("glm_precision") != cfg["solver_precision"]),
        limits["precision_other"]))
    return out


def stand_in(cfg, ref, x, precision) -> dict:
    """The reference's own sweep at ``precision`` on the plane ``x``: every
    fold lane fitted and scored, the best grid point refitted on every
    training row."""
    points, values = _fold_metrics(cfg, ref, x, precision)
    best = int(np.argmax([np.mean(v) for v in values]))
    fit = _refit(cfg, ref, x, points[best], precision)
    return {
        "summary": {
            "bestModelType": "OpLogisticRegression",
            "bestGrid": dict(points[best]),
            "validationResults": [
                {"grid": dict(p), "metricValues": list(v)}
                for p, v in zip(points, values)],
            "candidateAttempts": [],
        },
        "winner": {"grid": dict(points[best]),
                   "arrays": {"weights": fit["weights"],
                              "intercept": fit["intercept"]},
                   "thresholds": None},
        "states": {"logistic_multinomial": cfg["logistic_multinomial"],
                   "glm_precision": cfg["solver_precision"]},
    }
