"""Pure-JAX training solvers for generalized linear models.

Replaces Spark MLlib's L-BFGS/OWL-QN/WLS native-BLAS path (SURVEY.md §2.5
item 2) with XLA-native solvers designed for the TPU execution model:

  * fixed iteration counts + ``lax.scan`` -> one compiled graph, static
    shapes, no host round-trips per iteration;
  * every solver is ``vmap``-able over its hyperparameters, so a model
    selector's param grid trains as ONE batched XLA computation instead of a
    driver thread pool (OpValidator.scala:363-367 -> vmap axis);
  * row masks (not dynamic slicing) express CV folds / resampling, keeping
    one compiled shape across folds.

Losses follow Spark semantics: mean log-loss / squared error over unmasked
rows + lambda * (alpha*||w||_1 + (1-alpha)/2*||w||_2^2), intercept
unregularized, features standardized internally (standardization=true
default) with coefficients mapped back to the original scale.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


class GLMParams(NamedTuple):
    weights: jax.Array    # [D] or [D, C]
    intercept: jax.Array  # scalar or [C]


def _effectively_constant(
    std: jax.Array, scale: jax.Array, rel_tol: float = 1e-5
) -> jax.Array:
    """Columns whose std is ~float-noise relative to their magnitude.

    An exact `std > 0` check misses fold-constant columns: a column stuck
    at c within the mask computes var ≈ (c·eps)² > 0 through float
    cancellation, and dividing by that phantom std amplifies weights into
    garbage. ``rel_tol`` calibrates to the two-pass centered sum's error
    (~eps·c; 1e-5 covers it). The batched logistic solver instead detects
    constants exactly via masked min/max — order-invariant, so sharded and
    single-device runs agree bit-for-bit."""
    return std <= jnp.maximum(rel_tol * scale, 1e-12)


def _masked_minmax(x: jax.Array, rm: jax.Array):
    """Per-(lane, column) masked min/max: ``([K, D] min, [K, D] max)`` for
    x [N, D] under masks rm [K, N].

    The one-shot broadcast form (``jnp.where(rm[:, :, None] > 0, x[None],
    ±big)`` reduced over axis 1) materializes O(K·N·D) temporaries — ~100 MB
    per reduction at Titanic sweep shapes, and the allocation scales with
    the grid. ``lax.map`` scans the K mask lanes instead, so peak extra
    memory is one [N, D] buffer regardless of K. min/max are exact under
    ANY association, so the result is bit-identical to the broadcast form
    (and invariant across shardings — the property the constant-column
    gate relies on)."""
    big = jnp.asarray(jnp.finfo(x.dtype).max, x.dtype)

    def one(mask_row):  # [N] -> ([D], [D])
        mb = mask_row[:, None] > 0
        return (
            jnp.min(jnp.where(mb, x, big), axis=0),
            jnp.max(jnp.where(mb, x, -big), axis=0),
        )

    return jax.lax.map(one, rm)


def _standardize(x: jax.Array, row_mask: jax.Array):
    n = jnp.maximum(row_mask.sum(), 1.0)
    mean = (x * row_mask[:, None]).sum(0) / n
    var = ((x - mean) ** 2 * row_mask[:, None]).sum(0) / n
    std = jnp.sqrt(var)
    const = _effectively_constant(std, jnp.sqrt(var + mean**2))
    safe = jnp.where(const, 1.0, std)
    xs = jnp.where(row_mask[:, None], (x - mean) / safe, 0.0)
    # zero the constant columns entirely: (x - mean) there is pure noise
    xs = jnp.where(const[None, :], 0.0, xs)
    return xs, mean, safe, const


def _scale_only(x: jax.Array, row_mask: jax.Array, std, const):
    """Scale-without-centering variant for fit_intercept=False (Spark
    parity: centering would bake an implicit mean·w offset into training
    that predict never applies). Constant columns stay zeroed — otherwise
    they would absorb a pseudo-intercept the caller asked not to fit."""
    xs = jnp.where(row_mask[:, None] > 0, x / std, 0.0)
    return jnp.where(const[None, :], 0.0, xs)


def _soft_threshold(w: jax.Array, t: jax.Array) -> jax.Array:
    return jnp.sign(w) * jnp.maximum(jnp.abs(w) - t, 0.0)


def _fista(grad_fn, prox_fn, w0, step, num_iters):
    """Accelerated proximal gradient with fixed iterations (lax.scan)."""

    def body(carry, _):
        w_prev, z, t = carry
        g = grad_fn(z)
        w_next = prox_fn(z - step * g, step)
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_next = w_next + ((t - 1.0) / t_next) * (w_next - w_prev)
        return (w_next, z_next, t_next), None

    (w, _, _), _ = jax.lax.scan(body, (w0, w0, jnp.array(1.0)), None, length=num_iters)
    return w


@partial(jax.jit, static_argnames=("num_iters", "fit_intercept"))
def fit_linear_batched(
    x: jax.Array,            # [N, D] SHARED feature matrix
    y: jax.Array,            # [N]
    row_masks: jax.Array,    # [K, N] per-fit masks (folds x grid)
    reg_params: jax.Array,   # [K]
    elastic_nets: jax.Array,  # [K]
    num_iters: int = 200,
    fit_intercept: bool = True,
) -> GLMParams:
    """K elastic-net linear regressions sharing ONE feature matrix.

    The regression selector's LinearRegression family previously fit
    sequentially — folds x grid separate fit_linear dispatches, each a
    host round trip for microseconds of FLOPs. Lanes batch as GEMM columns exactly like
    fit_logistic_binary_batched: per iteration one [N, K] forward GEMM +
    one [K, D] gradient GEMM on the shared x, with per-lane
    standardization applied implicitly (Xs_k' r = (xc' (r·m) −
    mean_k·Σ(r·m)) / std_k, xc globally shifted so one-pass lane moments
    don't cancel in f32). Per-lane semantics mirror fit_linear: same FISTA,
    same effectively-constant column rule, same no-intercept
    scale-without-centering parity. Returns weights [K, D], intercept [K].
    """
    rm = row_masks.astype(x.dtype)
    n = jnp.maximum(rm.sum(axis=1), 1.0)                    # [K]
    gshift = x.mean(axis=0)
    xc = x - gshift[None, :]
    s1 = rm @ xc                                            # [K, D]
    s2 = rm @ (xc * xc)
    mean_shift = s1 / n[:, None]
    var = jnp.maximum(s2 / n[:, None] - mean_shift**2, 0.0)
    std = jnp.sqrt(var)
    mean_true = mean_shift + gshift[None, :]
    # fold-constant detection must be EXACT (masked min/max, like
    # fit_logistic_binary_batched): an all-zero-in-mask column has
    # mean_true ~ 0, so the std-relative-to-scale test degenerates
    # (scale == std) and the phantom one-pass std would pass through —
    # the column then absorbs a garbage weight that corrupts held-out
    # predictions wherever the column is nonzero outside the mask
    xmin, xmax = _masked_minmax(x, rm)                      # [K, D] each
    const = (xmax <= xmin) | _effectively_constant(
        std, jnp.sqrt(var + mean_true**2)
    )
    safe = jnp.where(const, 1.0, std)
    if not fit_intercept:
        # Spark parity: scale only, never center x OR y (see fit_linear)
        mean_shift = jnp.zeros_like(mean_shift)
        xc = x
        ym = jnp.zeros_like(n)
    else:
        ym = (rm @ y) / n                                   # [K]
    yc = jnp.where(rm > 0, y[None, :] - ym[:, None], 0.0)   # [K, N]
    l1 = (reg_params * elastic_nets)[:, None]
    l2 = (reg_params * (1.0 - elastic_nets))[:, None]

    def grad(w_std):
        # w_std [K, D] in standardized space; const columns pinned at 0
        v = jnp.where(const, 0.0, w_std / safe)             # [K, D]
        logits = xc @ v.T - (mean_shift * v).sum(axis=1)[None, :]  # [N, K]
        r = (logits.T - yc) * rm                            # [K, N]
        g_raw = r @ xc - mean_shift * r.sum(axis=1)[:, None]
        g = jnp.where(const, 0.0, g_raw / safe) / n[:, None]
        return g + l2 * w_std

    def prox(w, step):
        return _soft_threshold(w, step * l1)

    # per-lane standardized column second moments: 1 for centered columns,
    # (var + mean^2)/std^2 for the scale-only no-intercept path (a
    # large-mean column there has norm >> 1 — assuming 1 diverges)
    if fit_intercept:
        col2 = jnp.where(const, 0.0, 1.0)
    else:
        col2 = jnp.where(const, 0.0, (var + mean_true**2) / (safe * safe))
    lip = col2.sum(axis=1)[:, None] + l2                     # [K, 1]
    step = 1.0 / jnp.maximum(lip, 1e-6)
    w0 = jnp.zeros((rm.shape[0], x.shape[1]), dtype=x.dtype)
    w_std = _fista(grad, prox, w0, step, num_iters)
    w = jnp.where(const, 0.0, w_std / safe)
    b = ym - (w_std * jnp.where(const, 0.0, mean_true / safe)).sum(axis=1)
    if not fit_intercept:
        b = jnp.zeros_like(b)
    return GLMParams(weights=w, intercept=b)


# --------------------------------------------------------------------------
# Batched L-BFGS / OWL-QN (MLlib LogisticRegression's actual algorithm —
# SURVEY.md §2.5 item 2). First-order FISTA does not converge on
# ill-conditioned one-hot matrices (Titanic 891×957, κ≈2e4) inside any
# reasonable fixed budget; the quasi-Newton direction does. TPU-shaped:
#   * K independent fits (folds × grid) advance in lockstep as rows of one
#     [K, P] parameter matrix — every GEMM stays MXU-sized;
#   * the line search evaluates ALL step candidates with ONE batched GEMM
#     ([T·K] lanes) instead of a data-dependent backtracking loop;
#   * fixed iteration count under `lax.scan` (static shapes, AOT-exportable);
#     converged lanes freeze in place so extra iterations are no-ops.
# OWL-QN (Andrew & Gao 2007) handles per-lane L1 via the pseudo-gradient +
# orthant projection; lanes with l1=0 degrade exactly to plain L-BFGS.
# --------------------------------------------------------------------------

_LBFGS_M = 8           # history pairs (MLlib/breeze default m=10; 8 aligns)
_LS_STEPS = (1.0, 0.5, 0.25, 0.1, 0.03, 0.01, 0.003)  # Armijo candidates
_LS_C1 = 1e-4


def _lbfgs_owlqn(
    value_grad,        # W [K, P] -> (F [K], g_smooth [K, P]); F includes l1
    candidates_value,  # Wc [T, K, P] -> F [T, K]
    p0,                # [K, P] initial params
    l1_mat,            # [K, P] per-component l1 strength (0 on intercepts)
    gamma0,            # [K] initial inverse-Hessian scale (≈ 1/Lipschitz)
    num_iters: int,
    gtol: float = 1e-7,
):
    """Returns argmin params [K, P]. All control flow is branchless so the
    whole optimizer is one scanned XLA program, vmap- and GSPMD-friendly."""
    k_fits, p_dim = p0.shape
    m = _LBFGS_M
    ts = jnp.asarray(_LS_STEPS, dtype=p0.dtype)

    def pseudo_grad(w, g):
        # ∂(f + l1·|w|): sign(w)-side derivative away from 0; at 0 the
        # steepest one-sided descent direction (0 inside the [-l1, l1] band)
        gp = g + l1_mat
        gm = g - l1_mat
        at0 = jnp.where(gm > 0, gm, jnp.where(gp < 0, gp, 0.0))
        return jnp.where(w > 0, gp, jnp.where(w < 0, gm, at0))

    def two_loop(pg, S, Y, rho, gamma):
        q = pg
        alphas = []
        for i in range(m - 1, -1, -1):
            a = rho[i] * (S[i] * q).sum(-1)          # [K]
            q = q - a[:, None] * Y[i]
            alphas.append(a)
        r = gamma[:, None] * q
        for i in range(m):
            a = alphas[m - 1 - i]
            b = rho[i] * (Y[i] * r).sum(-1)
            r = r + S[i] * (a - b)[:, None]
        return -r

    def body(carry, _):
        w, f_cur, g, S, Y, rho, gamma = carry
        pg = pseudo_grad(w, g)
        d = two_loop(pg, S, Y, rho, gamma)
        # OWL-QN: constrain d to a descent direction of the pseudo-gradient
        # on l1-active components (l1=0 lanes pass through untouched)
        d = jnp.where((l1_mat > 0) & (d * pg >= 0), 0.0, d)
        xi = jnp.where(w != 0, jnp.sign(w), jnp.sign(-pg))
        cand = w[None] + ts[:, None, None] * d[None]          # [T, K, P]
        cand = jnp.where((l1_mat > 0) & (cand * xi < 0), 0.0, cand)
        f_cand = candidates_value(cand)                       # [T, K]
        pgd = ((cand - w[None]) * pg[None]).sum(-1)           # [T, K]
        accept = f_cand <= f_cur[None] + _LS_C1 * pgd
        first_ok = jnp.argmax(accept, axis=0)                 # largest t ok
        fallback = jnp.argmin(f_cand, axis=0)
        idx = jnp.where(accept.any(axis=0), first_ok, fallback)
        sel = jax.nn.one_hot(idx, len(_LS_STEPS), dtype=w.dtype, axis=0)
        w_sel = (cand * sel[:, :, None]).sum(0)
        f_sel = (f_cand * sel).sum(0)
        conv = jnp.abs(pg).max(-1) <= gtol * jnp.maximum(1.0, jnp.abs(f_cur))
        move = (f_sel < f_cur) & ~conv
        w_next = jnp.where(move[:, None], w_sel, w)
        f_next_sel, g_next = value_grad(w_next)
        f_next = jnp.where(move, f_next_sel, f_cur)
        s = w_next - w
        yv = g_next - g
        sy = (s * yv).sum(-1)
        # relative curvature gate (breeze-style): tiny-positive f32 sy
        # garbage would otherwise produce huge rho and garbage directions
        s_nrm = jnp.sqrt((s * s).sum(-1))
        y_nrm = jnp.sqrt((yv * yv).sum(-1))
        valid = move & (sy > 1e-8 * s_nrm * y_nrm + 1e-20)
        # line-search failure away from convergence means the quasi-Newton
        # direction went bad (stale/ill-conditioned history): RESET to
        # steepest descent with the 1/Lipschitz scale. Without this the
        # carry never changes and the lane deadlocks at a non-converged
        # point (every later iteration rebuilds the same rejected step).
        fail = ~move & ~conv
        s = jnp.where(valid[:, None], s, 0.0)
        yv = jnp.where(valid[:, None], yv, 0.0)
        rho_new = jnp.where(valid, 1.0 / jnp.where(valid, sy, 1.0), 0.0)
        vslot = valid[None, :, None]
        S_next = jnp.where(vslot, jnp.concatenate([S[1:], s[None]]), S)
        Y_next = jnp.where(vslot, jnp.concatenate([Y[1:], yv[None]]), Y)
        rho_next = jnp.where(
            valid[None, :], jnp.concatenate([rho[1:], rho_new[None]]), rho
        )
        S_next = jnp.where(fail[None, :, None], 0.0, S_next)
        Y_next = jnp.where(fail[None, :, None], 0.0, Y_next)
        rho_next = jnp.where(fail[None, :], 0.0, rho_next)
        gamma_next = jnp.where(
            valid, sy / jnp.maximum((yv * yv).sum(-1), 1e-20), gamma
        )
        gamma_next = jnp.where(fail, gamma00, gamma_next)
        return (w_next, f_next, g_next, S_next, Y_next, rho_next, gamma_next), None

    f0, g0 = value_grad(p0)
    gamma00 = gamma0.astype(p0.dtype)
    S0 = jnp.zeros((m, k_fits, p_dim), dtype=p0.dtype)
    Y0 = jnp.zeros((m, k_fits, p_dim), dtype=p0.dtype)
    rho0 = jnp.zeros((m, k_fits), dtype=p0.dtype)
    carry0 = (p0, f0, g0, S0, Y0, rho0, gamma0.astype(p0.dtype))
    (w, *_), _ = jax.lax.scan(body, carry0, None, length=num_iters)
    return w


@partial(
    jax.jit,
    static_argnames=("num_iters", "fit_intercept", "standardization"),
)
def fit_logistic_binary(
    x: jax.Array,          # [N, D]
    y: jax.Array,          # [N] in {0, 1}
    row_mask: jax.Array,   # [N] bool/float — masked rows contribute nothing
    reg_param: jax.Array,  # lambda
    elastic_net: jax.Array,  # alpha in [0, 1]
    num_iters: int = 100,
    fit_intercept: bool = True,
    standardization: bool = True,
) -> GLMParams:
    """Binary logistic regression via L-BFGS/OWL-QN (OpLogisticRegression
    parity — core/.../classification/OpLogisticRegression.scala wraps Spark
    LR, whose optimizer is breeze L-BFGS, or OWL-QN when elasticNet > 0).

    Delegates to the K=1 lane of ``fit_logistic_binary_batched`` so the
    sweep and the winner's refit run IDENTICAL math (same standardization
    moments, same constant-column gate, same optimizer trajectory)."""
    out = fit_logistic_binary_batched(
        x,
        y,
        row_mask[None, :],
        jnp.asarray(reg_param, dtype=x.dtype)[None],
        jnp.asarray(elastic_net, dtype=x.dtype)[None],
        num_iters=num_iters,
        fit_intercept=fit_intercept,
        standardization=standardization,
    )
    return GLMParams(weights=out.weights[0], intercept=out.intercept[0])


@partial(
    jax.jit,
    static_argnames=("num_iters", "fit_intercept", "standardization"),
)
def fit_logistic_binary_batched(
    x: jax.Array,           # [N, D] SHARED feature matrix
    y: jax.Array,           # [N]
    row_masks: jax.Array,   # [K, N] per-fit masks (folds × grid)
    reg_params: jax.Array,  # [K]
    elastic_nets: jax.Array,  # [K]
    num_iters: int = 100,
    fit_intercept: bool = True,
    standardization: bool = True,
) -> GLMParams:
    """K binary logistic L-BFGS/OWL-QN fits sharing ONE feature matrix.

    The round-1 sweep vmapped the sequential solver, which materializes K
    per-lane standardized COPIES of x ([K, N, D] — 3 GB for the Titanic
    sweep) and turns every iteration into a memory-bound pass over them.
    Here lanes batch as GEMM columns on the shared x (per iteration: one
    [T·K]-lane line-search GEMM + one gradient GEMM pair), with per-lane
    standardization applied IMPLICITLY:
        xsᵀr = (xᵀ(r·m) − mean·Σ(r·m)) / std
    Round 2 ran FISTA here, which provably did not converge on Titanic's
    κ≈2e4 one-hot matrix within maxIter·4 iterations (fold metrics drifted
    ±0.3 AuPR under float reassociation); the quasi-Newton direction
    reaches gradient-norm convergence in tens of iterations, matching
    MLlib's optimizer family. Returns weights [K, D], intercept [K].
    """
    k_fits, _ = row_masks.shape
    rm = row_masks.astype(x.dtype)
    n = jnp.maximum(rm.sum(axis=1), 1.0)                 # [K]
    # shifted-data moments: center on the GLOBAL column means first so the
    # one-pass per-lane variance s2c/n - mean_c² operates on small values —
    # the raw one-pass form catastrophically cancels in f32 for large-mean
    # columns (mean² ~4e6 has float spacing ~0.5). Without standardization
    # the model must NOT center (iterates match the sequential raw-x path),
    # so gshift/mean_c stay zero and s2 is the raw second moment.
    if standardization:
        gshift = x.mean(axis=0)                          # [D]
    else:
        gshift = jnp.zeros(x.shape[1], dtype=x.dtype)
    xc = x - gshift[None, :]
    s1 = rm @ xc                                         # [K, D]
    s2 = rm @ (xc * xc)                                  # [K, D]
    mean_raw = s1 / n[:, None]
    var = jnp.maximum(s2 / n[:, None] - mean_raw**2, 0.0)
    std = jnp.sqrt(var)
    # Fold-constant detection must be EXACT and reduction-order-invariant:
    # a variance threshold computed from one-pass moments sits in f32
    # cancellation noise, so a mesh-sharded run and a single-device run
    # can flip a borderline column in opposite directions — one path pins
    # the weight at 0, the other divides by the phantom std and amplifies
    # it to O(10) (observed on Titanic fold masks). Masked min/max are
    # exact under ANY association, so both paths agree bit-for-bit
    # (_masked_minmax scans lanes instead of broadcasting [K, N, D]).
    xmin, xmax = _masked_minmax(x, rm)                      # [K, D] each
    const = xmax <= xmin
    # near-constant (but not exactly constant) columns still carry one-pass
    # cancellation noise in std; clamp to the noise floor instead of gating
    # — a continuous guard cannot flip discretely between shardings
    noise_floor = 2e-3 * jnp.sqrt(s2 / n[:, None]) + 1e-12
    if standardization:
        safe = jnp.where(const, 1.0, jnp.maximum(std, noise_floor))
        if fit_intercept:
            mean_c = mean_raw
        else:
            # no intercept → scale only, never center (Spark parity; a
            # centered fit would differ from predict by mean·w). Gradients
            # must then see RAW x, so undo the moment shift.
            mean_c = jnp.zeros_like(mean_raw)
            xc = x
    else:
        mean_c = jnp.zeros_like(mean_raw)
        safe = jnp.ones_like(std)
        xc = x
    l1 = (reg_params * elastic_nets)[:, None]            # [K, 1]
    l2 = (reg_params * (1.0 - elastic_nets))[:, None]
    d_cols = x.shape[1]

    def _loss_terms(logits, w_std):
        # logits [..., K, N], w_std [..., K, D] -> total objective [..., K]
        ll = jax.nn.softplus(logits) - y * logits
        f = (ll * rm).sum(-1) / n
        f = f + 0.5 * l2[:, 0] * (w_std * w_std).sum(-1)
        return f + l1[:, 0] * jnp.abs(w_std).sum(-1)

    def _logits_of(ws, b):
        # ws [..., K, D] (already scaled by 1/safe) -> logits [..., K, N]
        lead = ws.shape[:-1]
        lin = (xc @ ws.reshape(-1, d_cols).T).T.reshape(*lead, -1)
        out = lin - (mean_c * ws).sum(-1)[..., None]
        if fit_intercept:
            out = out + b[..., None]
        return out

    def candidates_value(cand):                          # [T, K, P]
        w_std, b = cand[..., :-1], cand[..., -1]
        return _loss_terms(_logits_of(w_std / safe, b), w_std)

    def value_grad(params):                              # [K, P]
        w_std, b = params[:, :-1], params[:, -1]
        ws = w_std / safe
        logits = _logits_of(ws, b)
        f_total = _loss_terms(logits, w_std)
        p = jax.nn.sigmoid(logits)
        r = (p - y[None, :]) * rm                        # [K, N]
        xr = r @ xc                                      # [K, D]
        rsum = r.sum(axis=1)[:, None]
        gw = (xr - mean_c * rsum) / safe / n[:, None] + l2 * w_std
        if standardization:
            # constant columns are pure cancellation noise: pin their
            # weights at 0 (matches _standardize zeroing those columns)
            gw = jnp.where(const, 0.0, gw)
        gb = jnp.where(fit_intercept, rsum[:, 0] / n, 0.0)
        return f_total, jnp.concatenate([gw, gb[:, None]], axis=1)

    # tr(XsᵀXs)/n per lane: centered standardized columns have unit
    # variance (0 for constant columns) → count of non-constant columns.
    # Scaled-but-NOT-centered columns (fit_intercept=False) have second
    # moment (var + mean²)/std² ≥ 1; without standardization it is the raw
    # masked second moment per column.
    if standardization and fit_intercept:
        col_sum = (~const).sum(axis=1).astype(x.dtype)
    elif standardization:
        raw_second = var + (gshift[None, :] + mean_raw) ** 2
        col_sum = jnp.where(const, 0.0, raw_second / safe**2).sum(axis=1)
    else:
        col_sum = (s2 / n[:, None]).sum(axis=1)
    lip = 0.25 * col_sum + l2[:, 0]
    gamma0 = 1.0 / jnp.maximum(lip, 1e-6)                # [K]

    # l1 applies to weight components only, never the intercept slot
    l1_mat = jnp.concatenate(
        [jnp.broadcast_to(l1, (k_fits, d_cols)),
         jnp.zeros((k_fits, 1), dtype=x.dtype)], axis=1,
    )
    params0 = jnp.zeros((k_fits, d_cols + 1), dtype=x.dtype)
    params = _lbfgs_owlqn(
        value_grad, candidates_value, params0, l1_mat, gamma0, num_iters
    )
    w_std, b_std = params[:, :-1], params[:, -1]
    w = w_std / safe
    mean_total = gshift[None, :] + mean_c
    b = b_std - (w_std * mean_total / safe).sum(axis=1)
    return GLMParams(
        weights=w,
        intercept=jnp.where(fit_intercept, b, jnp.zeros_like(b)),
    )


@partial(
    jax.jit,
    static_argnames=(
        "num_classes", "num_iters", "fit_intercept", "standardization"
    ),
)
def fit_logistic_multinomial(
    x: jax.Array,
    y: jax.Array,          # [N] int class ids
    row_mask: jax.Array,
    reg_param: jax.Array,
    elastic_net: jax.Array,
    num_classes: int,
    num_iters: int = 200,
    fit_intercept: bool = True,
    standardization: bool = True,
) -> GLMParams:
    """Softmax regression (Spark multinomial logistic parity)."""
    row_mask = row_mask.astype(x.dtype)
    n = jnp.maximum(row_mask.sum(), 1.0)
    if standardization:
        xs, mean, std, const = _standardize(x, row_mask)
        if not fit_intercept:
            mean = jnp.zeros(x.shape[1], dtype=x.dtype)
            xs = _scale_only(x, row_mask, std, const)
    else:
        xs = jnp.where(row_mask[:, None] > 0, x, 0.0)
        mean = jnp.zeros(x.shape[1], dtype=x.dtype)
        std = jnp.ones(x.shape[1], dtype=x.dtype)
    y1h = jax.nn.one_hot(y.astype(jnp.int32), num_classes, dtype=x.dtype)
    l1 = reg_param * elastic_net
    l2 = reg_param * (1.0 - elastic_net)
    d = x.shape[1]

    def unpack(params):
        return params[: d * num_classes].reshape(d, num_classes), params[d * num_classes:]

    def grad(params):
        w, b = unpack(params)
        logits = xs @ w + jnp.where(fit_intercept, b, 0.0)
        p = jax.nn.softmax(logits, axis=-1)
        r = (p - y1h) * row_mask[:, None]
        gw = xs.T @ r / n + l2 * w
        gb = jnp.where(fit_intercept, r.sum(0) / n, jnp.zeros_like(b))
        return jnp.concatenate([gw.reshape(-1), gb])

    def prox(params, step):
        w, b = unpack(params)
        return jnp.concatenate([_soft_threshold(w, step * l1).reshape(-1), b])

    col = (xs * xs).sum(0) / n
    lip = 0.5 * col.sum() + l2
    step = 1.0 / jnp.maximum(lip, 1e-6)
    params0 = jnp.zeros(d * num_classes + num_classes, dtype=x.dtype)
    params = _fista(grad, prox, params0, step, num_iters)
    w_std, b_std = unpack(params)
    w = w_std / std[:, None]
    b = b_std - (w_std * (mean / std)[:, None]).sum(0)
    return GLMParams(weights=w, intercept=b if fit_intercept else jnp.zeros_like(b))


@partial(jax.jit, static_argnames=("num_iters", "fit_intercept", "standardization"))
def fit_linear_svc(
    x: jax.Array,
    y: jax.Array,          # [N] in {0, 1}
    row_mask: jax.Array,
    reg_param: jax.Array,
    num_iters: int = 400,
    fit_intercept: bool = True,
    standardization: bool = True,
) -> GLMParams:
    """Linear SVM via Huberized hinge + L2 (OpLinearSVC parity —
    core/.../classification/OpLinearSVC.scala wraps Spark LinearSVC, which is
    hinge/OWL-QN). The hinge is smoothed on a width-``delta`` band so FISTA
    has a true Lipschitz constant and converges at the accelerated rate; as
    delta -> 0 this recovers the exact hinge objective."""
    row_mask = row_mask.astype(x.dtype)
    n = jnp.maximum(row_mask.sum(), 1.0)
    if standardization:
        xs, mean, std, const = _standardize(x, row_mask)
        if not fit_intercept:
            mean = jnp.zeros(x.shape[1], dtype=x.dtype)
            xs = _scale_only(x, row_mask, std, const)
    else:
        xs = jnp.where(row_mask[:, None] > 0, x, 0.0)
        mean = jnp.zeros(x.shape[1], dtype=x.dtype)
        std = jnp.ones(x.shape[1], dtype=x.dtype)
    s = 2.0 * y - 1.0  # {-1, +1}
    delta = jnp.asarray(0.1, dtype=x.dtype)

    def grad(params):
        w, b = params[:-1], params[-1]
        margin = s * (xs @ w + jnp.where(fit_intercept, b, 0.0))
        # dL/dmargin for Huberized hinge: -1 below the band, linear inside
        slope = -jnp.clip((1.0 - margin) / delta, 0.0, 1.0)
        r = slope * s * row_mask
        gw = (xs * r[:, None]).sum(0) / n + reg_param * w
        gb = jnp.where(fit_intercept, r.sum() / n, 0.0)
        return jnp.concatenate([gw, gb[None]])

    def prox(params, _step):
        return params

    col = (xs * xs).sum(0) / n
    lip = (col.sum() + 1.0) / delta + reg_param
    step = 1.0 / jnp.maximum(lip, 1e-6)
    params0 = jnp.zeros(x.shape[1] + 1, dtype=x.dtype)
    params = _fista(grad, prox, params0, step, num_iters)
    w_std, b_std = params[:-1], params[-1]
    w = w_std / std
    b = b_std - (w_std * mean / std).sum()
    return GLMParams(weights=w, intercept=jnp.where(fit_intercept, b, 0.0))


# GLM family/link codes (static ints so the IRLS graph stays compiled once
# per (family, link) pair — Spark GeneralizedLinearRegression.scala parity)
GLM_FAMILIES = {"gaussian": 0, "binomial": 1, "poisson": 2, "gamma": 3}
GLM_LINKS = {"identity": 0, "log": 1, "logit": 2, "inverse": 3, "sqrt": 4}
GLM_DEFAULT_LINK = {
    "gaussian": "identity", "binomial": "logit", "poisson": "log",
    "gamma": "inverse",
}


@partial(jax.jit, static_argnames=("family", "link", "num_iters", "fit_intercept"))
def fit_glm_irls(
    x: jax.Array,
    y: jax.Array,
    row_mask: jax.Array,
    reg_param: jax.Array,  # L2 only, like Spark GLM
    family: int = 0,
    link: int = 0,
    num_iters: int = 25,
    fit_intercept: bool = True,
) -> GLMParams:
    """Iteratively reweighted least squares for generalized linear models
    (OpGeneralizedLinearRegression parity — Spark GLR's IRLS, maxIter=25).
    One `lax.scan` of normal-equation solves; D is small in tabular AutoML so
    the [D+1, D+1] solve per iteration is cheap on the MXU."""
    row_mask = row_mask.astype(x.dtype)
    n = jnp.maximum(row_mask.sum(), 1.0)
    d = x.shape[1]
    ones = jnp.ones((x.shape[0], 1), dtype=x.dtype)
    xa = jnp.concatenate([x, ones], axis=1) if fit_intercept else x
    da = xa.shape[1]
    eps = jnp.asarray(1e-7, dtype=x.dtype)

    def linkinv(eta):
        return jax.lax.switch(
            link,
            [
                lambda e: e,                       # identity
                lambda e: jnp.exp(e),              # log
                lambda e: jax.nn.sigmoid(e),       # logit
                lambda e: 1.0 / jnp.where(jnp.abs(e) > eps, e, eps),  # inverse
                lambda e: e * e,                   # sqrt
            ],
            eta,
        )

    def dmu_deta(eta, mu):
        return jax.lax.switch(
            link,
            [
                lambda: jnp.ones_like(eta),
                lambda: mu,
                lambda: mu * (1.0 - mu),
                lambda: -mu * mu,
                lambda: 2.0 * jnp.sqrt(jnp.maximum(mu, eps)),
            ],
        )

    def variance(mu):
        return jax.lax.switch(
            family,
            [
                lambda m: jnp.ones_like(m),        # gaussian
                lambda m: m * (1.0 - m),           # binomial
                lambda m: m,                       # poisson
                lambda m: m * m,                   # gamma
            ],
            mu,
        )

    def init_eta():
        # family-aware starting point on the linear scale
        mu0 = jax.lax.switch(
            family,
            [
                lambda: y,
                lambda: (y + 0.5) / 2.0,
                lambda: jnp.maximum(y, 0.0) + 0.1,
                lambda: jnp.maximum(y, eps),
            ],
        )
        return jax.lax.switch(
            link,
            [
                lambda m: m,
                lambda m: jnp.log(jnp.maximum(m, eps)),
                lambda m: jnp.log(jnp.maximum(m, eps) / jnp.maximum(1.0 - m, eps)),
                lambda m: 1.0 / jnp.maximum(m, eps),
                lambda m: jnp.sqrt(jnp.maximum(m, 0.0)),
            ],
            mu0,
        )

    def body(beta, _):
        eta = xa @ beta
        mu = linkinv(eta)
        dmu = dmu_deta(eta, mu)
        dmu = jnp.where(jnp.abs(dmu) > eps, dmu, eps)
        var = jnp.maximum(variance(mu), eps)
        z = eta + (y - mu) / dmu
        w = row_mask * dmu * dmu / var
        xtwx = (xa * w[:, None]).T @ xa / n
        xtwz = (xa * w[:, None]).T @ z / n
        reg = reg_param * jnp.eye(da, dtype=x.dtype)
        if fit_intercept:  # intercept unregularized
            reg = reg.at[da - 1, da - 1].set(0.0)
        beta_next = jnp.linalg.solve(xtwx + reg + eps * jnp.eye(da, dtype=x.dtype), xtwz)
        return beta_next, None

    eta0 = init_eta()
    w0 = row_mask
    xtwx0 = (xa * w0[:, None]).T @ xa / n
    xtwz0 = (xa * w0[:, None]).T @ eta0 / n
    beta0 = jnp.linalg.solve(
        xtwx0 + (reg_param + eps) * jnp.eye(da, dtype=x.dtype), xtwz0
    )
    beta, _ = jax.lax.scan(body, beta0, None, length=num_iters)
    if fit_intercept:
        return GLMParams(weights=beta[:-1], intercept=beta[-1])
    return GLMParams(weights=beta, intercept=jnp.zeros((), dtype=x.dtype))


@partial(jax.jit, static_argnames=("num_iters", "fit_intercept"))
def fit_linear(
    x: jax.Array,
    y: jax.Array,
    row_mask: jax.Array,
    reg_param: jax.Array,
    elastic_net: jax.Array,
    num_iters: int = 200,
    fit_intercept: bool = True,
) -> GLMParams:
    """Linear regression with elastic net (OpLinearRegression parity; Spark
    WLS/normal-equation semantics for alpha=0 via converged FISTA)."""
    row_mask = row_mask.astype(x.dtype)
    n = jnp.maximum(row_mask.sum(), 1.0)
    xs, mean, std, const = _standardize(x, row_mask)
    if not fit_intercept:
        # Spark parity: scale only, never center x OR y — a centered fit
        # bakes an implicit intercept into training that predict never
        # applies (same fix as the logistic/SVC no-intercept paths)
        mean = jnp.zeros(x.shape[1], dtype=x.dtype)
        xs = _scale_only(x, row_mask, std, const)
        ym = jnp.zeros((), dtype=x.dtype)
    else:
        ym = (y * row_mask).sum() / n
    yc = jnp.where(row_mask > 0, y - ym, 0.0)
    l1 = reg_param * elastic_net
    l2 = reg_param * (1.0 - elastic_net)

    def grad(w):
        r = (xs @ w - yc) * row_mask
        return xs.T @ r / n + l2 * w

    def prox(w, step):
        return _soft_threshold(w, step * l1)

    col = (xs * xs).sum(0) / n
    lip = col.sum() + l2
    step = 1.0 / jnp.maximum(lip, 1e-6)
    w0 = jnp.zeros(x.shape[1], dtype=x.dtype)
    w_std = _fista(grad, prox, w0, step, num_iters)
    w = w_std / std
    b = ym - (w_std * mean / std).sum()
    return GLMParams(weights=w, intercept=jnp.where(fit_intercept, b, 0.0))


# --------------------------------------------------------------------------
# compiled-program contract audit (analysis/program.py, TPJ0xx)
# --------------------------------------------------------------------------
def program_trace_specs():
    """Representative trace shapes for the banked GLM sweep programs.

    The bucketed axis is the LANE count K (``compiler.bucketing``): the
    default buckets cross the pow2(<=64) / 32-multiple boundary so the
    TPJ005 fingerprint check proves every bucket compiles the same
    program family. Small N/D and tiny iteration counts keep the whole
    trace in milliseconds — jaxpr structure does not depend on them."""
    import jax

    def _glm_args(k: int):
        f32 = "float32"
        return (
            jax.ShapeDtypeStruct((16, 3), f32),   # x
            jax.ShapeDtypeStruct((16,), f32),     # y
            jax.ShapeDtypeStruct((k, 16), f32),   # row_masks
            jax.ShapeDtypeStruct((k,), f32),      # reg_params
            jax.ShapeDtypeStruct((k,), f32),      # elastic_nets
        )

    # donation contract of the lane sweep (mirrored by the sharded twins
    # in parallel/sweep.py): the per-lane hyperparam vectors [K] alias
    # into the output intercept [K] — TPJ003 lowers this donating twin
    # and requires the aliasing to land in the StableHLO
    return [
        dict(
            name="linear_batched",
            fn=fit_linear_batched,
            build=lambda k: (
                _glm_args(k), dict(num_iters=2, fit_intercept=True)
            ),
            buckets=(8, 64, 96),
            bucket_axis="lanes",
            donate_argnums=(3, 4),
            base_fn=getattr(fit_linear_batched, "__wrapped__", None),
            static_argnames=("num_iters", "fit_intercept"),
        ),
        dict(
            name="logistic_binary_batched",
            fn=fit_logistic_binary_batched,
            build=lambda k: (
                _glm_args(k),
                dict(num_iters=2, fit_intercept=True, standardization=True),
            ),
            buckets=(8, 64, 96),
            bucket_axis="lanes",
            donate_argnums=(3, 4),
            base_fn=getattr(
                fit_logistic_binary_batched, "__wrapped__", None
            ),
            static_argnames=(
                "num_iters", "fit_intercept", "standardization"
            ),
        ),
    ]
