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

Every product of a GLM fit runs at ONE stated matmul precision,
``GLM_PRECISION`` (through ``_mm``): on a TPU the default precision of a
float32 product is one bfloat16 pass, so a configuration that states
float32 holds the solvers to this constant. It is not an option.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

#: the matmul precision of every GLM product (six bfloat16 passes on a TPU:
#: float32 products; the default is one pass)
GLM_PRECISION = jax.lax.Precision.HIGHEST


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at ``GLM_PRECISION``: the one matmul of this module."""
    return jnp.matmul(a, b, precision=GLM_PRECISION)


def _bmm(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` for ``[S, m, k] × [S, k, n]`` over a leading lane axis, at
    ``GLM_PRECISION``: ``lax.dot_general`` keeps a lane axis of 1 (the
    batched matmul of ``jnp.matmul`` squeezes it), so a program's structure
    does not change with its lane count."""
    return jax.lax.dot_general(
        a, b, (((2,), (1,)), ((0,), (0,))), precision=GLM_PRECISION)


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


class _LaneMoments(NamedTuple):
    """Per-lane column moments of a shared plane under K row masks (the
    implicit standardization of the batched solvers)."""

    n: jax.Array         # [K] rows a lane holds (at least 1)
    gshift: jax.Array    # [D] global column means (0 without standardization)
    xc: jax.Array        # [N, D] the plane the products read
    s2: jax.Array        # [K, D] second moments of x - gshift
    mean_raw: jax.Array  # [K, D] lane means of x - gshift
    var: jax.Array       # [K, D]
    const: jax.Array     # [K, D] columns constant on a lane's rows
    safe: jax.Array      # [K, D] the std a lane divides by
    mean_c: jax.Array    # [K, D] the centring the products apply


def _lane_moments(x, rm, standardization: bool, fit_intercept: bool):
    """One pass of moments for K lanes on the shared ``x`` [N, D] under
    masks ``rm`` [K, N]: the standardization every batched solver applies
    implicitly, ``xsᵀr = (xcᵀ(r·m) − mean_c·Σ(r·m)) / safe``."""
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
    s1 = _mm(rm, xc)                                     # [K, D]
    s2 = _mm(rm, xc * xc)                                # [K, D]
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
    return _LaneMoments(n, gshift, xc, s2, mean_raw, var, const, safe, mean_c)


def _col_second_moments(m: _LaneMoments, standardization: bool,
                        fit_intercept: bool) -> jax.Array:
    """tr(XsᵀXs)/n per lane [K]: centered standardized columns have unit
    variance (0 for constant columns) → count of non-constant columns.
    Scaled-but-NOT-centered columns (fit_intercept=False) have second
    moment (var + mean²)/std² ≥ 1; without standardization it is the raw
    masked second moment per column."""
    if standardization and fit_intercept:
        return (~m.const).sum(axis=1).astype(m.xc.dtype)
    if standardization:
        raw_second = m.var + (m.gshift[None, :] + m.mean_raw) ** 2
        return jnp.where(m.const, 0.0, raw_second / m.safe**2).sum(axis=1)
    return (m.s2 / m.n[:, None]).sum(axis=1)


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
    s1 = _mm(rm, xc)                                        # [K, D]
    s2 = _mm(rm, xc * xc)
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
        ym = _mm(rm, y) / n                                 # [K]
    yc = jnp.where(rm > 0, y[None, :] - ym[:, None], 0.0)   # [K, N]
    l1 = (reg_params * elastic_nets)[:, None]
    l2 = (reg_params * (1.0 - elastic_nets))[:, None]

    def grad(w_std):
        # w_std [K, D] in standardized space; const columns pinned at 0
        v = jnp.where(const, 0.0, w_std / safe)             # [K, D]
        logits = _mm(xc, v.T) - (mean_shift * v).sum(axis=1)[None, :]  # [N, K]
        r = (logits.T - yc) * rm                            # [K, N]
        g_raw = _mm(r, xc) - mean_shift * r.sum(axis=1)[:, None]
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
_GTOL = 1e-7


class OwlqnResult(NamedTuple):
    params: jax.Array      # [K, P]
    iters_moved: jax.Array  # [K] int32: iterations in which the lane moved
    converged: jax.Array   # [K] bool: the gradient test held at the end


def _lbfgs_owlqn(
    value_grad,        # W [K, P] -> (F [K], g_smooth [K, P]); F includes l1
    candidates_value,  # Wc [T, K, P] -> F [T, K]
    p0,                # [K, P] initial params
    l1_mat,            # [K, P] per-component l1 strength (0 on intercepts)
    gamma0,            # [K] initial inverse-Hessian scale (≈ 1/Lipschitz)
    num_iters: int,
    gtol: float = _GTOL,
) -> OwlqnResult:
    """argmin params [K, P], with each lane's count of iterations that
    moved it and whether its gradient test held. All control flow is
    branchless so the whole optimizer is one scanned XLA program, vmap-
    and GSPMD-friendly."""
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
        w, f_cur, g, S, Y, rho, gamma, moved, _conv = carry
        with jax.named_scope("glm/direction"):
            pg = pseudo_grad(w, g)
            d = two_loop(pg, S, Y, rho, gamma)
            # OWL-QN: constrain d to a descent direction of the
            # pseudo-gradient on l1-active components (l1=0 lanes pass
            # through untouched)
            d = jnp.where((l1_mat > 0) & (d * pg >= 0), 0.0, d)
            xi = jnp.where(w != 0, jnp.sign(w), jnp.sign(-pg))
            cand = w[None] + ts[:, None, None] * d[None]      # [T, K, P]
            cand = jnp.where((l1_mat > 0) & (cand * xi < 0), 0.0, cand)
        with jax.named_scope("glm/line_search"):
            f_cand = candidates_value(cand)                   # [T, K]
            pgd = ((cand - w[None]) * pg[None]).sum(-1)       # [T, K]
            accept = f_cand <= f_cur[None] + _LS_C1 * pgd
            first_ok = jnp.argmax(accept, axis=0)             # largest t ok
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
        with jax.named_scope("glm/direction"):
            s = w_next - w
            yv = g_next - g
            sy = (s * yv).sum(-1)
            # relative curvature gate (breeze-style): tiny-positive f32 sy
            # garbage would otherwise produce huge rho and garbage
            # directions
            s_nrm = jnp.sqrt((s * s).sum(-1))
            y_nrm = jnp.sqrt((yv * yv).sum(-1))
            valid = move & (sy > 1e-8 * s_nrm * y_nrm + 1e-20)
            # line-search failure away from convergence means the
            # quasi-Newton direction went bad (stale/ill-conditioned
            # history): RESET to steepest descent with the 1/Lipschitz
            # scale. Without this the carry never changes and the lane
            # deadlocks at a non-converged point (every later iteration
            # rebuilds the same rejected step).
            fail = ~move & ~conv
            s = jnp.where(valid[:, None], s, 0.0)
            yv = jnp.where(valid[:, None], yv, 0.0)
            rho_new = jnp.where(valid, 1.0 / jnp.where(valid, sy, 1.0), 0.0)
            vslot = valid[None, :, None]
            S_next = jnp.where(vslot, jnp.concatenate([S[1:], s[None]]), S)
            Y_next = jnp.where(vslot, jnp.concatenate([Y[1:], yv[None]]), Y)
            rho_next = jnp.where(
                valid[None, :], jnp.concatenate([rho[1:], rho_new[None]]),
                rho,
            )
            S_next = jnp.where(fail[None, :, None], 0.0, S_next)
            Y_next = jnp.where(fail[None, :, None], 0.0, Y_next)
            rho_next = jnp.where(fail[None, :], 0.0, rho_next)
            gamma_next = jnp.where(
                valid, sy / jnp.maximum((yv * yv).sum(-1), 1e-20), gamma
            )
            gamma_next = jnp.where(fail, gamma00, gamma_next)
        return (w_next, f_next, g_next, S_next, Y_next, rho_next, gamma_next,
                moved + move.astype(jnp.int32), conv), None

    f0, g0 = value_grad(p0)
    gamma00 = gamma0.astype(p0.dtype)
    S0 = jnp.zeros((m, k_fits, p_dim), dtype=p0.dtype)
    Y0 = jnp.zeros((m, k_fits, p_dim), dtype=p0.dtype)
    rho0 = jnp.zeros((m, k_fits), dtype=p0.dtype)
    carry0 = (p0, f0, g0, S0, Y0, rho0, gamma0.astype(p0.dtype),
              jnp.zeros(k_fits, jnp.int32), jnp.zeros(k_fits, bool))
    (w, *_, moved, conv), _ = jax.lax.scan(body, carry0, None,
                                          length=num_iters)
    return OwlqnResult(w, moved, conv)


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
    standardization applied IMPLICITLY (``_lane_moments``):
        xsᵀr = (xᵀ(r·m) − mean·Σ(r·m)) / std
    Round 2 ran FISTA here, which provably did not converge on Titanic's
    κ≈2e4 one-hot matrix within maxIter·4 iterations (fold metrics drifted
    ±0.3 AuPR under float reassociation); the quasi-Newton direction
    reaches gradient-norm convergence in tens of iterations, matching
    MLlib's optimizer family. Returns weights [K, D], intercept [K].
    """
    k_fits, _ = row_masks.shape
    rm = row_masks.astype(x.dtype)
    with jax.named_scope("glm/moments"):
        mo = _lane_moments(x, rm, standardization, fit_intercept)
    n, xc, safe, mean_c = mo.n, mo.xc, mo.safe, mo.mean_c
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
        lin = _mm(xc, ws.reshape(-1, d_cols).T).T.reshape(*lead, -1)
        out = lin - (mean_c * ws).sum(-1)[..., None]
        if fit_intercept:
            out = out + b[..., None]
        return out

    def candidates_value(cand):                          # [T, K, P]
        w_std, b = cand[..., :-1], cand[..., -1]
        return _loss_terms(_logits_of(w_std / safe, b), w_std)

    def value_grad(params):                              # [K, P]
        with jax.named_scope("glm/value_grad"):
            w_std, b = params[:, :-1], params[:, -1]
            ws = w_std / safe
            logits = _logits_of(ws, b)
            f_total = _loss_terms(logits, w_std)
            p = jax.nn.sigmoid(logits)
            r = (p - y[None, :]) * rm                    # [K, N]
            xr = _mm(r, xc)                              # [K, D]
            rsum = r.sum(axis=1)[:, None]
            gw = (xr - mean_c * rsum) / safe / n[:, None] + l2 * w_std
            if standardization:
                # constant columns are pure cancellation noise: pin their
                # weights at 0 (matches _standardize zeroing those columns)
                gw = jnp.where(mo.const, 0.0, gw)
            gb = jnp.where(fit_intercept, rsum[:, 0] / n, 0.0)
            return f_total, jnp.concatenate([gw, gb[:, None]], axis=1)

    col_sum = _col_second_moments(mo, standardization, fit_intercept)
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
    ).params
    w_std, b_std = params[:, :-1], params[:, -1]
    w = w_std / safe
    mean_total = mo.gshift[None, :] + mean_c
    b = b_std - (w_std * mean_total / safe).sum(axis=1)
    return GLMParams(
        weights=w,
        intercept=jnp.where(fit_intercept, b, jnp.zeros_like(b)),
    )


class MultinomialFit(NamedTuple):
    """What one batched multinomial fit program returns, lane by lane."""

    weights: jax.Array      # [K, D, C] original-scale coefficients
    intercept: jax.Array    # [K, C], centred to mean 0
    logits: jax.Array       # [K, C, N] every row's class logits at the fit
    iters_moved: jax.Array  # [K] int32 iterations in which the lane moved
    converged: jax.Array    # [K] bool: the gradient test held at the end


def _multinomial_penalty_weights(reg_params, elastic_nets, safe, classes):
    """Spark's elastic net in the ``[K, D·C + C]`` parameter layout: per
    parameter (l1 [K, P], l2 [K, P]), λα and λ(1 − α) on every coefficient
    of the STANDARDIZED plane (Spark's with standardization), 0 on the
    intercepts, which are never penalised."""
    k, d = safe.shape
    coef = jnp.ones((k, d * classes), dtype=safe.dtype)
    free = jnp.zeros((k, classes), dtype=safe.dtype)
    l1 = (reg_params * elastic_nets)[:, None] * coef
    l2 = (reg_params * (1.0 - elastic_nets))[:, None] * coef
    return (jnp.concatenate([l1, free], axis=1),
            jnp.concatenate([l2, free], axis=1))


@partial(
    jax.jit,
    static_argnames=(
        "num_classes", "num_iters", "fit_intercept", "standardization"
    ),
)
def fit_logistic_multinomial_batched(
    x: jax.Array,            # [N, D] SHARED feature matrix
    y: jax.Array,            # [N] class ids 0 … C - 1
    row_masks: jax.Array,    # [K, N] per-fit masks (folds × grid)
    reg_params: jax.Array,   # [K]
    elastic_nets: jax.Array,  # [K]
    num_classes: int,
    num_iters: int = 100,
    fit_intercept: bool = True,
    standardization: bool = True,
) -> MultinomialFit:
    """K softmax regressions over C classes sharing ONE feature matrix:
    Spark 2.4's multinomial family (no pivot class), by the binary fit's
    L-BFGS/OWL-QN, ``num_iters`` iterations (Spark's ``maxIter``, 1:1).

    Lanes batch as GEMM columns on the shared x, standardized implicitly
    by the binary fit's moments (``_lane_moments``): parameters are
    ``[K, D·C + C]`` (the standardized coefficients, then the intercepts),
    so a line search evaluates every step candidate of every lane and
    class in ONE ``[N, D] × [D, T·K·C]`` product.

    Per lane with row mask m over n rows and one-hot labels Y:
    ``F = (1/n) Σ m_i (logsumexp(z_i) − z_i·Y_i) + ½λ(1−α)‖β‖² + λα‖β‖₁``
    with ``z = xs β + b`` on the lane's standardized plane xs; columns
    constant on the lane's rows are pinned at 0. Start: β = 0 and b the
    centred log(1 + class count) of the lane's rows (Spark's start). At
    the end the coefficients map back to the original scale, the
    intercepts are centred to mean 0, and at λ = 0 the coefficients too
    (Spark's identifiability rule); ``logits`` are every row's class
    logits at the fitted point, up to a constant a row (what a softmax
    and an arg max read)."""
    k_fits = row_masks.shape[0]
    c, d = num_classes, x.shape[1]
    rm = row_masks.astype(x.dtype)
    with jax.named_scope("glm/moments"):
        mo = _lane_moments(x, rm, standardization, fit_intercept)
        y1h = jax.nn.one_hot(y.astype(jnp.int32), c, dtype=x.dtype)  # [N, C]
        counts = _mm(rm, y1h)                                 # [K, C]
    n, xc, safe, mean_c = mo.n, mo.xc, mo.safe, mo.mean_c
    l1_mat, l2_mat = _multinomial_penalty_weights(
        reg_params, elastic_nets, safe, c)
    pinned = jnp.concatenate(
        [jnp.repeat(mo.const if standardization else jnp.zeros_like(
            mo.const), c, axis=1), jnp.zeros((k_fits, c), bool)], axis=1)
    rm_t = rm.T                                               # [N, K]

    def logits_of(params):
        # params [..., K, P] -> class logits [N, ..., K, C]
        lead = params.shape[:-1]
        w_std = params[..., : d * c].reshape(*lead, d, c)
        ws = w_std / safe[..., None]                          # [..., K, D, C]
        flat = jnp.moveaxis(ws, -2, 0).reshape(d, -1)         # [D, ...·K·C]
        z = _mm(xc, flat).reshape(-1, *lead, c)
        z = z - (mean_c[..., None] * ws).sum(-2)[None]
        if fit_intercept:
            z = z + params[..., d * c:][None]
        return z

    def objective(params, z):
        # z [N, ..., K, C] -> F [..., K]
        lse = jax.nn.logsumexp(z, axis=-1)
        zy = (z * y1h.reshape(-1, *([1] * (z.ndim - 2)), c)).sum(-1)
        loss = ((lse - zy) * rm_t.reshape(
            -1, *([1] * (z.ndim - 3)), k_fits)).sum(0) / n
        return (loss + 0.5 * (l2_mat * params * params).sum(-1)
                + (l1_mat * jnp.abs(params)).sum(-1))

    def candidates_value(cand):                               # [T, K, P]
        return objective(cand, logits_of(cand))

    def value_grad(params):                                   # [K, P]
        with jax.named_scope("glm/value_grad"):
            z = logits_of(params)                             # [N, K, C]
            f_total = objective(params, z)
            r = (jax.nn.softmax(z, axis=-1) - y1h[:, None, :]) * rm_t[
                :, :, None]                                   # [N, K, C]
            xr = _mm(xc.T, r.reshape(-1, k_fits * c)).reshape(d, k_fits, c)
            rsum = r.sum(axis=0)                              # [K, C]
            gw = (jnp.moveaxis(xr, 0, 1) - mean_c[..., None] * rsum[:, None])
            gw = gw / safe[..., None] / n[:, None, None]
            gb = rsum / n[:, None] if fit_intercept else jnp.zeros_like(rsum)
            g = jnp.concatenate([gw.reshape(k_fits, d * c), gb], axis=1)
            g = g + l2_mat * params
            return f_total, jnp.where(pinned, 0.0, g)

    col_sum = _col_second_moments(mo, standardization, fit_intercept)
    gamma0 = 1.0 / jnp.maximum(
        0.5 * col_sum + reg_params * (1.0 - elastic_nets), 1e-6)
    if fit_intercept:
        prior = jnp.log1p(counts)
        b0 = prior - prior.mean(axis=1, keepdims=True)
    else:
        b0 = jnp.zeros((k_fits, c), dtype=x.dtype)
    params0 = jnp.concatenate(
        [jnp.zeros((k_fits, d * c), dtype=x.dtype), b0], axis=1)
    fit = _lbfgs_owlqn(
        value_grad, candidates_value, params0, l1_mat, gamma0, num_iters)
    params = fit.params
    with jax.named_scope("glm/scores"):
        z = logits_of(params)                                 # [N, K, C]
        logits = jnp.moveaxis(z, 0, -1)                       # [K, C, N]
    w_std = params[:, : d * c].reshape(k_fits, d, c)
    w = w_std / safe[..., None]
    mean_total = (mo.gshift[None, :] + mean_c)[..., None]
    if fit_intercept:
        b = params[:, d * c:] - (w_std * mean_total / safe[..., None]).sum(1)
        b = b - b.mean(axis=1, keepdims=True)
    else:
        b = jnp.zeros((k_fits, c), dtype=x.dtype)
    w = jnp.where((reg_params == 0.0)[:, None, None],
                  w - w.mean(axis=(1, 2), keepdims=True), w)
    return MultinomialFit(w, b, logits, fit.iters_moved, fit.converged)


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
    num_iters: int = 100,
    fit_intercept: bool = True,
    standardization: bool = True,
) -> GLMParams:
    """Softmax regression (Spark's multinomial family): the K=1 lane of
    ``fit_logistic_multinomial_batched``, so a single fit and a sweep's
    lane run IDENTICAL math."""
    out = fit_logistic_multinomial_batched(
        x, y, row_mask[None, :],
        jnp.asarray(reg_param, dtype=x.dtype)[None],
        jnp.asarray(elastic_net, dtype=x.dtype)[None],
        num_classes=num_classes, num_iters=num_iters,
        fit_intercept=fit_intercept, standardization=standardization,
    )
    return GLMParams(weights=out.weights[0], intercept=out.intercept[0])


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
        margin = s * (_mm(xs, w) + jnp.where(fit_intercept, b, 0.0))
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


# --------------------------------------------------------------------------
# Generalized linear regression: Spark's GeneralizedLinearRegression as one
# K-lane program on the shared plane. A lane is one (family, link, λ, tol)
# on one row mask; the family and the link are lane DATA, so one program
# fits a grid that mixes them. Per lane (Spark 2.4's learner):
#
#   * gaussian/identity: ONE WeightedLeastSquares solve of the label at
#     unit weights with standardized features and label (Spark's closed
#     form: it does not iterate);
#   * every other pair: the start of FamilyAndLink.initialize (μ⁰ = the
#     family's initialize(y), η⁰ = g(μ⁰), one such solve of η⁰), then
#     IterativelyReweightedLeastSquares: η = xβ + b, μ = project(g⁻¹(η)),
#     z = η + (y − μ)·g′(μ), w = 1 / (g′(μ)²·V(μ)), a solve of z at weights
#     w with neither standardized, until every coefficient and the
#     intercept move by less than ``tol`` (or ``max_iter`` solves).
#
# WeightedLeastSquares here: the columns centred by their weighted means
# and scaled by their weighted stds, the penalty on the diagonal of that
# system as Spark puts it there (λ / σ_label; ÷ σ_j² where the features are
# not standardized, × σ_label where the label is not), the intercept never
# penalised, a batched Cholesky. The weighted Grams stream the plane once
# an iteration in row blocks, only for the lanes that have not stopped.
# --------------------------------------------------------------------------

# GLM family/link codes: lane data of the IRLS program
GLM_FAMILIES = {"gaussian": 0, "binomial": 1, "poisson": 2, "gamma": 3}
GLM_LINKS = {"identity": 0, "log": 1, "logit": 2, "inverse": 3, "sqrt": 4}
GLM_DEFAULT_LINK = {
    "gaussian": "identity", "binomial": "logit", "poisson": "log",
    "gamma": "inverse",
}

#: Spark's ``project`` floor for float32 (its float64 takes 1e-16): 2^-24,
#: so that a binomial lane's 1 − ε stays below 1
GLR_EPS = 2.0 ** -24
#: Tweedie's δ: a poisson lane starts at μ⁰ = δ where its label is 0
GLR_DELTA = 0.1
#: the float32 floor of the stop rule: a component has also stopped once
#: it moves by at most this many ulps (2^-23 of its own size) — Spark's
#: absolute ``tol`` cannot be met in float32 by a value over ≈ 8
GLR_ULP_FLOOR = 16.0
#: rows a block of the program's passes over the plane
GLR_ROW_BLOCK = 4096
#: a column's std is at least this share of its root mean square about
#: the plane's column means (the noise floor of ``_lane_moments``)
_GLR_NOISE_FLOOR = 2e-3
#: the ridge of a lane whose standardized system is singular (λ = 0 on
#: collinear columns: Spark's Cholesky fails there and it falls back to a
#: quasi-Newton solver)
_GLR_SINGULAR_RIDGE = 1e-6


class GLRFit(NamedTuple):
    """What one IRLS program returns, lane by lane."""

    weights: jax.Array           # [K, D] original-scale coefficients
    intercept: jax.Array         # [K]
    mu: jax.Array                # [K, N] every row's fitted mean
    iters: jax.Array             # [K] int32 IRLS solves after the start
    stop_tol: jax.Array          # [K] bool: Spark's rule stopped the lane
    stop_floor: jax.Array        # [K] bool: the float32 floor stopped it
    rows_projected: jax.Array    # [K] int32 lane rows whose μ was projected
    rows_nonpositive: jax.Array  # [K] int32 lane rows fitted at μ ≤ 0
    iterations: jax.Array        # [] int32 iterations the program ran


def _glr_unlink(eta, link):
    """g⁻¹(η) by the lane's link code."""
    return jnp.select(
        [link == 0, link == 1, link == 2, link == 3],
        [eta, jnp.exp(eta), jax.nn.sigmoid(eta), 1.0 / eta], eta * eta)


def _glr_link(mu, link):
    """g(μ) by the lane's link code."""
    return jnp.select(
        [link == 0, link == 1, link == 2, link == 3],
        [mu, jnp.log(mu), jnp.log(mu) - jnp.log1p(-mu), 1.0 / mu],
        jnp.sqrt(mu))


def _glr_deriv(mu, link):
    """g′(μ) by the lane's link code."""
    return jnp.select(
        [link == 0, link == 1, link == 2, link == 3],
        [jnp.ones_like(mu), 1.0 / mu, 1.0 / (mu * (1.0 - mu)),
         -1.0 / (mu * mu)],
        0.5 / jnp.sqrt(mu))


def _glr_variance(mu, family):
    """V(μ) by the lane's family code."""
    return jnp.select(
        [family == 0, family == 1, family == 2],
        [jnp.ones_like(mu), mu * (1.0 - mu), mu], mu * mu)


def _glr_project(mu, family):
    """Spark's ``project``: infinities to the largest float; a binomial μ
    into [ε, 1 − ε], a poisson or gamma μ to at least ε."""
    big = jnp.finfo(mu.dtype).max
    return jnp.select(
        [family == 0, family == 1],
        [jnp.clip(mu, -big, big), jnp.clip(mu, GLR_EPS, 1.0 - GLR_EPS)],
        jnp.clip(mu, GLR_EPS, big))


def _glr_initialize(y, family):
    """μ⁰ by the lane's family (Spark's ``Family.initialize``)."""
    return jnp.select(
        [family == 1, family == 2],
        [(y + 0.5) / 2.0, jnp.where(y == 0.0, GLR_DELTA, y)], y)


def _glr_slot_sizes(k: int) -> tuple[int, ...]:
    """The lane counts an iteration's Gram pass is compiled for: eighths
    of ``k`` (rounded up), so a pass over the lanes that have not stopped
    costs at most an eighth of ``k`` more than they need."""
    return tuple(sorted({max(1, -(-k * j // 8)) for j in range(1, 9)}))


def _cholesky_bordered(a, c):
    """(low, y): the lower Cholesky factors ``low`` of the SPD matrices
    ``a`` [S, D, D] and y = low⁻¹·c for ``c`` [S, D], from ONE loop over
    the D columns of the system bordered by its right-hand side (the
    border's row of the factor is y, so no forward substitution runs), a
    column a step, every product through ``_bmm``. NaN where a matrix is
    not positive definite. (``jnp.linalg.cholesky`` and its triangular
    solves lower to LAPACK custom calls on XLA:CPU, and a banked
    executable of this program that holds them faults when a later
    process runs it, at widths of 256 and more; these loops lower alike on
    every backend and for every lane count.)"""
    s, d = c.shape
    border = jnp.concatenate([c, jnp.zeros((s, 1), a.dtype)], axis=1)
    m = jnp.concatenate(
        [jnp.concatenate([a, c[:, :, None]], axis=2), border[:, None, :]],
        axis=1)                                                 # [S, D+1, D+1]
    rows = jnp.arange(d + 1)

    def column(j, low):
        # low holds columns < j; row j of it is zero from column j on
        col = m[:, :, j] - _bmm(low, low[:, j, :, None])[..., 0]  # [S, D+1]
        pivot = jnp.sqrt(col[:, j])
        below = jnp.broadcast_to(rows > j, col.shape)
        col = jnp.where(below, col / pivot[:, None], 0.0)
        return low.at[:, :, j].set(col.at[:, j].set(pivot))

    low = jax.lax.fori_loop(0, d, column, jnp.zeros_like(m))
    return low[:, :d, :d], low[:, d, :d]


def _back_solve(low, y):
    """x with lowᵀ·x = y for factors ``low`` [S, D, D], ``y`` [S, D]: back
    substitution, a component a step."""
    d = y.shape[-1]

    def backward(i, x):
        j = d - 1 - i
        xj = (y[:, j] - _bmm(low[:, None, :, j], x[..., None])[:, 0, 0]) / (
            low[:, j, j])
        return x.at[:, j].set(xj)

    return jax.lax.fori_loop(0, d, backward, jnp.zeros_like(y))


def _glr_wls(sw, s1, gram, sz, szz, r, zshift, const, lam, gshift,
             standardize: bool, fit_intercept: bool):
    """Spark's WeightedLeastSquares for lanes ``[S]`` from their weighted
    sums over the shifted plane xc = x − gshift and the shifted label
    z′ = z − ``zshift`` [S]: Σv ``sw`` [S], Σv·xc ``s1`` [S, D], Σv·xc·xcᵀ
    ``gram`` [S, D, D], Σv·z′ ``sz``, Σv·z′² ``szz``, Σv·z′·xc ``r``
    [S, D] (a label far from 0 beside its spread would cancel in float32
    sums, as a column far from 0 does); ``const`` [S, D] the columns
    constant on a lane's rows. ``standardize``: Spark's standardizeFeatures
    and standardizeLabel (the start and the closed form: true; an IRLS
    step: false). Returns the original-scale (β [S, D], b [S])."""
    n = jnp.maximum(sw, jnp.finfo(sw.dtype).tiny)
    m = s1 / n[:, None]                                  # weighted means
    zdev = sz / n
    zbar = zdev + zshift
    second = gram / n[:, None, None]
    cov = second - m[:, :, None] * m[:, None, :]
    cz = r / n[:, None] - m * zdev[:, None]
    sigma = jnp.sqrt(jnp.maximum(jnp.diagonal(cov, axis1=1, axis2=2), 0.0))
    rms = jnp.sqrt(jnp.maximum(jnp.diagonal(second, axis1=1, axis2=2), 0.0))
    safe = jnp.where(const, 1.0,
                     jnp.maximum(sigma, _GLR_NOISE_FLOOR * rms + 1e-12))
    zstd = jnp.sqrt(jnp.maximum(szz / n - zdev * zdev, 0.0))
    # Spark's bStd: the label's std, or |mean| where the label is constant
    zscale = jnp.where(zstd > 0.0, zstd, jnp.abs(zbar))
    lam_eff = lam / jnp.maximum(zscale, jnp.finfo(sw.dtype).tiny)
    if standardize:
        pen = lam_eff[:, None] * jnp.ones_like(safe)
    else:
        pen = lam_eff[:, None] / (safe * safe) * zscale[:, None]
    if not fit_intercept:
        # no intercept: raw second moments about 0, still scaled by the
        # centred stds (Spark's WLS without an intercept column)
        g = gshift[None, :]
        raw = m + g
        cov = (second + m[:, :, None] * g[:, None, :]
               + g[:, :, None] * raw[:, None, :])
        cz = r / n[:, None] + zshift[:, None] * m + g * zbar[:, None]
    off = const[:, :, None] | const[:, None, :]
    a = jnp.where(off, 0.0, cov / (safe[:, :, None] * safe[:, None, :]))
    diag = jnp.arange(a.shape[-1])
    a = a.at[:, diag, diag].add(jnp.where(const, 1.0, pen))
    c = jnp.where(const, 0.0, cz / safe)
    chol, y = _cholesky_bordered(a, c)
    singular = ~(jnp.isfinite(chol).all(axis=(1, 2))
                 & jnp.isfinite(y).all(axis=1))

    def ridged():
        chol_r, y_r = _cholesky_bordered(
            a.at[:, diag, diag].add(_GLR_SINGULAR_RIDGE), c)
        return (jnp.where(singular[:, None, None], chol_r, chol),
                jnp.where(singular[:, None], y_r, y))

    chol, y = jax.lax.cond(singular.any(), ridged, lambda: (chol, y))
    gamma = _back_solve(chol, y)
    beta = jnp.where(const, 0.0, gamma / safe)
    if fit_intercept:
        # a constant label: no coefficients, the intercept its mean
        beta = jnp.where((zstd > 0.0)[:, None], beta, 0.0)
        b = zbar - _mm(beta, gshift) - (beta * m).sum(axis=1)
    else:
        b = jnp.zeros_like(zbar)
    return beta, b


@partial(jax.jit, static_argnames=("max_iter", "fit_intercept"))
def fit_glr_irls_batched(
    x: jax.Array,           # [N, D] SHARED feature matrix
    y: jax.Array,           # [N]
    masks: jax.Array,       # [M, N] the sweep's distinct row masks
    lane_mask: jax.Array,   # [K] int32: the row mask of each lane
    family: jax.Array,      # [K] int32 (GLM_FAMILIES)
    link: jax.Array,        # [K] int32 (GLM_LINKS)
    reg_params: jax.Array,  # [K] λ
    tols: jax.Array,        # [K] Spark's tol
    max_iter: int = 25,
    fit_intercept: bool = True,
) -> GLRFit:
    """K generalized linear regressions (Spark's IRLS) sharing ONE plane,
    in one program: the learner the section above states, every product
    at ``GLM_PRECISION``.

    The plane is read in blocks of ``GLR_ROW_BLOCK`` rows, shifted by its
    column means as ``_lane_moments`` shifts it; no ``[K, N, D]`` buffer
    and no per-lane ``[N, D]`` copy is made. The unweighted Gram of each
    row mask is summed once, with every lane's start right-hand side; it
    gives every lane's start and every gaussian/identity lane's closed
    form. Each iteration then sums, for the lanes that have not stopped
    (gathered into the smallest of ``_glr_slot_sizes(K)`` slots that holds
    them), the weighted Grams and right-hand sides of their reweighted
    rows, and solves. A stopped lane stays as it is; the loop ends when
    every lane has stopped or after ``max_iter`` iterations. A lane stops
    when every coefficient and the intercept move by less than its ``tol``
    (Spark's rule), or by at most ``GLR_ULP_FLOOR`` ulps of their own size.
    ``mu`` is every row's fitted mean, project(g⁻¹(xβ + b))."""
    n, d = x.shape
    k = lane_mask.shape[0]
    n_masks = masks.shape[0]
    masks = masks.astype(x.dtype)
    y = y.astype(x.dtype)
    block = min(GLR_ROW_BLOCK, n)
    n_blocks = -(-n // block)
    big = jnp.asarray(jnp.finfo(x.dtype).max, x.dtype)
    gshift = x.mean(axis=0)                                    # [D]

    def rows_of(i):
        """Block ``i``: (x, y, masks) at rows [start, start + block), the
        rows an earlier block already read masked off."""
        start = jnp.minimum(i * block, n - block)
        keep = (start + jnp.arange(block)) >= i * block
        xb = jax.lax.dynamic_slice(x, (start, 0), (block, d))
        yb = jax.lax.dynamic_slice(y, (start,), (block,))
        mb = jax.lax.dynamic_slice(masks, (0, start), (n_masks, block))
        return xb, yb, mb * keep.astype(x.dtype)

    def gram_of(xc, v):
        """Σ_rows v·xc·xcᵀ for each row of ``v`` [S, B]: [S, D, D]."""
        return _mm(jnp.swapaxes(xc[None] * v[:, :, None], 1, 2), xc)

    fam_k, link_k = family[:, None], link[:, None]

    def start_label(yb):
        """Each lane's start label g(μ⁰) at a block's rows [K, B]."""
        return _glr_link(_glr_initialize(yb[None], fam_k), link_k)

    # each lane's start label is summed about its mean over the first block
    _x0, y0, m0 = rows_of(0)
    ml0 = m0[lane_mask]
    zshift0 = (jnp.where(ml0 > 0, start_label(y0), 0.0) * ml0).sum(
        axis=1) / jnp.maximum(ml0.sum(axis=1), 1.0)

    def start_block(i, acc):
        g0, s10, sw0, lo, hi, r0, sz0, szz0 = acc
        xb, yb, mb = rows_of(i)
        xc = xb - gshift
        with jax.named_scope("glm/gram"):
            g0 = g0 + gram_of(xc, mb)
            s10 = s10 + _mm(mb, xc)
            sw0 = sw0 + mb.sum(axis=1)
        inside = mb[:, :, None] > 0
        lo = jnp.minimum(lo, jnp.where(inside, xb[None], big).min(axis=1))
        hi = jnp.maximum(hi, jnp.where(inside, xb[None], -big).max(axis=1))
        with jax.named_scope("glm/reweight"):
            ml = mb[lane_mask]                                  # [K, B]
            z0 = jnp.where(ml > 0, start_label(yb) - zshift0[:, None], 0.0)
            vz = ml * z0
        with jax.named_scope("glm/gram"):
            r0 = r0 + _mm(vz, xc)
            sz0 = sz0 + vz.sum(axis=1)
            szz0 = szz0 + (vz * z0).sum(axis=1)
        return g0, s10, sw0, lo, hi, r0, sz0, szz0

    zeros_k = jnp.zeros(k, x.dtype)
    g0, s10, sw0, lo, hi, r0, sz0, szz0 = jax.lax.fori_loop(
        0, n_blocks, start_block,
        (jnp.zeros((n_masks, d, d), x.dtype),
         jnp.zeros((n_masks, d), x.dtype), jnp.zeros(n_masks, x.dtype),
         jnp.full((n_masks, d), big), jnp.full((n_masks, d), -big),
         jnp.zeros((k, d), x.dtype), zeros_k, zeros_k))
    const = (hi <= lo)[lane_mask]                               # [K, D]
    with jax.named_scope("glm/solve"):
        beta, b = _glr_wls(
            sw0[lane_mask], s10[lane_mask], g0[lane_mask], sz0, szz0, r0,
            zshift0, const, reg_params, gshift, True, fit_intercept)
    # gaussian/identity is Spark's closed form: the start is its answer
    active = ~((family == 0) & (link == 0)) & (max_iter > 0)
    sizes = _glr_slot_sizes(k)
    floor = GLR_ULP_FLOOR * 2.0 ** -23

    def step(s, state):
        """One IRLS iteration over the (at most ``s``) lanes that have not
        stopped, gathered into ``s`` slots."""
        t, beta, b, active, iters, stol, sfloor, proj = state
        order = jnp.argsort(~active, stable=True)               # active first
        idx = order[:s]
        live = active[idx]
        bs, bb = beta[idx], b[idx]
        fam_s, link_s = family[idx][:, None], link[idx][:, None]
        lm = lane_mask[idx]
        off = _mm(bs, gshift) + bb

        def body(i, acc):
            gram, s1, sw, sz, szz, r, pj = acc
            xb, yb, mb = rows_of(i)
            xc = xb - gshift
            ml = mb[lm] * live[:, None].astype(x.dtype)         # [s, B]
            inside = ml > 0
            with jax.named_scope("glm/reweight"):
                # z is summed about the lane's η at the shifted plane's
                # origin, ``off``
                lin = _mm(xc, bs.T).T
                raw = _glr_unlink(lin + off[:, None], link_s)
                mu = _glr_project(raw, fam_s)
                gp = _glr_deriv(mu, link_s)
                z = jnp.where(inside, lin + (yb[None] - mu) * gp, 0.0)
                w = 1.0 / (gp * gp * _glr_variance(mu, fam_s))
                v = jnp.where(inside, ml * w, 0.0)
                vz = v * z
                pj = pj + (inside & (mu != raw)).sum(axis=1)
            with jax.named_scope("glm/gram"):
                gram = gram + gram_of(xc, v)
                s1 = s1 + _mm(v, xc)
                r = r + _mm(vz, xc)
                sw = sw + v.sum(axis=1)
                sz = sz + vz.sum(axis=1)
                szz = szz + (vz * z).sum(axis=1)
            return gram, s1, sw, sz, szz, r, pj

        zs = jnp.zeros(s, x.dtype)
        gram, s1, sw, sz, szz, r, pj = jax.lax.fori_loop(
            0, n_blocks, body,
            (jnp.zeros((s, d, d), x.dtype), jnp.zeros((s, d), x.dtype), zs,
             zs, zs, jnp.zeros((s, d), x.dtype), jnp.zeros(s, jnp.int32)))
        with jax.named_scope("glm/solve"):
            nbeta, nbias = _glr_wls(
                sw, s1, gram, sz, szz, r, off, const[idx], reg_params[idx],
                gshift, False, fit_intercept)
            dbeta, dbias = jnp.abs(nbeta - bs), jnp.abs(nbias - bb)
            tol = tols[idx]
            by_tol = jnp.maximum(dbeta.max(axis=1), dbias) < tol
            by_floor = (
                (dbeta <= jnp.maximum(tol[:, None], floor * jnp.maximum(
                    jnp.abs(nbeta), jnp.abs(bs)))).all(axis=1)
                & (dbias <= jnp.maximum(tol, floor * jnp.maximum(
                    jnp.abs(nbias), jnp.abs(bb)))))
            stop = live & (by_tol | by_floor)
        beta = beta.at[idx].set(jnp.where(live[:, None], nbeta, bs))
        b = b.at[idx].set(jnp.where(live, nbias, bb))
        active = active.at[idx].set(live & ~stop)
        iters = iters.at[idx].add(live.astype(jnp.int32))
        stol = stol.at[idx].set(stol[idx] | (stop & by_tol))
        sfloor = sfloor.at[idx].set(sfloor[idx] | (stop & ~by_tol))
        proj = proj.at[idx].add(jnp.where(live, pj, 0))
        return t + 1, beta, b, active, iters, stol, sfloor, proj

    def more(state):
        return (state[0] < max_iter) & state[3].any()

    def iterate(state):
        branch = jnp.searchsorted(
            jnp.asarray(sizes, jnp.int32), state[3].sum(dtype=jnp.int32))
        return jax.lax.switch(
            branch, [partial(step, s) for s in sizes], state)

    no = jnp.zeros(k, bool)
    t, beta, b, active, iters, stol, sfloor, proj = jax.lax.while_loop(
        more, iterate,
        (jnp.int32(0), beta, b, active, jnp.zeros(k, jnp.int32), no, no,
         jnp.zeros(k, jnp.int32)))
    with jax.named_scope("glm/scores"):
        eta = _mm(x - gshift, beta.T).T + (_mm(beta, gshift) + b)[:, None]
        raw = _glr_unlink(eta, link[:, None])
        mu = _glr_project(raw, family[:, None])
        rows = masks[lane_mask] > 0                              # [K, N]
        proj = proj + (rows & (mu != raw)).sum(axis=1)
        nonpositive = (rows & (mu <= 0.0)).sum(axis=1)
    return GLRFit(beta, b, mu, iters, stol, sfloor, proj, nonpositive, t)


def fit_glr_irls(x, y, row_mask, reg_param, family: int = 0, link: int = 0,
                 max_iter: int = 25, tol: float = 1e-6,
                 fit_intercept: bool = True) -> GLRFit:
    """One generalized linear regression: the K=1 lane of
    ``fit_glr_irls_batched``, so a single fit and a sweep's lane run
    IDENTICAL math."""
    one = jnp.ones(1, jnp.int32)
    return fit_glr_irls_batched(
        x, y, jnp.asarray(row_mask)[None], jnp.zeros(1, jnp.int32),
        one * int(family), one * int(link),
        jnp.full(1, reg_param, jnp.float32), jnp.full(1, tol, jnp.float32),
        max_iter=max_iter, fit_intercept=fit_intercept)


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
        r = (_mm(xs, w) - yc) * row_mask
        return _mm(xs.T, r) / n + l2 * w

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

    def _glr_args(k: int):
        f32, i32 = "float32", "int32"
        return (
            jax.ShapeDtypeStruct((16, 3), f32),   # x
            jax.ShapeDtypeStruct((16,), f32),     # y
            jax.ShapeDtypeStruct((2, 16), f32),   # masks
            jax.ShapeDtypeStruct((k,), i32),      # lane_mask
            jax.ShapeDtypeStruct((k,), i32),      # family
            jax.ShapeDtypeStruct((k,), i32),      # link
            jax.ShapeDtypeStruct((k,), f32),      # reg_params
            jax.ShapeDtypeStruct((k,), f32),      # tols
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
        dict(
            name="logistic_multinomial_batched",
            fn=fit_logistic_multinomial_batched,
            build=lambda k: (
                _glm_args(k),
                dict(num_classes=3, num_iters=2, fit_intercept=True,
                     standardization=True),
            ),
            buckets=(8, 64, 96),
            bucket_axis="lanes",
            base_fn=getattr(
                fit_logistic_multinomial_batched, "__wrapped__", None
            ),
            static_argnames=(
                "num_classes", "num_iters", "fit_intercept",
                "standardization",
            ),
        ),
        dict(
            name="glr_irls_batched",
            fn=fit_glr_irls_batched,
            build=lambda k: (
                _glr_args(k), dict(max_iter=2, fit_intercept=True)),
            buckets=(8, 64, 96),
            bucket_axis="lanes",
            base_fn=getattr(fit_glr_irls_batched, "__wrapped__", None),
            static_argnames=("max_iter", "fit_intercept"),
        ),
    ]
