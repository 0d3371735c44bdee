"""The check of a boosted-tree sweep (XGBoost binary), with its plain
reference: float32 ``jax.numpy`` at ``highest`` matmul precision, in row
blocks. A configuration names this file under ``"check"``. Nothing here
imports the program.

The reference makes its own float64 quantile thresholds and bin codes, and
then reads, of the LAST timed sweep:

* every fold lane: it grows each grid point's trees itself on each fold's
  training rows, scores the fold's validation rows, and reads the widest
  gap between its validation metric and the one the program reported
  (``fold_metric_gap``): the fold fits, their predictions and the
  evaluator at once;
* the winner's refit: every node of every tree is checked the way a served
  greedy token is: the reference builds the node's gradient histogram from
  the rows the tree above routes there and reads the gap by which the
  split the program chose lies below the reference's best split
  (``split_gain_gap``), and the gap of each leaf value
  (``leaf_value_gap``);
* what the program states of itself: the histogram implementation the
  configuration guarantees (``hist_impl_other``).

``precision="bf16"`` computes the same one step below the float32 the
configuration states (gradients, histograms and leaves held in bfloat16):
the control, through ``stand_in``.
"""
from __future__ import annotations

import numpy as np

from benchmarks.lib.reference import aupr, entry, grid_points, result_of

ROW_BLOCK = 8192

def quantile_thresholds(x: np.ndarray, bins: int) -> np.ndarray:
    """[F, bins-1] float32 edges at the quantiles k/bins of each column."""
    qs = np.linspace(0, 1, bins + 1)[1:-1]
    return np.quantile(x.astype(np.float64), qs, axis=0).T.astype(np.float32)


def _blocks(n: int):
    pad = (-n) % ROW_BLOCK
    return pad, (n + pad) // ROW_BLOCK


def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def bin_codes(x: np.ndarray, thresholds: np.ndarray):
    """int32 [N, F] on the device: thresholds strictly below each value."""
    jax, jnp = _jnp()

    @jax.jit
    def codes(xb, thr):
        return (xb[:, :, None] > thr[None, :, :]).sum(-1).astype(jnp.int32)

    thr = jnp.asarray(thresholds)
    step = 1 << 16
    return jnp.concatenate(
        [codes(jnp.asarray(x[i:i + step]), thr) for i in range(0, len(x), step)]
    )


def _round_bf16(a):
    """To bfloat16's 8 exponent and 7 mantissa bits, kept in float32. (A
    cast there and back is folded away by XLA, which allows excess
    precision.)"""
    jax, _ = _jnp()
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _level_histograms(codes, node, g, h, n_nodes, bins, precision):
    """[2, n_nodes, F, bins]: per node, feature and bin the sums of g and h
    over the rows with ``node`` in [0, n_nodes); others count nowhere."""
    jax, jnp = _jnp()
    n, f = codes.shape
    pad, nblk = _blocks(n)
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    prec = None if precision == "bf16" else jax.lax.Precision.HIGHEST

    def body(acc, blk):
        c, nd, gb, hb = blk
        node1h = jax.nn.one_hot(nd, n_nodes, dtype=jnp.float32)
        lhs = jnp.concatenate(
            [node1h * gb[:, None], node1h * hb[:, None]], axis=1
        ).astype(dt)
        code1h = jax.nn.one_hot(c, bins, dtype=dt).reshape(c.shape[0], -1)
        return acc + jnp.matmul(
            lhs.T, code1h, precision=prec,
            preferred_element_type=jnp.float32,
        ), None

    def padded(a, fill):
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                    constant_values=fill)
        return a.reshape(nblk, ROW_BLOCK, *a.shape[1:])

    acc0 = jnp.zeros((2 * n_nodes, f * bins), dtype=jnp.float32)
    acc, _ = jax.lax.scan(
        body, acc0,
        (padded(codes, 0), padded(node, -1), padded(g, 0.0), padded(h, 0.0)),
    )
    if precision == "bf16":
        # the control stores its histograms in bfloat16 too (the MXU's f32
        # accumulation stays): at round 1 g = +-0.5 and h = 0.25 are exact
        # in bfloat16, so rounding the inputs alone changes nothing
        acc = _round_bf16(acc)
    return acc.reshape(2, n_nodes, f, bins)


def _gains(hist, lam, gamma, mcw):
    """[n_nodes, F, bins-1] gain of splitting each node at (feature, bin):
    rows with code > bin go right. Invalid (a child under min_child_weight)
    is -inf."""
    _, jnp = _jnp()
    hg, hh = hist[0], hist[1]
    gl, hl = jnp.cumsum(hg, -1)[..., :-1], jnp.cumsum(hh, -1)[..., :-1]
    gt, ht = hg.sum(-1, keepdims=True), hh.sum(-1, keepdims=True)
    gr, hr = gt - gl, ht - hl
    gain = 0.5 * (
        gl**2 / (hl + lam) + gr**2 / (hr + lam) - gt**2 / (ht + lam)
    ) - gamma
    # a child's weight a rounding below the minimum still counts as valid
    valid = (hl >= mcw * (1 - 1e-5)) & (hr >= mcw * (1 - 1e-5))
    return jnp.where(valid, gain, -jnp.inf)


def _grads(margin, y, mask, precision):
    jax, jnp = _jnp()
    p = jax.nn.sigmoid(margin)
    g, h = (p - y) * mask, p * (1.0 - p) * mask
    if precision == "bf16":
        g, h = _round_bf16(g), _round_bf16(h)
    return g, h


def _level_nodes(level: int) -> int:
    """Two compiled sizes serve every level (a program per level would
    compile ten times)."""
    return 32 if level <= 5 else 512


def _level_step(codes, node, active, g, h, feat, sbin, knobs, *,
                n_nodes, bins, precision, grow):
    """One level of one tree: histograms of the live nodes, the gain of
    every (feature, bin), the split taken (the reference's own best when
    ``grow``, else the given ``feat``/``sbin``), the widest gap of a taken
    split below the best, and the rows routed to the next level."""
    _, jnp = _jnp()
    lam, gamma, mcw, mig = knobs
    hist = _level_histograms(
        codes, jnp.where(active, node, -1), g, h, n_nodes, bins, precision)
    gain = _gains(hist, lam, gamma, mcw).reshape(n_nodes, -1)
    best = gain.max(axis=1)
    occupied = hist[1, :, 0, :].sum(-1) > 0
    if grow:
        arg = gain.argmax(axis=1).astype(jnp.int32)
        split = best > mig
        feat = jnp.where(split, arg // (bins - 1), -1)
        sbin = jnp.where(split, arg % (bins - 1), 0)
    flat = jnp.maximum(feat, 0) * (bins - 1) + sbin
    chosen = jnp.take_along_axis(gain, flat[:, None], 1)[:, 0]
    chosen = jnp.where(feat >= 0, chosen, mig)
    # a split the reference finds invalid has lost the whole gain
    gap = jnp.minimum(
        (jnp.maximum(best, mig) - chosen)
        / (jnp.maximum(best, 0.0) + gamma + 1e-12), 1.0)
    gap = jnp.where(occupied, gap, 0.0).max()
    row_feat, row_bin = feat[node], sbin[node]
    code = jnp.take_along_axis(
        codes, jnp.maximum(row_feat, 0)[:, None], 1)[:, 0]
    go_right = active & (row_feat >= 0) & (code > row_bin)
    return (feat, sbin, gap, node * 2 + go_right.astype(jnp.int32),
            active & (row_feat >= 0))


def _leaves(g, h, node, theirs, mask, lam, *, slots, precision):
    """Leaf values of one tree from the rows each leaf holds, and the
    widest gap to ``theirs`` over the largest leaf value."""
    jax, jnp = _jnp()
    leaf = -jax.ops.segment_sum(g, node, slots) / (
        jax.ops.segment_sum(h, node, slots) + lam)
    if precision == "bf16":
        leaf = _round_bf16(leaf)
    held = jax.ops.segment_sum(mask, node, slots) > 0
    diff = jnp.where(held, jnp.abs(theirs - leaf), 0.0).max()
    return leaf, diff / (jnp.abs(leaf).max() + 1e-12)


_PROGRAMS: list = []


def _programs():
    """The two jitted programs, made once a process (every lane of every
    comparison reuses them)."""
    if not _PROGRAMS:
        jax, _ = _jnp()
        _PROGRAMS.extend([
            jax.jit(_level_step, static_argnames=(
                "n_nodes", "bins", "precision", "grow")),
            jax.jit(_leaves, static_argnames=("slots", "precision")),
        ])
    return _PROGRAMS


def boosted(codes, y, mask, params, trees=None, precision="f32"):
    """Grow (``trees`` None) or check (``trees`` = the program's
    split_feat/split_bin [R, depth, nodes] and leaf_value [R, nodes]) R
    rounds of depth-``max_depth`` trees on binary logistic loss.

    Returns (trees, gaps, the margin of every row under the reference's
    own leaves). ``gaps["split_gain_gap"]`` is the widest gap, over all
    nodes that hold rows, by which the split taken lies below the
    reference's best (no split counts as gain 0), relative to that node's
    best raw gain (gain + gamma); ``gaps["leaf_value_gap"]`` the widest
    leaf-value gap relative to the largest leaf value. Growing reads both
    gaps as 0. Rows outside ``mask`` count in no histogram and no leaf, and
    are routed and scored like the others."""
    jax, jnp = _jnp()
    depth, bins = int(params["max_depth"]), int(params["max_bins"])
    eta, lam = float(params["eta"]), float(params["reg_lambda"])
    knobs = jnp.asarray(
        [lam, float(params["gamma"]), float(params["min_child_weight"]),
         max(float(params.get("min_info_gain", 0.0)), 0.0)], jnp.float32)
    n = codes.shape[0]
    slots = 1 << depth
    y, mask = jnp.asarray(y, jnp.float32), jnp.asarray(mask, jnp.float32)
    step, leaves = _programs()

    margin = jnp.zeros(n, jnp.float32)
    out = {"split_feat": [], "split_bin": [], "leaf_value": []}
    split_gap = leaf_gap = 0.0
    for r in range(int(params["num_round"])):
        g, h = _grads(margin, y, mask, precision)
        node = jnp.zeros(n, jnp.int32)
        # every row is routed (a fold's validation rows are scored below);
        # g and h are zero outside the mask, so only its rows are counted
        active = jnp.ones(n, bool)
        feats = np.full((depth, slots), -1, np.int32)
        sbins = np.zeros((depth, slots), np.int32)
        for level in range(depth):
            width = min(_level_nodes(level), slots)
            if trees is not None:
                feats[level] = trees["split_feat"][r, level]
                sbins[level] = trees["split_bin"][r, level]
            feat, sbin, gap, node, active = step(
                codes, node, active, g, h,
                jnp.asarray(feats[level, :width]),
                jnp.asarray(sbins[level, :width]), knobs,
                n_nodes=width, bins=bins, precision=precision,
                grow=trees is None)
            feats[level, :width] = np.asarray(feat)
            sbins[level, :width] = np.asarray(sbin)
            split_gap = max(split_gap, float(gap))
        theirs = (jnp.zeros(slots) if trees is None
                  else jnp.asarray(trees["leaf_value"][r], jnp.float32))
        leaf, gap = leaves(g, h, node, theirs, mask, knobs[0],
                           slots=slots, precision=precision)
        if trees is not None:
            leaf_gap = max(leaf_gap, float(gap))
        margin = margin + eta * leaf[node]
        out["split_feat"].append(feats)
        out["split_bin"].append(sbins)
        out["leaf_value"].append(np.asarray(leaf))
    out = {k: np.stack(v) for k, v in out.items()}
    return (out, {"split_gain_gap": split_gap, "leaf_value_gap": leaf_gap},
            np.asarray(margin))


# ----------------------------------------------------------- the comparison
def _binned(ref, x, bins):
    """(thresholds, device bin codes) of ``x``; the reference's own plane's
    are kept on ``ref`` for the next comparison in this process."""
    if x is ref["x"] and "_binned" in ref:
        return ref["_binned"]
    thr = quantile_thresholds(x, bins)
    made = (thr, bin_codes(x, thr))
    if x is ref["x"]:
        ref["_binned"] = made
    return made


def _fold_metrics(cfg, ref, codes, precision):
    """[point][fold] validation metric of the reference's own fit of every
    fold lane, and the grid points. Those of the reference's own binned
    plane are kept on ``ref`` like it."""
    points = grid_points(cfg)
    own = "_binned" in ref and codes is ref["_binned"][1]
    if own and precision in ref.get("_fold_metrics", {}):
        return points, ref["_fold_metrics"][precision]
    values = []
    for point in points:
        params = {**cfg["estimator_defaults"], **point}
        row = []
        for train, val in ref["folds"]:
            _, _, margin = boosted(codes, ref["y"], train.astype(np.float32),
                                   params, precision=precision)
            row.append(aupr(ref["y"][val], margin[val]))
        values.append(row)
    if own:
        ref.setdefault("_fold_metrics", {})[precision] = values
    return points, values


def compare(cfg, ref, product) -> list[dict]:
    limits, winner = cfg["limits"], product["winner"]
    grid = {**cfg["estimator_defaults"], **winner["grid"]}
    thr, codes = _binned(ref, ref["x"], int(grid["max_bins"]))
    out = []
    theirs = np.asarray(winner["thresholds"], np.float32)
    tgap = np.inf
    if theirs.shape == thr.shape:
        span = np.maximum(np.abs(thr).max(axis=1, keepdims=True), 1.0)
        tgap = float((np.abs(theirs - thr) / span).max())
    out.append(entry("thresholds_gap", tgap, limits["thresholds_gap"]))
    points, mine = _fold_metrics(cfg, ref, codes, "f32")
    fgap = 0.0
    for point, row in zip(points, mine):
        r = result_of(product["summary"], point)
        if r is None or len(r["metricValues"]) != len(row):
            fgap = np.inf
            continue
        fgap = max(fgap, float(np.abs(np.asarray(r["metricValues"]) - row).max()))
    out.append(entry("fold_metric_gap", fgap, limits["fold_metric_gap"]))
    trees = {k: np.asarray(winner["arrays"][k])
             for k in ("split_feat", "split_bin", "leaf_value")}
    mask = np.asarray(product["plane"]["row_mask"], np.float32)
    _, gaps, _ = boosted(codes, ref["y"], mask, grid, trees=trees)
    for name, value in gaps.items():
        out.append(entry(name, value, limits[name]))
    other = product["states"].get("hist_impl") != cfg["hist_impl"]
    out.append(entry("hist_impl_other", int(other), limits["hist_impl_other"]))
    return out


def stand_in(cfg, ref, x, precision) -> dict:
    """The reference's own sweep at ``precision`` on the plane ``x``: every
    fold lane fitted and scored, the best grid point refitted on all
    training rows."""
    bins = int(cfg["estimator_defaults"]["max_bins"])
    thr, codes = _binned(ref, x, bins)
    points, values = _fold_metrics(cfg, ref, codes, precision)
    best = int(np.argmax([np.mean(v) for v in values]))
    params = {**cfg["estimator_defaults"], **points[best]}
    trees, _, _ = boosted(codes, ref["y"], np.ones(len(ref["y"]), np.float32),
                          params, precision=precision)
    return {
        "summary": {
            "bestModelType": "XGBoost", "bestGrid": dict(points[best]),
            "validationResults": [
                {"grid": dict(p), "metricValues": list(v)}
                for p, v in zip(points, values)],
            "candidateAttempts": [],
        },
        "winner": {"grid": dict(points[best]), "arrays": trees,
                   "thresholds": thr},
        "states": {"hist_impl": cfg["hist_impl"]},
    }
