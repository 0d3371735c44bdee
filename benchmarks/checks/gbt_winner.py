"""The check of a gradient-boosted-tree sweep (Spark ML's GBTClassifier as
TransmogrifAI's selector runs it, binary label), with its plain reference:
float32 ``jax.numpy`` at ``highest`` matmul precision, in row blocks (a
leaf's value alone is a float64 mean taken on the host: ``_leaves`` says
why). A configuration names this file under ``"check"``. Nothing here
imports the program; thresholds, bin codes and the blocked histogram are
those of ``checks/xgb_winner.py``, the dense numbering of a level's live
nodes and the routing those of ``checks/forest_winner.py``.

The learner (``cfg["learner"]`` states the same; lane with row mask m, label
y in {0, 1}, y~ = 2y - 1, F columns, F_0 = 0)::

    round 1:     t_i = y~_i,                                  w_1 = 1
    round r > 1: t_i = 4 y~_i / (1 + exp(2 y~_i F_{r-1}(x_i))), w_r = step_size
    tree T_r over the rows of m, every node searching all F columns:
      node: S = sum t, n = its rows, prediction S / n
      split (f, b), left = code <= b, valid iff n_L >= min_instances_per_node
        and n_R >= min_instances_per_node (ROW COUNTS)
      gain(f, b) = (S_L^2/n_L + S_R^2/n_R - S^2/n) / n   (variance decrease)
      the node splits on its best valid (f, b) (ties: lowest f, then lowest
        b) iff that gain >= min_info_gain and > 0; growth stops at max_depth
    F_r = F_{r-1} + w_r T_r;  P(y = 1) = 1 / (1 + exp(-2 F_M))

The reference grows by ``bg = (S_L^2/n_L + S_R^2/n_R - S^2/n) / 2`` and the
rule ``2 bg / n`` (the same float32 expressions on the same sums give the
same arg-max), and reads, of the LAST timed sweep:

* every fold lane: its own trees of each grid point on each fold's training
  rows, the fold's validation rows scored, the widest gap to the metric the
  program reported (``fold_metric_gap``);
* the winner's refit, node by node along the program's own routing, each
  round's targets from the margin of the reference's own leaves:
  ``split_gain_gap`` (how far the split taken lies under the best valid
  one, as a share of it), ``leaf_value_gap`` (over the tree's largest
  leaf), ``stop_rule_violations`` (a child of a split with fewer ROWS than
  ``min_instances_per_node``; and, by the variance decrease per row with a
  band of ``STOP_BAND`` around ``min_info_gain`` for float32 rounding, a
  node split under it or left whole though its best valid gain passes it),
  ``first_tree_weight_gap`` (|w_0 - 1| + the widest |w_m - step_size| of the
  weights the product states) and ``residual_gap`` (rounds after the first:
  the widest distance, in the target's own units, between a leaf of the
  program's and the mean over that leaf's rows of r(F) at the reference's
  margin: what the program's leaves imply its targets were);
* ``hist_impl_other``, as the boosted check.

``precision="bf16"`` rounds the kernel's INPUTS, the targets, to bfloat16
(as a ``g`` held in bfloat16 would be: the histogram kernel and the leaf
sums read the same array) and keeps every sum, gain and leaf in float32:
the control, through ``stand_in``. The first round's targets are +-1 and
exact; from the second they are real numbers, so here rounding the inputs
alone already moves the sums (the two accepted cells' inputs are exact in
bfloat16, and their controls had to round the accumulations too).
"""
from __future__ import annotations

import numpy as np

from benchmarks.checks import forest_winner as level
from benchmarks.checks import xgb_winner as hist
from benchmarks.lib.reference import (
    aupr, entry, grid_points, result_of)

CHUNK, NARROW = level.CHUNK, level.NARROW
#: relative band around ``min_info_gain`` inside which the stop rule is not
#: judged: float32 rounding of S^2/n sums near 1e6 against a gain of 1e-3
STOP_BAND = 1e-3
TREE_KEYS = ("split_feat", "split_bin", "leaf_value")
FOUND = ("split_gain_gap", "leaf_value_gap", "stop_rule_violations",
         "residual_gap")


def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def tree_weights(params) -> np.ndarray:
    """[R] float32: 1 for the first tree, ``step_size`` for the rest."""
    w = np.full(int(params["max_iter"]), float(params["step_size"]),
                np.float32)
    w[:1] = 1.0
    return w


def _targets(ys, mask, margin, first, precision):
    """(m * t, m): the round's two histogram channels."""
    _, jnp = _jnp()
    t = jnp.where(first, ys, 4.0 * ys / (1.0 + jnp.exp(2.0 * ys * margin)))
    if precision == "bf16":
        t = hist._round_bf16(t)
    return t * mask, mask


def _chunk_step(codes, slot, wt, w, feat, sbin, knobs, *, n_nodes, bins,
                grow):
    """The nodes in ``n_nodes`` compact slots (``slot`` is -1 for rows of no
    such node): histograms, the gain of every (column, bin), and either the
    reference's own decision (``grow``) or what it finds wrong with the
    given ``feat``/``sbin``."""
    _, jnp = _jnp()
    mi, mig = knobs
    h = hist._level_histograms(codes, slot, wt, w, n_nodes, bins, "f32")
    hs, hn = h[0], h[1]                                   # [M, F, B]
    sl, nl = jnp.cumsum(hs, -1)[..., :-1], jnp.cumsum(hn, -1)[..., :-1]
    st, nt = hs.sum(-1, keepdims=True), hn.sum(-1, keepdims=True)
    sr, nr = st - sl, nt - nl
    bg = 0.5 * (sl**2 / nl + sr**2 / nr - st**2 / nt)
    valid = (nl >= mi) & (nr >= mi)
    gain = jnp.where(valid, bg, -jnp.inf).reshape(n_nodes, -1)
    best = gain.max(axis=1)
    arg = gain.argmax(axis=1).astype(jnp.int32)
    n_node = nt[:, 0, 0]
    occupied = n_node > 0
    if grow:
        split = (best > 0.0) & (2.0 * best / n_node >= mig)
        feat = jnp.where(split, arg // (bins - 1), -1)
        sbin = jnp.where(split, arg % (bins - 1), 0)
        return feat, sbin, (jnp.float32(0.0), jnp.int32(0))
    took = (feat >= 0) & occupied
    flat = jnp.maximum(feat, 0) * (bins - 1) + sbin
    chosen = jnp.take_along_axis(gain, flat[:, None], 1)[:, 0]
    # how far the split taken lies under the best valid one; a split the
    # reference finds invalid has lost the whole gain
    gap = jnp.where(
        took, jnp.minimum((best - chosen) / jnp.maximum(best, 1e-30), 1.0),
        0.0)
    gap = jnp.where(jnp.isfinite(gap), gap, 1.0).max()
    small = took & ~jnp.take_along_axis(
        valid.reshape(n_nodes, -1), flat[:, None], 1)[:, 0]
    # the stop rule on the variance decrease per row, of the split taken
    # whatever its children hold, and of the best valid one
    per_row = 2.0 * jnp.take_along_axis(
        bg.reshape(n_nodes, -1), flat[:, None], 1)[:, 0] / n_node
    early = took & ~((per_row >= mig * (1.0 - STOP_BAND)) & (per_row > 0))
    g_best = 2.0 * best / n_node
    late = ~(feat >= 0) & occupied & jnp.isfinite(best) & (best > 0) & (
        g_best > mig * (1.0 + STOP_BAND))
    return feat, sbin, (gap, (early | late | small).sum().astype(jnp.int32))


def _leaves(wt, w, node, theirs, slots):
    """Leaf values S/n from the rows each leaf holds, the widest distance to
    ``theirs``, and that distance over the largest leaf value. The means are
    taken in float64, on the host: a shallow tree's leaf holds 3e5 rows,
    from round 2 their targets are real numbers, and one float32 running sum
    over them strayed 6.7e-5 from the leaf's mean, enough for two score
    levels of a depth-6 lane to change places (PR 31's chip runs, seed
    631800289: PERF.md section 2)."""
    node = np.asarray(node)
    s = np.bincount(node, np.asarray(wt, np.float64), slots)
    n = np.bincount(node, np.asarray(w, np.float64), slots)
    held = n > 0
    leaf = np.where(held, s / np.where(held, n, 1.0), 0.0).astype(np.float32)
    diff = np.where(held, np.abs(np.asarray(theirs, np.float32) - leaf), 0.0)
    diff = float(np.where(np.isfinite(diff), diff, np.inf).max())
    return leaf, diff, diff / float(np.abs(leaf).max() + 1e-12)


_PROGRAMS: dict = {}


def _programs():
    if not _PROGRAMS:
        jax, _ = _jnp()
        _PROGRAMS.update(
            targets=jax.jit(_targets, static_argnames=("precision",)),
            compact=jax.jit(level._compact, static_argnames=("cap",)),
            step=jax.jit(_chunk_step, static_argnames=(
                "n_nodes", "bins", "grow")),
            route=jax.jit(level._route),
        )
    return _PROGRAMS


# -------------------------------------------------------------- the rounds
def boosted(codes, y, mask, params, trees=None, precision="f32"):
    """Grow (``trees`` None) or check (``trees`` = the program's split_feat
    / split_bin [R, depth, 2^depth] and leaf_value [R, 2^depth]) ``max_iter``
    rounds of depth-``max_depth`` trees on the rows of ``mask``.

    Returns (trees, findings, the margin F of every row under the
    reference's own leaves). Rows outside ``mask`` count in no histogram and
    no leaf, and are routed and scored like the others."""
    _, jnp = _jnp()
    depth, bins = int(params["max_depth"]), int(params["max_bins"])
    rounds = int(params["max_iter"])
    weights = tree_weights(params)
    knobs = jnp.asarray([float(params["min_instances_per_node"]),
                         float(params["min_info_gain"])], jnp.float32)
    n = codes.shape[0]
    slots, cap = 1 << depth, max(1 << max(depth - 1, 0), NARROW)
    cap = -(-cap // CHUNK) * CHUNK if cap > NARROW else NARROW
    ys = 2.0 * jnp.asarray(y, jnp.float32) - 1.0
    mask = jnp.asarray(mask, jnp.float32)
    prog = _programs()

    margin = jnp.zeros(n, jnp.float32)
    out = {k: [] for k in TREE_KEYS}
    found = {"split_gain_gap": 0.0, "leaf_value_gap": 0.0,
             "stop_rule_violations": 0, "residual_gap": 0.0}
    for r in range(rounds):
        wt, w = prog["targets"](ys, mask, margin, jnp.asarray(r == 0),
                                precision=precision)
        heap = jnp.ones(n, jnp.int32)
        # every row is routed (a fold's validation rows are scored below);
        # w is zero outside the mask, so only its rows are counted
        active = jnp.ones(n, bool)
        feats = np.full((depth, slots), -1, np.int32)
        sbins = np.zeros((depth, slots), np.int32)
        for lv in range(depth):
            slot, n_live, slot_heap = prog["compact"](
                heap, active, jnp.int32(lv), cap=cap)
            n_live = int(n_live)
            lv_f = np.full(cap, -1, np.int32)
            lv_b = np.zeros(cap, np.int32)
            heap_np = np.asarray(slot_heap)
            ids = np.maximum(heap_np - (1 << lv), 0)
            if trees is not None:
                lv_f = np.where(heap_np > 0,
                                trees["split_feat"][r, lv][ids], -1)
                lv_b = np.where(heap_np > 0,
                                trees["split_bin"][r, lv][ids], 0)
                lv_f, lv_b = lv_f.astype(np.int32), lv_b.astype(np.int32)
            width = NARROW if n_live <= NARROW else CHUNK
            for c0 in range(0, n_live, width):
                here = (slot >= c0) & (slot < c0 + width)
                cf, cb, (gap, wrong) = prog["step"](
                    codes, jnp.where(here, slot - c0, -1), wt, w,
                    jnp.asarray(lv_f[c0:c0 + width]),
                    jnp.asarray(lv_b[c0:c0 + width]), knobs,
                    n_nodes=width, bins=bins, grow=trees is None)
                lv_f[c0:c0 + width] = np.asarray(cf)
                lv_b[c0:c0 + width] = np.asarray(cb)
                found["split_gain_gap"] = max(
                    found["split_gain_gap"], float(gap))
                found["stop_rule_violations"] += int(wrong)
            heap, active = prog["route"](
                codes, heap, active, slot, jnp.asarray(lv_f),
                jnp.asarray(lv_b))
            feats[lv, ids[:n_live]] = lv_f[:n_live]
            sbins[lv, ids[:n_live]] = lv_b[:n_live]
        theirs = (np.zeros(slots, np.float32) if trees is None
                  else trees["leaf_value"][r])
        leaf, dist, gap = _leaves(wt, w, heap - slots, theirs, slots)
        if trees is not None:
            found["leaf_value_gap"] = max(found["leaf_value_gap"], gap)
            if r > 0:
                found["residual_gap"] = max(found["residual_gap"], dist)
        margin = margin + jnp.float32(weights[r]) * jnp.asarray(leaf)[
            heap - slots]
        out["split_feat"].append(feats)
        out["split_bin"].append(sbins)
        out["leaf_value"].append(leaf)
    out = {k: np.stack(v) for k, v in out.items()}
    return out, found, np.asarray(margin)


# ----------------------------------------------------------- the comparison
def _fold_metrics(cfg, ref, codes, precision):
    """[point][fold] validation metric of the reference's own fit of every
    fold lane, and the grid points; those of the reference's own binned
    plane are kept on ``ref``."""
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
    thr, codes = hist._binned(ref, ref["x"], int(grid["max_bins"]))
    out = []
    tgap = np.inf
    if winner["thresholds"] is not None:
        theirs = np.asarray(winner["thresholds"], np.float32)
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
    arrays = winner["arrays"]
    depth, rounds = int(grid["max_depth"]), int(grid["max_iter"])
    shapes = {"split_feat": (rounds, depth, 1 << depth),
              "split_bin": (rounds, depth, 1 << depth),
              "leaf_value": (rounds, 1 << depth)}
    if any(k not in arrays or np.shape(arrays[k]) != s
           for k, s in shapes.items()):
        found = dict.fromkeys(FOUND, np.inf)
    else:
        mask = np.asarray(product["plane"]["row_mask"], np.float32)
        _, found, _ = boosted(
            codes, ref["y"], mask, grid,
            trees={k: np.asarray(arrays[k]) for k in TREE_KEYS})
    for name in FOUND:
        out.append(entry(name, found[name], limits[name]))
    want = tree_weights(grid)
    stated = np.asarray(arrays.get("tree_weights", ()), np.float32)
    wgap = np.inf
    if stated.shape == want.shape:
        wgap = float(np.abs(stated - want)[0]
                     + np.abs(stated - want)[1:].max(initial=0.0))
    out.append(entry("first_tree_weight_gap", wgap,
                     limits["first_tree_weight_gap"]))
    other = product["states"].get("hist_impl") != cfg["hist_impl"]
    out.append(entry("hist_impl_other", int(other), limits["hist_impl_other"]))
    return out


def stand_in(cfg, ref, x, precision) -> dict:
    """The reference's own sweep at ``precision`` on the plane ``x``: every
    fold lane fitted and scored, the best grid point refitted on all
    training rows."""
    bins = int(cfg["estimator_defaults"]["max_bins"])
    thr, codes = hist._binned(ref, x, bins)
    points, values = _fold_metrics(cfg, ref, codes, precision)
    best = int(np.argmax([np.mean(v) for v in values]))
    params = {**cfg["estimator_defaults"], **points[best]}
    trees, _, _ = boosted(codes, ref["y"], np.ones(len(ref["y"]), np.float32),
                          params, precision=precision)
    return {
        "summary": {
            "bestModelType": "OpGBTClassifier",
            "bestGrid": dict(points[best]),
            "validationResults": [
                {"grid": dict(p), "metricValues": list(v)}
                for p, v in zip(points, values)],
            "candidateAttempts": [],
        },
        "winner": {"grid": dict(points[best]),
                   "arrays": {**trees, "tree_weights": tree_weights(params)},
                   "thresholds": thr},
        "states": {"hist_impl": cfg["hist_impl"]},
    }
