"""The check of a random-forest sweep on a label of K > 2 classes (Spark
ML's RandomForestClassifier as TransmogrifAI's multiclass selector runs it),
with its plain reference: float32 ``jax.numpy`` at ``highest`` matmul
precision, in row blocks. A configuration names this file under
``"check"``. Nothing here imports the program, and nothing comes from
``checks/forest_winner.py``; the float64 quantile thresholds and the bin
codes are ``checks/xgb_winner.py``'s, as every cell's.

The learner (``cfg["learner"]`` states the same), for tree t of a lane with
row mask m, label y in {0, …, K - 1}, F columns::

    tkeys          = jax.random.split(jax.random.PRNGKey(seed), num_trees)
    k_boot, k_cols = jax.random.split(tkeys[t])
    w_i = m_i * c_i,  c = jax.random.poisson(k_boot, subsampling_rate, (n,))
          (c = 1 when num_trees is 1 and the rate is 1.0)
    node j (heap index: root 1, children 2j and 2j+1), rows routed to it:
      C_k = sum w_i 1[y_i = k], W = sum_k C_k, gini = 1 - sum_k (C_k / W)^2
      S(t, j) = jax.random.choice(jax.random.fold_in(k_cols, j), F,
                                  (n_sub,), replace=False)
      (f, b) admissible iff f in S(t, j), W_L >= min_instances_per_node and
        W_R >= min_instances_per_node (rows with code > b go right)
      gain(f, b) = gini - (W_L/W) gini_L - (W_R/W) gini_R
                 = (1/W) sum_k (C_kL^2/W_L + C_kR^2/W_R - C_k^2/W)
      the node splits on its best admissible (f, b) (ties: lowest f, then
        lowest b) iff that gain >= min_info_gain and > 0
    a leaf's value is the vector C / W; the forest's probability is the mean
    of its trees' leaf vectors, its prediction the arg max (ties: lowest k);
    the validation metric is the weighted F1.

Histograms are K channels a (node, column, bin): w and w 1[y = k] for
k = 1 … K - 1, one-hot matmuls over row blocks of 8,192 accumulated in
float32 (a scatter-add serialises per element on the TPU; the sums are
integers below 2^24 either way, so exact). Class 0's count is W less the
others'. To GROW, the reference takes the arg max of
``bg = (sum_{k>=1} X_k + X_S) / 2``, X the bracket above for one channel
and S the sum of classes 1 … K - 1: class 0's bracket equals S's (C_0 =
W - S and the linear terms cancel over a split), and a gain that small
beside its terms turns its arg max on the last bits of the expression, so
the reference adds in the order the learner's line names the classes; the
split is taken iff ``bg > 0`` and ``2 bg / W >= min_info_gain``. To JUDGE
(the winner's walk) it uses the source's own form, 1 - sum p^2 of parent
and children over all K counts.

It reads, of the LAST timed sweep:

* every fold lane: its own forest of each grid point on each fold's
  training rows, the fold's validation rows scored by the weighted F1 in
  float64, the widest gap to the metric the program reported
  (``fold_metric_gap``);
* the winner's refit, node by node along the program's own routing:
  ``split_gain_gap`` (how far the Gini gain of the split taken lies under
  the best admissible one, as a share of it), ``leaf_value_gap`` (the
  widest gap over the K-vector of any leaf), ``class_prob_gap`` (the
  forest's [rows, K] probabilities on the first fold's validation rows:
  the program's leaves against the reference's, along the same routing),
  ``node_subset_violations``, ``stop_rule_violations`` (with a band of
  ``STOP_BAND`` around ``min_info_gain`` for float32 rounding);
* what the program states of itself: ``forest_learner_other`` (1 unless it
  states the configuration's ``forest_multiclass``: one forest whose nodes
  hold K class counts) and ``hist_impl_other``.

``precision="bf16"`` holds the histograms' accumulations and the leaves in
bfloat16: the control, through ``stand_in``. (Rounding the inputs alone
changes nothing: they are small integers.)
"""
from __future__ import annotations

import math

import numpy as np

from benchmarks.checks import xgb_winner as hist
from benchmarks.lib.reference import entry, grid_points, result_of

#: node slots one histogram pass holds; a level with more live nodes takes
#: several passes (rows of the other slots count nowhere)
CHUNK = 128
NARROW = 32
#: relative band around ``min_info_gain`` inside which the stop rule is not
#: judged: float32 rounding of a gain of 1e-3 from impurities near 0.6
STOP_BAND = 1e-3
TREE_KEYS = ("split_feat", "split_bin", "leaf_value")
FOUND = ("split_gain_gap", "leaf_value_gap", "class_prob_gap",
         "node_subset_violations", "stop_rule_violations")


def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def n_subset(strategy: str, features: int, num_trees: int) -> int:
    """Columns a node may split on, as Spark's DecisionTreeMetadata resolves
    ``featureSubsetStrategy`` for classification."""
    s = strategy.lower()
    if s == "auto":
        s = "all" if num_trees == 1 else "sqrt"
    return {
        "all": features,
        "sqrt": math.ceil(math.sqrt(features)),
        "onethird": math.ceil(features / 3.0),
        "log2": max(1, math.ceil(math.log2(features))),
    }[s]


def tree_keys(seed: int, num_trees: int):
    """[(k_boot, k_cols)] per tree."""
    jax, _ = _jnp()
    tkeys = jax.random.split(jax.random.PRNGKey(int(seed)), int(num_trees))
    return [tuple(jax.random.split(tk)) for tk in tkeys]


def bootstrap_counts(k_boot, rate: float, n: int, num_trees: int):
    jax, jnp = _jnp()
    if num_trees == 1 and rate == 1.0:
        return jnp.ones(n, jnp.float32)
    return jax.random.poisson(k_boot, jnp.float32(rate), (n,)).astype(
        jnp.float32)


def weighted_f1(y: np.ndarray, pred: np.ndarray) -> float:
    """OpMultiClassificationEvaluator's F1: each class's F1 weighted by its
    share of the rows, over the classes seen as label or prediction."""
    y, pred = np.asarray(y, np.int64), np.asarray(pred, np.int64)
    total = 0.0
    for c in np.union1d(y, pred):
        tp = float(((pred == c) & (y == c)).sum())
        fp = float(((pred == c) & (y != c)).sum())
        fn = float(((pred != c) & (y == c)).sum())
        p = tp / (tp + fp) if tp + fp > 0 else 0.0
        r = tp / (tp + fn) if tp + fn > 0 else 0.0
        f = 2 * p * r / (p + r) if p + r > 0 else 0.0
        total += f * float((y == c).sum()) / max(len(y), 1)
    return total


# ------------------------------------------------------------ one level
def _class_histograms(codes, slot, wk, n_nodes, bins, precision):
    """[K, n_nodes, F, bins]: per node, column and bin the sums of each row
    of ``wk`` ([K, N]: w 1[y = 1], …, w 1[y = K - 1], w) over the rows with
    ``slot`` in [0, n_nodes); the others count nowhere."""
    jax, jnp = _jnp()
    n, f = codes.shape
    k = wk.shape[0]
    pad, nblk = hist._blocks(n)
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    prec = None if precision == "bf16" else jax.lax.Precision.HIGHEST

    def body(acc, blk):
        c, nd, wb = blk                                   # wb [K, block]
        node1h = jax.nn.one_hot(nd, n_nodes, dtype=jnp.float32)
        lhs = (wb[:, :, None] * node1h[None]).astype(dt)  # [K, block, M]
        lhs = jnp.swapaxes(lhs, 1, 2).reshape(k * n_nodes, -1)
        code1h = jax.nn.one_hot(c, bins, dtype=dt).reshape(c.shape[0], -1)
        return acc + jnp.matmul(
            lhs, code1h, precision=prec, preferred_element_type=jnp.float32,
        ), None

    def blocks(a, fill, axis=0):
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, pad)
        a = jnp.pad(a, widths, constant_values=fill)
        if axis:                                          # [K, N] -> [nblk, K, B]
            return jnp.swapaxes(a.reshape(k, nblk, hist.ROW_BLOCK), 0, 1)
        return a.reshape(nblk, hist.ROW_BLOCK, *a.shape[1:])

    acc0 = jnp.zeros((k * n_nodes, f * bins), dtype=jnp.float32)
    acc, _ = jax.lax.scan(
        body, acc0, (blocks(codes, 0), blocks(slot, -1), blocks(wk, 0.0, 1)))
    if precision == "bf16":
        acc = hist._round_bf16(acc)
    return acc.reshape(k, n_nodes, f, bins)


def _compact(heap, active, level, *, cap):
    """Dense numbering of the level's live nodes: each active row's slot
    (-1 for the others), the live count, and each slot's heap index (0
    where none)."""
    jax, jnp = _jnp()
    base = jnp.left_shift(jnp.int32(1), level)
    node = jnp.where(active, heap - base, cap)
    live = jax.ops.segment_sum(
        active.astype(jnp.int32), node, cap + 1)[:cap] > 0
    rank = jnp.cumsum(live) - live
    slot = jnp.where(active, rank[jnp.minimum(node, cap - 1)], -1)
    n_live = live.sum()
    ids = jnp.nonzero(live, size=cap, fill_value=0)[0].astype(jnp.int32)
    slot_heap = jnp.where(jnp.arange(cap) < n_live, ids + base, 0)
    return slot.astype(jnp.int32), n_live, slot_heap


def _gini(counts, w):
    """1 - sum_k (C_k / W)^2 from [K, ...] counts."""
    _, jnp = _jnp()
    return 1.0 - ((counts / w) ** 2).sum(axis=0)


def _chunk_step(codes, slot, wk, slot_heap, k_cols, feat, sbin, knobs, *,
                n_nodes, bins, n_sub, precision, grow):
    """The nodes in ``n_nodes`` compact slots (``slot`` is -1 for rows of no
    such node): histograms, each node's admissible columns, the gain of
    every (column, bin), and either the reference's own decision
    (``grow``) or what it finds wrong with the given ``feat``/``sbin``."""
    jax, jnp = _jnp()
    mi, mig = knobs
    f = codes.shape[1]
    h = _class_histograms(codes, slot, wk, n_nodes, bins, precision)
    hc, hw = h[:-1], h[-1]                    # classes 1 … K - 1; W
    wl = jnp.cumsum(hw, -1)[..., :-1]
    wt = hw.sum(-1, keepdims=True)
    wr = wt - wl

    def bracket(c):
        cl = jnp.cumsum(c, -1)[..., :-1]
        ct = c.sum(-1, keepdims=True)
        cr = ct - cl
        return cl**2 / wl + cr**2 / wr - ct**2 / wt

    total = bracket(hc[0])
    for k in range(1, hc.shape[0]):
        total = total + bracket(hc[k])
    bg = 0.5 * (total + bracket(hc.sum(axis=0)))          # [M, F, B - 1]
    if n_sub < f:
        def draw(j):
            return jax.random.choice(
                jax.random.fold_in(k_cols, j), f, (n_sub,), replace=False)

        sel = jax.vmap(draw)(slot_heap)                   # [M, n_sub]
        admitted = (sel[:, :, None] == jnp.arange(f)).any(axis=1)
    else:
        admitted = jnp.ones((n_nodes, f), bool)
    valid = (wl >= mi) & (wr >= mi) & admitted[:, :, None]
    gain = jnp.where(valid, bg, -jnp.inf).reshape(n_nodes, -1)
    best = gain.max(axis=1)
    arg = gain.argmax(axis=1).astype(jnp.int32)
    w_node = wt[:, 0, 0]
    occupied = w_node > 0
    if grow:
        split = (best > 0.0) & (2.0 * best / w_node >= mig)
        feat = jnp.where(split, arg // (bins - 1), -1)
        sbin = jnp.where(split, arg % (bins - 1), 0)
        zero = jnp.float32(0.0)
        return feat, sbin, (zero, jnp.int32(0), jnp.int32(0))
    took = feat >= 0
    flat = jnp.maximum(feat, 0) * (bins - 1) + sbin

    # the source's own form of the gain, from all K counts
    left = jnp.cumsum(h, -1)[..., :-1].reshape(h.shape[0], n_nodes, -1)
    whole = h.sum(-1)[:, :, 0]                            # [K, M]

    def counts(c):                                        # [K, M] -> K counts
        return jnp.concatenate([(c[-1] - c[:-1].sum(axis=0))[None], c[:-1]])

    def gini_gain(at):
        a_l = jnp.take_along_axis(left, at[None, :, None], 2)[:, :, 0]
        a_r = whole - a_l
        w0, w_l, w_r = whole[-1], a_l[-1], a_r[-1]
        return (_gini(counts(whole), w0)
                - w_l / w0 * _gini(counts(a_l), w_l)
                - w_r / w0 * _gini(counts(a_r), w_r))

    g_taken, g_best = gini_gain(flat), gini_gain(arg)
    admissible = jnp.take_along_axis(
        valid.reshape(n_nodes, -1), flat[:, None], 1)[:, 0]
    # how far the split taken lies under the best admissible one; a split
    # the reference finds inadmissible has lost the whole gain
    gap = jnp.where(
        took & occupied,
        jnp.where(admissible,
                  jnp.clip((g_best - g_taken) / jnp.maximum(g_best, 1e-30),
                           0.0, 1.0), 1.0),
        0.0)
    gap = jnp.where(jnp.isfinite(gap), gap, 1.0).max()
    outside = took & occupied & ~jnp.take_along_axis(
        admitted, jnp.maximum(feat, 0)[:, None], 1)[:, 0]
    early = took & ~(g_taken >= mig * (1.0 - STOP_BAND)) | took & ~(g_taken > 0)
    late = ~took & jnp.isfinite(best) & (g_best > mig * (1.0 + STOP_BAND)) & (
        g_best > 0)
    wrong = (occupied & (early | late)).sum().astype(jnp.int32)
    return feat, sbin, (gap, outside.sum().astype(jnp.int32), wrong)


def _route(codes, heap, active, slot, feat, sbin):
    """Rows to the next level: a row of a node that split goes by its code,
    every other row goes left and counts in no later histogram."""
    _, jnp = _jnp()
    at = jnp.maximum(slot, 0)
    row_feat = jnp.where(slot >= 0, feat[at], -1)
    code = jnp.take_along_axis(
        codes, jnp.maximum(row_feat, 0)[:, None], 1)[:, 0]
    right = active & (row_feat >= 0) & (code > sbin[at])
    return heap * 2 + right.astype(jnp.int32), active & (row_feat >= 0)


def _leaves(wk, node, theirs, *, slots, precision):
    """Leaf vectors C / W [slots, K] from the rows each leaf holds, and the
    widest gap to ``theirs`` over the K-vector of any leaf that holds
    rows."""
    jax, jnp = _jnp()
    sums = jax.vmap(lambda v: jax.ops.segment_sum(v, node, slots))(wk)
    wt = sums[-1]
    counts = jnp.concatenate(
        [(wt - sums[:-1].sum(axis=0))[None], sums[:-1]])  # [K, slots]
    leaf = (counts / wt).T
    if precision == "bf16":
        leaf = hist._round_bf16(leaf)
    held = (wt > 0)[:, None]
    leaf = jnp.where(held, leaf, 0.0)
    diff = jnp.where(held, jnp.abs(theirs - leaf), 0.0)
    return leaf, jnp.where(jnp.isfinite(diff), diff, jnp.inf).max()


_PROGRAMS: dict = {}


def _programs():
    if not _PROGRAMS:
        jax, _ = _jnp()
        _PROGRAMS.update(
            compact=jax.jit(_compact, static_argnames=("cap",)),
            step=jax.jit(_chunk_step, static_argnames=(
                "n_nodes", "bins", "n_sub", "precision", "grow")),
            route=jax.jit(_route),
            leaves=jax.jit(_leaves, static_argnames=("slots", "precision")),
        )
    return _PROGRAMS


# ------------------------------------------------------------ the forest
def forest(codes, y, mask, params, classes, trees=None, precision="f32"):
    """Grow (``trees`` None) or check (``trees`` = the program's split_feat /
    split_bin [T, depth, 2^depth] and leaf_value [T, 2^depth, K]) a forest
    of ``num_trees`` depth-``max_depth`` trees on the rows of ``mask``.

    Returns (trees, findings, the forest's [N, K] probabilities on every
    row under the reference's own leaves, and under ``trees``' leaves where
    given, else None). Rows outside ``mask`` count in no histogram and no
    leaf, and are routed and scored like the others."""
    jax, jnp = _jnp()
    depth, bins = int(params["max_depth"]), int(params["max_bins"])
    num_trees = int(params["num_trees"])
    rate = float(params.get("subsampling_rate", 1.0))
    n, f = codes.shape
    n_sub = n_subset(params.get("feature_subset_strategy", "auto"), f,
                     num_trees)
    knobs = jnp.asarray([float(params["min_instances_per_node"]),
                         float(params["min_info_gain"])], jnp.float32)
    slots, cap = 1 << depth, max(1 << max(depth - 1, 0), NARROW)
    cap = -(-cap // CHUNK) * CHUNK if cap > NARROW else NARROW
    y = jnp.asarray(y, jnp.int32)
    mask = jnp.asarray(mask, jnp.float32)
    ind = jnp.stack([(y == k).astype(jnp.float32) for k in range(1, classes)])
    prog = _programs()

    out = {k: [] for k in TREE_KEYS}
    found = dict.fromkeys(FOUND, 0)
    found.update(split_gain_gap=0.0, leaf_value_gap=0.0, class_prob_gap=0.0)
    mine, theirs_p = [], []
    for t, (k_boot, k_cols) in enumerate(
            tree_keys(params.get("seed", 42), num_trees)):
        w = mask * bootstrap_counts(k_boot, rate, n, num_trees)
        wk = jnp.concatenate([ind * w[None], w[None]])    # [K, N]
        heap = jnp.ones(n, jnp.int32)
        # every row is routed (a fold's validation rows are scored below);
        # w is zero outside the mask, so only its rows are counted
        active = jnp.ones(n, bool)
        feats = np.full((depth, slots), -1, np.int32)
        sbins = np.zeros((depth, slots), np.int32)
        for level in range(depth):
            slot, n_live, slot_heap = prog["compact"](
                heap, active, jnp.int32(level), cap=cap)
            n_live = int(n_live)
            lv_f = np.full(cap, -1, np.int32)
            lv_b = np.zeros(cap, np.int32)
            heap_np = np.asarray(slot_heap)
            ids = np.maximum(heap_np - (1 << level), 0)
            if trees is not None:
                lv_f = np.where(heap_np > 0,
                                trees["split_feat"][t, level][ids], -1)
                lv_b = np.where(heap_np > 0,
                                trees["split_bin"][t, level][ids], 0)
                lv_f, lv_b = lv_f.astype(np.int32), lv_b.astype(np.int32)
            width = NARROW if n_live <= NARROW else CHUNK
            for c0 in range(0, n_live, width):
                here = (slot >= c0) & (slot < c0 + width)
                cf, cb, (gap, outside, wrong) = prog["step"](
                    codes, jnp.where(here, slot - c0, -1), wk,
                    slot_heap[c0:c0 + width], k_cols,
                    jnp.asarray(lv_f[c0:c0 + width]),
                    jnp.asarray(lv_b[c0:c0 + width]), knobs,
                    n_nodes=width, bins=bins, n_sub=n_sub,
                    precision=precision, grow=trees is None)
                lv_f[c0:c0 + width] = np.asarray(cf)
                lv_b[c0:c0 + width] = np.asarray(cb)
                found["split_gain_gap"] = max(
                    found["split_gain_gap"], float(gap))
                found["node_subset_violations"] += int(outside)
                found["stop_rule_violations"] += int(wrong)
            heap, active = prog["route"](
                codes, heap, active, slot, jnp.asarray(lv_f),
                jnp.asarray(lv_b))
            feats[level, ids[:n_live]] = lv_f[:n_live]
            sbins[level, ids[:n_live]] = lv_b[:n_live]
        theirs = (jnp.zeros((slots, classes)) if trees is None
                  else jnp.asarray(trees["leaf_value"][t], jnp.float32))
        leaf, gap = prog["leaves"](wk, heap - slots, theirs,
                                   slots=slots, precision=precision)
        mine.append(leaf[heap - slots])
        if trees is not None:
            found["leaf_value_gap"] = max(found["leaf_value_gap"], float(gap))
            theirs_p.append(theirs[heap - slots])
        out["split_feat"].append(feats)
        out["split_bin"].append(sbins)
        out["leaf_value"].append(np.asarray(leaf))
    out = {k: np.stack(v) for k, v in out.items()}
    prob = np.asarray(jnp.stack(mine).mean(axis=0))
    given = (np.asarray(jnp.stack(theirs_p).mean(axis=0))
             if theirs_p else None)
    return out, found, prob, given


# ----------------------------------------------------------- the comparison
def _fold_metrics(cfg, ref, codes, precision):
    """[point][fold] weighted F1 of the reference's own forest of every fold
    lane, and the grid points; those of the reference's own binned plane
    are kept on ``ref``."""
    points = grid_points(cfg)
    own = "_binned" in ref and codes is ref["_binned"][1]
    if own and precision in ref.get("_fold_metrics", {}):
        return points, ref["_fold_metrics"][precision]
    values = []
    for point in points:
        params = {**cfg["estimator_defaults"], **point}
        row = []
        for train, val in ref["folds"]:
            _, _, prob, _ = forest(
                codes, ref["y"], train.astype(np.float32), params,
                int(cfg["classes"]), precision=precision)
            # ties go to the lowest class, as numpy's arg max takes them
            row.append(weighted_f1(ref["y"][val], prob[val].argmax(axis=1)))
        values.append(row)
    if own:
        ref.setdefault("_fold_metrics", {})[precision] = values
    return points, values


def winner_trees(arrays: dict) -> dict:
    """The one forest of a winner's arrays, under the program's ``c0__``
    prefix or none."""
    prefix = "c0__" if "c0__split_feat" in arrays else ""
    return {k: np.asarray(arrays[prefix + k]) for k in TREE_KEYS}


def compare(cfg, ref, product) -> list[dict]:
    limits, winner = cfg["limits"], product["winner"]
    classes = int(cfg["classes"])
    grid = {**cfg["estimator_defaults"], **winner["grid"]}
    thr, codes = hist._binned(ref, ref["x"], int(grid["max_bins"]))
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
    trees = winner_trees(winner["arrays"])
    depth, num_trees = int(grid["max_depth"]), int(grid["num_trees"])
    one_forest = (
        "c1__split_feat" not in winner["arrays"]
        and trees["split_feat"].shape == (num_trees, depth, 1 << depth)
        and trees["leaf_value"].shape == (num_trees, 1 << depth, classes))
    if not one_forest:
        found = dict.fromkeys(FOUND, np.inf)
    else:
        mask = np.asarray(product["plane"]["row_mask"], np.float32)
        _, found, prob, given = forest(
            codes, ref["y"], mask, grid, classes, trees=trees)
        val = ref["folds"][0][1]
        found["class_prob_gap"] = float(np.abs(given[val] - prob[val]).max())
    for name in FOUND:
        out.append(entry(name, found[name], limits[name]))
    states = product["states"]
    out.append(entry(
        "forest_learner_other",
        int(states.get("forest_multiclass") != cfg["forest_multiclass"]),
        limits["forest_learner_other"]))
    out.append(entry(
        "hist_impl_other", int(states.get("hist_impl") != cfg["hist_impl"]),
        limits["hist_impl_other"]))
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
    trees, _, _, _ = forest(
        codes, ref["y"], np.ones(len(ref["y"]), np.float32), params,
        int(cfg["classes"]), precision=precision)
    return {
        "summary": {
            "bestModelType": "OpRandomForestClassifier",
            "bestGrid": dict(points[best]),
            "validationResults": [
                {"grid": dict(p), "metricValues": list(v)}
                for p, v in zip(points, values)],
            "candidateAttempts": [],
        },
        "winner": {"grid": dict(points[best]), "arrays": trees,
                   "thresholds": thr},
        "states": {"hist_impl": cfg["hist_impl"],
                   "forest_multiclass": cfg["forest_multiclass"]},
    }
