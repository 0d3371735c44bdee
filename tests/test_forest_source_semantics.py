"""The forest as the source defines it (Spark ML's RandomForest under
TransmogrifAI's selector): per-NODE feature subsets drawn from (seed, tree,
heap index), ``min_info_gain`` compared with the impurity decrease per row,
no bootstrap for one tree. The program's forests are compared node for node
with a plain numpy reference written here; the draw is the rule
``benchmarks/configs/flagship_rf.json`` states, in ``jax.random`` alone."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.models import gbdt as G
from transmogrifai_tpu.models import hist_pallas as HP
from transmogrifai_tpu.models import trees as TR

BINS = 16
DEPTH = 6
F32 = np.float32


def _table(n, seed=3, f_wide=12, f_narrow=12):
    """(x, codes [N, F], y): wide real columns and 0/1 columns, a label
    that several of both kinds explain."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f_wide + f_narrow)).astype(F32)
    x[:, f_wide:] = x[:, f_wide:] > 0.4
    logit = (x[:, 0] + x[:, 1] * x[:, 2] - x[:, f_wide] + x[:, f_wide + 3]
             + 0.5 * np.sin(3 * x[:, 4]) + 0.4 * rng.normal(size=n))
    y = (logit > 0.2).astype(F32)
    thr = TR.quantile_thresholds(x, BINS)
    codes = np.asarray(TR.bin_data(jnp.asarray(x), jnp.asarray(thr)))
    return x, codes, y


def _groups(x):
    return G._feature_bin_groups(x)


def node_subset(k_cols, j, f, n_sub):
    """S(t, j): the rule of the configuration's file."""
    return np.asarray(jax.random.choice(
        jax.random.fold_in(k_cols, j), f, (n_sub,), replace=False))


def tree_keys(seed, num_trees):
    tkeys = jax.random.split(jax.random.PRNGKey(seed), num_trees)
    return [tuple(jax.random.split(tk)) for tk in tkeys]


def reference_forest(codes, y, mask, *, num_trees, n_sub, min_instances,
                     min_info_gain, seed, norm=4.0, depth=DEPTH, bins=BINS):
    """Plain per-node growth: float32 sums, the equations of
    ``flagship_rf.json``'s ``learner``. Returns split_feat / split_bin
    [T, depth, 2^depth], leaf_value [T, 2^depth] and, per tree, the heap
    indices of the nodes that split with the subset each drew."""
    n, f = codes.shape
    bootstrap = num_trees > 1
    feats = np.full((num_trees, depth, 1 << depth), -1, np.int32)
    sbins = np.zeros((num_trees, depth, 1 << depth), np.int32)
    leaves = np.zeros((num_trees, 1 << depth), F32)
    drawn = []
    for t, (k_boot, k_cols) in enumerate(tree_keys(seed, num_trees)):
        c = (np.asarray(jax.random.poisson(k_boot, F32(1.0), (n,)), F32)
             if bootstrap else np.ones(n, F32))
        w = (mask * c).astype(F32)
        wy = (w * y).astype(F32)
        heap = np.ones(n, np.int64)
        active = np.ones(n, bool)
        drawn.append({})
        for level in range(depth):
            for j in np.unique(heap[active]):
                rows = active & (heap == j)
                wt, pt = w[rows].sum(dtype=F32), wy[rows].sum(dtype=F32)
                subset = (node_subset(k_cols, int(j), f, n_sub)
                          if n_sub < f else np.arange(f))
                best = (F32(-np.inf), -1, 0)
                for col in sorted(int(v) for v in subset):
                    hw = np.bincount(codes[rows, col], w[rows], bins).astype(F32)
                    hp = np.bincount(codes[rows, col], wy[rows], bins).astype(F32)
                    wl, pl = np.cumsum(hw)[:-1], np.cumsum(hp)[:-1]
                    wr, pr = wt - wl, pt - pl
                    with np.errstate(all="ignore"):
                        bg = F32(0.5) * (pl * pl / wl + pr * pr / wr
                                         - pt * pt / wt)
                    ok = (wl >= min_instances) & (wr >= min_instances)
                    for b in np.nonzero(ok)[0]:
                        if bg[b] > best[0]:  # strict: lowest column, bin
                            best = (bg[b], col, int(b))
                gain, col, b = best
                with np.errstate(all="ignore"):
                    split = gain > 0 and F32(norm) * gain / wt >= F32(
                        min_info_gain)
                if not split:
                    active &= ~rows
                    continue
                drawn[t][int(j)] = set(int(v) for v in subset)
                feats[t, level, j - (1 << level)] = col
                sbins[t, level, j - (1 << level)] = b
            node = heap - (1 << level)
            took = active & (feats[t, level][node] >= 0)
            right = took & (
                codes[np.arange(n), np.maximum(feats[t, level][node], 0)]
                > sbins[t, level][node])
            heap = heap * 2 + right
            active = took
        node = heap - (1 << depth)
        pw = np.bincount(node, w, 1 << depth).astype(F32)
        pp = np.bincount(node, wy, 1 << depth).astype(F32)
        with np.errstate(all="ignore"):
            leaves[t] = np.where(pw > 0, pp / pw, 0.0)
    return {"split_feat": feats, "split_bin": sbins, "leaf_value": leaves,
            "drawn": drawn}


def _lane(trees, k):
    out = {f: np.asarray(getattr(trees, f))[k] for f in TR.Tree._fields}
    out["leaf_value"] = np.nan_to_num(out["leaf_value"])
    return out


def _assert_same(mine, theirs):
    np.testing.assert_array_equal(mine["split_feat"], theirs["split_feat"])
    np.testing.assert_array_equal(mine["split_bin"], theirs["split_bin"])
    np.testing.assert_array_equal(mine["leaf_value"], theirs["leaf_value"])


N = 2048
LANES = dict(min_instances=np.asarray([5.0, 20.0], F32),
             min_info_gain=np.asarray([0.0005, 0.005], F32))


def _masks(n, seed=9):
    rng = np.random.default_rng(seed)
    return np.stack([np.ones(n, F32), (rng.random(n) < 0.75).astype(F32)])


def _fit(codes, y, masks, strategy, num_trees, groups=None, **kw):
    f = codes.shape[1]
    n_sub = G.resolve_feature_subset(strategy, f, num_trees, True)
    args = dict(
        num_trees=num_trees, max_depth=DEPTH, num_bins=BINS, seed=17,
        feature_subset=n_sub, info_gain_norm=4.0, bootstrap=num_trees > 1,
        lowp=True, feature_groups=groups, **LANES)
    args.update(kw)
    return n_sub, TR.fit_forest_batched(jnp.asarray(codes), y, masks, **args)


@pytest.mark.parametrize("grouped", [False, True])
@pytest.mark.parametrize("num_trees", [1, 3])
@pytest.mark.parametrize("strategy", ["all", "sqrt", "onethird"])
def test_forest_equals_plain_reference_node_for_node(
    strategy, num_trees, grouped
):
    x, codes, y = _table(N)
    masks = _masks(N)
    n_sub, trees = _fit(codes, y, masks, strategy, num_trees,
                        groups=_groups(x) if grouped else None)
    grown = 0
    for k in range(2):
        ref = reference_forest(
            codes, y, masks[k], num_trees=num_trees, n_sub=n_sub,
            min_instances=LANES["min_instances"][k],
            min_info_gain=LANES["min_info_gain"][k], seed=17)
        _assert_same(_lane(trees, k), ref)
        grown += int((ref["split_feat"] >= 0).sum())
    assert grown >= 4 * num_trees, "the lanes grow trees worth comparing"


def test_sibling_nodes_draw_different_subsets_and_splits_stay_inside():
    _x, codes, y = _table(N)
    masks = _masks(N)
    n_sub, trees = _fit(codes, y, masks, "sqrt", 3)
    f = codes.shape[1]
    assert n_sub == 5 < f
    ref = reference_forest(codes, y, masks[0], num_trees=3, n_sub=n_sub,
                           min_instances=5.0, min_info_gain=0.0005, seed=17)
    sf = np.asarray(trees.split_feat)[0]
    siblings = 0
    for t, (_kb, k_cols) in enumerate(tree_keys(17, 3)):
        for level in range(DEPTH):
            for node in np.nonzero(sf[t, level] >= 0)[0]:
                j = (1 << level) + int(node)
                assert sf[t, level, node] in node_subset(k_cols, j, f, n_sub)
        for j, subset in ref["drawn"][t].items():
            if j % 2 == 0 and j + 1 in ref["drawn"][t]:
                siblings += 1
                assert subset != ref["drawn"][t][j + 1]
        # the draw belongs to the tree too
        assert set(node_subset(k_cols, 1, f, n_sub)) != set(
            node_subset(tree_keys(17, 3)[(t + 1) % 3][1], 1, f, n_sub))
    assert siblings >= 3


@pytest.mark.parametrize("variant", ["one_lane", "four_lanes", "chunk_32",
                                     "chunk_256", "grouped"])
def test_a_node_draws_the_same_subset_whatever_builds_it(variant, monkeypatch):
    """Lane count, chunk width, width rung and column grouping change where
    a node's histogram is built, not its subset: lane 0's forest is the
    same forest."""
    n, depth = 4096, 8
    x, codes, y = _table(n, seed=5)
    base_masks = np.ones((2, n), F32)
    kw = dict(max_depth=depth, hist_impl=None,
              min_instances=1.0, min_info_gain=0.0)
    kw.pop("hist_impl")
    _, base = _fit(codes, y, base_masks, "sqrt", 2, **kw)
    want = _lane(base, 0)
    assert (want["split_feat"][:, depth - 1] >= 0).sum() > 32, "deep and wide"
    masks = base_masks
    groups = None
    if variant == "one_lane":
        masks = base_masks[:1]
    elif variant == "four_lanes":
        masks = np.concatenate([base_masks, _masks(n)])
    elif variant == "grouped":
        groups = _groups(x)
    else:
        monkeypatch.setattr(TR, "_resolved_impl", lambda: "gemm")
        monkeypatch.setattr(HP, "_GEMM_CHUNK_CEIL", int(variant.split("_")[1]))
    _, got = _fit(codes, y, masks, "sqrt", 2, groups=groups, **kw)
    _assert_same(_lane(got, 0), want)


def test_chunked_levels_count_their_chunks(monkeypatch):
    """At 32-slot chunks a level with more live nodes runs several chunks
    and skips the empty tail; the counts say so."""
    n, depth = 4096, 8
    _x, codes, y = _table(n, seed=5)
    monkeypatch.setattr(TR, "_resolved_impl", lambda: "gemm")
    monkeypatch.setattr(HP, "_GEMM_CHUNK_CEIL", 32)
    n_sub = G.resolve_feature_subset("sqrt", codes.shape[1], 2, True)
    _trees, slots = TR.fit_forest_batched(
        jnp.asarray(codes), y, np.ones((1, n), F32), num_trees=2,
        max_depth=depth, num_bins=BINS, min_instances=1.0, seed=17,
        feature_subset=n_sub, info_gain_norm=4.0, return_slots=True)
    s = {k: np.asarray(v) for k, v in slots._asdict().items()}
    assert (s["built"] == 32 * s["chunks_run"]).all()
    assert s["chunks_run"].max() > 1 and s["chunks_skipped"].max() >= 1
    # a chunk's 32 slots hold sibling PAIRS: 4 chunks serve the 256 nodes
    assert ((s["chunks_run"] + s["chunks_skipped"])[s["built"] > 0] == 4).all()
    nodes = s["nodes_built"] + s["nodes_derived"]  # one lane: its live nodes
    assert (s["live"][:, 1:] * 2 == nodes[:, 1:]).all()
    np.testing.assert_array_equal(s["subset_pairs"], nodes * codes.shape[1])
    np.testing.assert_array_equal(s["subset_admitted"], nodes * n_sub)


def test_sharded_forest_draws_the_same_subsets():
    if len(jax.devices()) < 8:
        pytest.skip("needs the forced 8-device CPU mesh")
    from transmogrifai_tpu.parallel import make_mesh

    mesh = make_mesh(n_data=8, n_model=1)
    _x, codes, y = _table(1000, seed=7)
    masks = _masks(1000)
    _, single = _fit(codes, y, masks, "sqrt", 3)
    _, sharded = _fit(codes, y, masks, "sqrt", 3, mesh=mesh)
    for k in range(2):
        _assert_same(_lane(sharded, k), _lane(single, k))


@pytest.mark.parametrize("family", ["classifier", "regressor"])
def test_sequential_and_batched_fits_grow_the_same_forests(family):
    x, _codes, y = _table(1500, seed=11)
    if family == "regressor":
        est = G.RandomForestRegressor(num_trees=3, max_depth=4, max_bins=BINS)
        y = (y + x[:, 0]).astype(np.float64)
    else:
        est = G.RandomForestClassifier(num_trees=3, max_depth=4,
                                       max_bins=BINS)
        y = y.astype(np.float64)
    masks = [m for m in _masks(1500)]
    points = [{"min_info_gain": 0.001, "min_instances_per_node": 10},
              {"min_info_gain": 0.01, "min_instances_per_node": 50}]
    batched = est.fit_arrays_batched_masks(x, y, masks, points)
    for mi, mask in enumerate(masks):
        for pi, point in enumerate(points):
            seq = est.with_params(**point).fit_arrays(x, y, mask)
            a, b = batched[mi][pi].get_arrays(), seq.get_arrays()
            assert set(a) == set(b)
            for key in a:
                np.testing.assert_array_equal(
                    np.nan_to_num(np.asarray(a[key])),
                    np.nan_to_num(np.asarray(b[key])), err_msg=key)


def test_min_info_gain_is_per_row_and_bites_on_4096_rows():
    """Under the un-normalised rule 0.001 and 0.1 grew the same tree (a
    root gain is in the hundreds); per row, 0.1 stops at the root or just
    under it and 0.001 grows on."""
    x, _codes, y = _table(4096, seed=13)
    sizes = {}
    for mig in (0.001, 0.1):
        model = G.RandomForestClassifier(
            num_trees=1, max_depth=DEPTH, min_info_gain=mig,
            min_instances_per_node=10, max_bins=BINS,
        ).fit_arrays(x, y.astype(np.float64), np.ones(4096, F32))
        sizes[mig] = int((model.get_arrays()["c0__split_feat"] >= 0).sum())
    assert sizes[0.1] <= 2 < 10 <= sizes[0.001], sizes


@pytest.mark.parametrize("est,stops_all", [
    # Spark's boosting (PR 32): targets +-1, then |4y/(1+exp(2yF))| < 4: the
    # variance of a node's targets is under 4.5 in both rounds
    (G.GBTClassifier(max_iter=2, max_depth=4, max_bins=BINS), 4.5),
    (G.DecisionTreeClassifier(max_depth=4, max_bins=BINS), 0.5),
    (G.DecisionTreeRegressor(max_depth=4, max_bins=BINS), 0.5),
], ids=lambda e: type(e).__name__ if not isinstance(e, float) else "")
def test_the_per_row_rule_reaches_gbt_and_the_decision_trees(est, stops_all):
    x, _codes, y = _table(4096, seed=13)
    ones = np.ones(4096, F32)

    def splits(mig):
        arrays = est.with_params(min_info_gain=mig).fit_arrays(
            x, y.astype(np.float64), ones).get_arrays()
        return sum(int((np.asarray(v) >= 0).sum())
                   for k, v in arrays.items() if k.endswith("split_feat"))

    # as an absolute threshold this would stop nothing at 4,096 rows (a
    # root gain is in the hundreds)
    assert splits(stops_all) == 0 < splits(0.0)


def test_one_tree_is_the_decision_tree():
    """``auto`` at one tree is every column and no bootstrap: the forest of
    one tree IS the decision tree."""
    x, _codes, y = _table(1500, seed=11)
    ones = np.ones(1500, F32)
    kw = dict(max_depth=5, min_instances_per_node=5, min_info_gain=0.001,
              max_bins=BINS)
    tree = G.DecisionTreeClassifier(**kw).fit_arrays(x, y, ones).get_arrays()
    rf = G.RandomForestClassifier(num_trees=1, **kw).fit_arrays(
        x, y, ones).get_arrays()
    for key in tree:
        np.testing.assert_array_equal(np.nan_to_num(tree[key]),
                                      np.nan_to_num(rf[key]), err_msg=key)


@pytest.mark.parametrize("strategy,f,trees,classification,want", [
    ("auto", 357, 1, True, 357), ("auto", 357, 50, True, 19),
    ("auto", 357, 2, False, 119), ("auto", 10, 1, False, 10),
    ("sqrt", 357, 1, True, 19), ("sqrt", 16, 3, True, 4),
    ("onethird", 357, 2, True, 119), ("onethird", 10, 2, True, 4),
    ("log2", 357, 2, True, 9), ("log2", 1, 2, True, 1),
    ("all", 357, 50, True, 357), ("SQRT", 24, 2, True, 5),
])
def test_feature_subset_strategy_resolves_as_sparks(
    strategy, f, trees, classification, want
):
    assert G.resolve_feature_subset(strategy, f, trees, classification) == want


def test_unknown_strategy_is_refused():
    with pytest.raises(ValueError, match="feature_subset_strategy"):
        G.resolve_feature_subset("half", 10, 2, True)


def test_forest_params_round_trip_with_the_strategy():
    est = G.RandomForestClassifier(feature_subset_strategy="log2")
    again = G.RandomForestClassifier(**est.get_params())
    assert again.get_params() == est.get_params()
    assert "feature_subset_strategy" in G.RandomForestClassifier._STATIC_GRID_KEYS


# ---- XGBoost's trees are the parent commit's: pinned from 7654763 by the
# fit below (its stop rule is absolute, it draws no subsets)
FEAT = [[[[0, -1, -1, -1, -1, -1, -1, -1], [2, 0, -1, -1, -1, -1, -1, -1], [7, 0, 2, 2, -1, -1, -1,
    -1]], [[0, -1, -1, -1, -1, -1, -1, -1], [0, 0, -1, -1, -1, -1, -1, -1], [2, 2, 2, 2, -1, -1,
    -1, -1]]], [[[0, -1, -1, -1, -1, -1, -1, -1], [0, 0, -1, -1, -1, -1, -1, -1], [2, 2, 2, 2,
    -1, -1, -1, -1]], [[0, -1, -1, -1, -1, -1, -1, -1], [0, 0, -1, -1, -1, -1, -1, -1], [2, 2,
    2, 2, -1, -1, -1, -1]]]]
BIN = [[[[3, 0, 0, 0, 0, 0, 0, 0], [1, 4, 0, 0, 0, 0, 0, 0], [0, 1, 4, 6, 0, 0, 0, 0]], [[2, 0, 0, 0,
    0, 0, 0, 0], [1, 4, 0, 0, 0, 0, 0, 0], [0, 1, 2, 6, 0, 0, 0, 0]]], [[[3, 0, 0, 0, 0, 0, 0,
    0], [1, 4, 0, 0, 0, 0, 0, 0], [1, 1, 2, 6, 0, 0, 0, 0]], [[3, 0, 0, 0, 0, 0, 0, 0], [1, 5,
    0, 0, 0, 0, 0, 0], [1, 2, 2, 5, 0, 0, 0, 0]]]]
LEAF = ['-0x1.bbbbbc0000000p-1', '0x1.4c1bac0000000p+0', '-0x1.dea51c0000000p+0',
    '-0x1.02b6ba0000000p+0', '0x1.def7be0000000p-1', '-0x1.af286c0000000p-2',
    '0x1.9a659a0000000p+0', '0x1.0750760000000p-1', '-0x1.9461380000000p-1',
    '-0x1.6d2ede0000000p+0', '0x1.3565580000000p-1', '-0x1.fd30600000000p-1',
    '0x1.e166380000000p-1', '-0x1.8473a80000000p-2', '0x1.3385d40000000p+0',
    '0x1.7c163e0000000p-2', '-0x1.1000000000000p+0', '-0x1.d8fd900000000p+0',
    '0x1.81a98e0000000p-1', '-0x1.e18e880000000p-1', '0x1.2094f20000000p+0',
    '0x1.435e500000000p-3', '0x1.9ce73a0000000p+0', '0x1.5555560000000p-2',
    '-0x1.91b0240000000p-1', '-0x1.6b48460000000p+0', '0x1.6f54380000000p-2',
    '-0x1.9a33500000000p-1', '0x1.1961aa0000000p+0', '0x1.10f8be0000000p-2',
    '0x1.6928940000000p+0', '0x1.70200e0000000p-1']


def test_xgboost_trees_are_bit_identical_to_the_pinned_parent():
    rng = np.random.default_rng(20261001)
    n, f = 1536, 10
    x = rng.normal(size=(n, f)).astype(F32)
    x[:, 6:] = x[:, 6:] > 0.3
    y = ((x[:, 0] - x[:, 2] * x[:, 7] + 0.5 * rng.normal(size=n)) > 0
         ).astype(F32)
    thr = TR.quantile_thresholds(x, 8)
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
    masks = np.ones((2, n), F32)
    masks[1, ::3] = 0.0
    trees, _margin = TR.fit_boosted_batched(
        binned, y, masks, num_rounds=2, max_depth=3, num_bins=8, eta=0.3,
        reg_lambda=1.0, gamma=np.asarray([0.0, 0.8], F32),
        min_child_weight=np.asarray([1.0, 10.0], F32), min_info_gain=0.0)
    np.testing.assert_array_equal(np.asarray(trees.split_feat), FEAT)
    np.testing.assert_array_equal(np.asarray(trees.split_bin), BIN)
    leaves = np.asarray(trees.leaf_value, F32).ravel()
    assert [float(v).hex() for v in leaves] == LEAF
