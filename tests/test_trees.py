"""Histogram tree / forest / boosting tests (parity: XGBoost/RF/GBT/DT
classification + regression test suites)."""
import jax.numpy as jnp
import numpy as np
import pytest

import transmogrifai_tpu.types as T
from transmogrifai_tpu.dataset import Dataset
from transmogrifai_tpu.evaluators import (
    BinaryClassificationEvaluator,
    RegressionEvaluator,
)
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.models import (
    DecisionTreeClassifier,
    GBTRegressor,
    MLPClassifier,
    RandomForestClassifier,
    RandomForestRegressor,
    XGBoostClassifier,
    XGBoostRegressor,
)
from transmogrifai_tpu.models import trees as TR
from transmogrifai_tpu.types.columns import NumericColumn, VectorColumn


def _pred_ds(x, y):
    n = len(y)
    return Dataset.of({
        "label": NumericColumn(T.RealNN, np.asarray(y, dtype=np.float64),
                               np.ones(n, dtype=bool)),
        "vec": VectorColumn(T.OPVector, np.asarray(x, dtype=np.float32)),
    })


def _wire(est):
    lbl = FeatureBuilder.RealNN("label").as_response()
    vec = FeatureBuilder.OPVector("vec").as_predictor()
    return est.set_input(lbl, vec)


# ------------------------------ primitives ----------------------------------
def test_quantile_binning_roundtrip(rng):
    x = rng.normal(size=(1000, 3)).astype(np.float32)
    thr = TR.quantile_thresholds(x, max_bins=8)
    assert thr.shape == (3, 7)
    binned = np.asarray(TR.bin_data(jnp.asarray(x), jnp.asarray(thr)))
    assert binned.min() >= 0 and binned.max() <= 7
    # roughly uniform occupancy
    counts = np.bincount(binned[:, 0], minlength=8)
    assert counts.min() > 50


def test_grow_tree_single_split(rng):
    # one feature perfectly separates the target at a known threshold
    n = 512
    x = rng.uniform(-1, 1, size=(n, 2)).astype(np.float32)
    y = (x[:, 0] > 0.25).astype(np.float32)
    thr = TR.quantile_thresholds(x, max_bins=16)
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
    tree = TR.grow_tree(
        binned, jnp.asarray(-y), jnp.ones(n), jnp.ones(n), jnp.ones(2),
        max_depth=2, num_bins=16, reg_lambda=0.0,
    )
    feat0 = int(np.asarray(tree.split_feat)[0, 0])
    assert feat0 == 0  # must pick the separating feature at the root
    leaf = np.asarray(TR.predict_tree(binned, tree))
    acc = ((leaf > 0.5) == (y > 0.5)).mean()
    assert acc > 0.97


def test_grow_tree_no_split_when_pure(rng):
    n = 128
    x = rng.normal(size=(n, 2)).astype(np.float32)
    y = np.ones(n, dtype=np.float32)  # constant target -> no gain anywhere
    thr = TR.quantile_thresholds(x, max_bins=8)
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
    tree = TR.grow_tree(
        binned, jnp.asarray(-y), jnp.ones(n), jnp.ones(n), jnp.ones(2),
        max_depth=3, num_bins=8, reg_lambda=0.0, min_info_gain=1e-6,
    )
    assert (np.asarray(tree.split_feat)[0] == -1).all()
    np.testing.assert_allclose(
        np.asarray(TR.predict_tree(binned, tree)), 1.0, atol=1e-6
    )


def test_min_child_weight_blocks_tiny_splits(rng):
    n = 100
    x = rng.normal(size=(n, 1)).astype(np.float32)
    y = np.zeros(n, dtype=np.float32)
    y[x[:, 0].argmax()] = 1.0  # a single positive outlier
    thr = TR.quantile_thresholds(x, max_bins=32)
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
    tree = TR.grow_tree(
        binned, jnp.asarray(-y), jnp.ones(n), jnp.ones(n), jnp.ones(1),
        max_depth=1, num_bins=32, reg_lambda=0.0, min_child_weight=60.0,
    )
    # both children must carry >= 60 of 100 rows: impossible -> leaf
    assert int(np.asarray(tree.split_feat)[0, 0]) == -1
    # sanity: with a permissive threshold the same data does split
    tree2 = TR.grow_tree(
        binned, jnp.asarray(-y), jnp.ones(n), jnp.ones(n), jnp.ones(1),
        max_depth=1, num_bins=32, reg_lambda=0.0, min_child_weight=1.0,
    )
    assert int(np.asarray(tree2.split_feat)[0, 0]) == 0


# ------------------------------- ensembles ----------------------------------
@pytest.fixture
def circles(rng):
    """Nonlinear binary problem trees should crack and linear models can't."""
    n = 1200
    x = rng.uniform(-1, 1, size=(n, 2)).astype(np.float32)
    y = ((x[:, 0] ** 2 + x[:, 1] ** 2) < 0.4).astype(np.float32)
    return x, y


def test_xgboost_classifier_nonlinear(circles):
    x, y = circles
    model = _wire(XGBoostClassifier(num_round=30, max_depth=3)).fit(_pred_ds(x, y))
    pred, prob, raw = model.predict_arrays(x)
    assert (pred == y).mean() > 0.93
    m = BinaryClassificationEvaluator().evaluate_arrays(y, pred, prob)
    assert m["AuROC"] > 0.97


def test_random_forest_classifier_nonlinear(circles):
    x, y = circles
    model = _wire(
        RandomForestClassifier(num_trees=30, max_depth=6, seed=5)
    ).fit(_pred_ds(x, y))
    pred, prob, _ = model.predict_arrays(x)
    assert (pred == y).mean() > 0.9
    assert prob.shape == (len(y), 2)
    np.testing.assert_allclose(prob.sum(axis=1), 1.0, atol=1e-9)


def test_decision_tree_classifier(circles):
    x, y = circles
    model = _wire(DecisionTreeClassifier(max_depth=6)).fit(_pred_ds(x, y))
    pred, prob, _ = model.predict_arrays(x)
    assert (pred == y).mean() > 0.85


def test_xgboost_regressor_friedman(rng):
    n = 2000
    x = rng.uniform(size=(n, 5)).astype(np.float32)
    y = (
        10 * np.sin(np.pi * x[:, 0] * x[:, 1])
        + 20 * (x[:, 2] - 0.5) ** 2
        + 10 * x[:, 3]
        + 5 * x[:, 4]
    ).astype(np.float32)
    model = _wire(XGBoostRegressor(num_round=50, max_depth=4)).fit(_pred_ds(x, y))
    pred, _, _ = model.predict_arrays(x)
    r2 = RegressionEvaluator().evaluate_arrays(y, pred, None)["R2"]
    assert r2 > 0.9


def test_gbt_and_rf_regressors(rng):
    n = 1000
    x = rng.normal(size=(n, 3)).astype(np.float32)
    y = (np.abs(x[:, 0]) + x[:, 1] ** 2).astype(np.float32)
    for est in (GBTRegressor(max_iter=30, max_depth=4),
                RandomForestRegressor(num_trees=30, max_depth=6)):
        model = _wire(est).fit(_pred_ds(x, y))
        pred, _, _ = model.predict_arrays(x)
        r2 = RegressionEvaluator().evaluate_arrays(y, pred, None)["R2"]
        assert r2 > 0.7, type(est).__name__


def test_xgboost_multiclass(rng):
    n = 900
    y = rng.integers(0, 3, n)
    centers = np.array([[2.0, 0], [-2, 1], [0, -2]])
    x = (centers[y] + rng.normal(size=(n, 2)) * 0.4).astype(np.float32)
    model = _wire(XGBoostClassifier(num_round=20, max_depth=3)).fit(
        _pred_ds(x, y.astype(float))
    )
    pred, prob, _ = model.predict_arrays(x)
    assert (pred == y).mean() > 0.9
    assert prob.shape == (n, 3)


def test_row_mask_respected_by_trees(rng):
    n = 600
    x = rng.normal(size=(n, 2)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    y_corrupt = y.copy()
    y_corrupt[300:] = 1 - y[300:]  # adversarial labels outside the mask
    mask = np.zeros(n, dtype=np.float32)
    mask[:300] = 1.0
    model = _wire(XGBoostClassifier(num_round=10, max_depth=3)).fit_arrays(
        x, y_corrupt, mask
    )
    pred, _, _ = model.predict_arrays(x[:300])
    assert (pred == y[:300]).mean() > 0.95


# --------------------------------- MLP --------------------------------------
def test_mlp_classifier_nonlinear(circles):
    x, y = circles
    model = _wire(MLPClassifier(hidden_layers=(16, 16), max_iter=400)).fit(
        _pred_ds(x, y)
    )
    pred, prob, _ = model.predict_arrays(x)
    assert (pred == y).mean() > 0.9
    np.testing.assert_allclose(prob.sum(axis=1), 1.0, atol=1e-6)


@pytest.mark.slow
class TestBatchedGridFits:
    """fit_arrays_batched folds same-static-shape grid points into one
    vmapped program (the validator's sweep hook, validators.py:102)."""

    def _data(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(200, 8)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.float64)
        yr = (x[:, 0] * 2 + rng.normal(0, 0.1, 200)).astype(np.float64)
        return x, y, yr, np.ones(200, np.float32)

    def test_batched_matches_sequential(self):
        from transmogrifai_tpu.models.gbdt import (
            GBTClassifier,
            RandomForestClassifier,
            XGBoostClassifier,
            XGBoostRegressor,
        )

        x, y, yr, mask = self._data()
        cases = [
            (XGBoostClassifier(num_round=5),
             [{"eta": 0.1, "min_child_weight": 1.0},
              {"eta": 0.3, "min_child_weight": 5.0}], y),
            (GBTClassifier(max_iter=4),
             [{"step_size": 0.1, "min_instances_per_node": 1},
              {"step_size": 0.2, "min_instances_per_node": 5}], y),
            (RandomForestClassifier(num_trees=4),
             [{"min_info_gain": 0.0}, {"min_info_gain": 0.01}], y),
            (XGBoostRegressor(num_round=5),
             [{"eta": 0.1}, {"eta": 0.3}], yr),
        ]
        for est, points, yy in cases:
            batched = est.fit_arrays_batched(x, yy, mask, points)
            for b, p in zip(batched, points):
                s = est.with_params(**p).fit_arrays(x, yy, mask)
                pb, _, _ = b.predict_arrays(x)
                ps, _, _ = s.predict_arrays(x)
                np.testing.assert_allclose(
                    np.asarray(pb), np.asarray(ps), atol=1e-4,
                    err_msg=f"{type(est).__name__} {p}",
                )

    def test_mixed_static_groups(self):
        """Points with different max_depth split into separate groups."""
        from transmogrifai_tpu.models.gbdt import RandomForestClassifier

        x, y, _, mask = self._data()
        est = RandomForestClassifier(num_trees=3)
        points = [
            {"max_depth": 3, "min_info_gain": 0.0},
            {"max_depth": 3, "min_info_gain": 0.01},
            {"max_depth": 5, "min_info_gain": 0.0},
        ]
        models = est.fit_arrays_batched(x, y, mask, points)
        assert len(models) == 3
        for m, p in zip(models, points):
            s = est.with_params(**p).fit_arrays(x, y, mask)
            pm, _, _ = m.predict_arrays(x)
            ps, _, _ = s.predict_arrays(x)
            np.testing.assert_allclose(np.asarray(pm), np.asarray(ps), atol=1e-4)


@pytest.mark.slow
def test_fori_chunk_path_matches_unrolled(rng):
    """Large chunk counts take a shared fori body (program-size bound);
    results must match the small-count Python-unrolled branch exactly."""
    import transmogrifai_tpu.models.trees as TR
    import jax.numpy as jnp

    n, f, b, depth, k_fits = 600, 2000, 32, 9, 32
    binned = jnp.asarray(rng.integers(0, b, (n, f)).astype(np.int32))
    g1 = -rng.normal(size=n).astype(np.float32)
    g = np.tile(g1[None, :], (k_fits, 1))
    ones = np.ones((k_fits, n), np.float32)
    tK = TR.grow_tree_batched(
        binned, jnp.asarray(g), jnp.asarray(ones), jnp.asarray(ones),
        jnp.asarray(np.ones((k_fits, f), np.float32)),
        max_depth=depth, num_bins=b,
    )  # K=32 shrinks the chunk budget below 8 chunks -> fori branch
    t1 = TR.grow_tree(
        binned, jnp.asarray(g1), jnp.ones(n), jnp.ones(n), jnp.ones(f),
        max_depth=depth, num_bins=b,
    )  # K=1 -> Python-unrolled branch
    for name in ("split_feat", "split_bin"):
        arr = np.asarray(getattr(tK, name))
        ref = np.asarray(getattr(t1, name))
        np.testing.assert_array_equal(arr[0], ref, err_msg=name)
        np.testing.assert_array_equal(arr[-1], ref, err_msg=name)
    leaf = np.asarray(tK.leaf_value)
    leaf_ref = np.asarray(t1.leaf_value)
    np.testing.assert_allclose(leaf[0], leaf_ref, atol=1e-4, err_msg="leaf")
    np.testing.assert_allclose(leaf[-1], leaf_ref, atol=1e-4, err_msg="leaf")


# ------------------------------ grower functions ----------------------------
def _record(n=16, f=3, k=1, classes=0, **kw):
    """A ``trees._Grow`` of a small fit (its arrays are never grown on)."""
    grad = np.zeros((k, max(classes - 1, 1), n) if classes > 2 else (k, n),
                    np.float32)
    ones = np.ones((k, n), np.float32)
    return TR._grow_record(
        jnp.zeros((n, f), jnp.int32), jnp.asarray(grad), jnp.asarray(ones),
        jnp.asarray(ones), jnp.ones((k, f)), max_depth=kw.pop("depth", 3),
        num_bins=kw.pop("num_bins", 8), hist_impl="scatter", **kw,
    )


@pytest.mark.parametrize("c0, width, pairs, want", [
    # every node built: the rows of nodes [c0, c0 + width), from 0
    (0, 4, False, [0, 1, 2, 3, -1, -1, -1, -1]),
    (4, 4, False, [-1, -1, -1, -1, 0, 1, 2, 3]),
    # sibling pairs: nodes 2j, 2j + 1 are pair j, numbered from c0 / 2,
    # for the rows of the child the level above chose (odd rows here)
    (0, 8, True, [-1, 0, -1, 1, -1, 2, -1, 3]),
    (4, 4, True, [-1, -1, -1, -1, -1, 0, -1, 1]),
])
def test_build_slots_numbers_nodes_or_sibling_pairs(c0, width, pairs, want):
    local = jnp.arange(8, dtype=jnp.int32)[None, :]  # row r in node r
    sib = None
    if pairs:
        sib = TR._Siblings(
            takes_part=(jnp.arange(8) % 2 == 1)[None, :], n_pairs=None,
            parent_slot=None, built_right=None, hists=(),
        )
    got = TR._build_slots(local, c0, width, sib)
    np.testing.assert_array_equal(np.asarray(got)[0], want)


@pytest.mark.parametrize("feats, right, n_pairs, parent_slot, built_right", [
    # slots 0, 2 and 3 split: pairs 0, 1, 2 hang from them
    ([3, -1, 0, 2, -1, -1, -1, -1], [1, 0, 0, 1, 1, 0, 0, 0], 3,
     [0, 2, 3, 0], [True, False, True, False]),
    # no split: no pair
    ([-1] * 8, [1] * 8, 0, [0, 0, 0, 0], [False] * 4),
    # every kept slot splits
    ([1, 1, 1, 1, -1, -1, -1, -1], [0, 1, 0, 1, 0, 0, 0, 0], 4,
     [0, 1, 2, 3], [False, True, False, True]),
])
def test_next_siblings_from_a_split_table(feats, right, n_pairs, parent_slot,
                                          built_right):
    rec = _record()._replace(keep=4, pair_slots=4)
    takes = jnp.ones((1, 16), bool)
    sib = TR._next_siblings(
        rec, jnp.asarray([feats], jnp.int32), jnp.asarray([right], bool),
        takes, ("kept",),
    )
    assert int(sib.n_pairs[0]) == n_pairs
    np.testing.assert_array_equal(np.asarray(sib.parent_slot)[0], parent_slot)
    np.testing.assert_array_equal(np.asarray(sib.built_right)[0], built_right)
    assert sib.takes_part is takes and sib.hists == ("kept",)


@pytest.mark.parametrize("narrow, wide", [([1], [0]), ([0], [1])])
def test_chunk_stats_breaks_a_cross_group_tie_by_feature_id(narrow, wide):
    """Columns 0 and 1 part the rows alike, one in each feature group: the
    gains tie, and the lower original id wins whichever group is merged
    first (the narrow one)."""
    n = 16
    half = np.arange(n) >= n // 2
    codes = np.zeros((n, 2), np.int32)
    codes[:, wide[0]] = np.where(half, 5, 2)
    codes[:, narrow[0]] = half  # the narrow group reads code > 0
    y = np.where(half, 1.0, -1.0).astype(np.float32)
    ones = jnp.ones((1, n))
    rec = TR._grow_record(
        jnp.asarray(codes), jnp.asarray(-y[None, :]), ones, ones,
        jnp.ones((1, 2)), max_depth=1, num_bins=8, hist_impl="scatter",
        min_child_weight=1.0, reg_lambda=0.0,
        feature_groups=(jnp.asarray(narrow), jnp.asarray(wide)),
    )
    assert [grp[3].tolist() for grp in rec.groups] == [narrow, wide]
    live = jnp.zeros((1, rec.max_nodes), bool).at[0, 0].set(True)
    local = jnp.zeros((1, n), jnp.int32)
    lv = TR._Level(live, jnp.zeros((1, rec.max_nodes), jnp.int32),
                   jnp.int32(0), local, (), (jnp.int32(0), jnp.int32(0)))
    loc = TR._build_slots(local, 0, rec.chunk_nodes, None)
    feats, bins, _, counts, _ = TR._chunk_stats(
        rec, lv, loc, 0, rec.chunk_nodes, None, ()
    )
    assert int(feats[0, 0]) == 0
    # the wide column's split bin sits between its two codes
    assert int(bins[0, 0]) == (0 if narrow == [0] else 2)
    assert int(counts.lane_builds) == 2


@pytest.mark.parametrize("left, right", [
    ((4, 1, 0), (0, 2, 5)),   # class counts (c0, c1, c2) of each child
    ((3, 3, 3), (1, 0, 0)),
    ((0, 5, 1), (2, 0, 6)),
])
def test_best_split_gini_adds_class_0_term(left, right):
    """Under ``GINI`` the gain is half the weighted Gini decrease over all
    three classes, class 0's (W less the others) included."""
    rec = _record(classes=3, info_gain_norm=TR.GINI, reg_lambda=0.0,
                  min_child_weight=0.0)
    assert rec.n_values == 2
    # bins 0 (left) and 1 (right): the negated class-1, class-2 indicator
    # sums, then the weight
    hist = jnp.asarray([[[[
        [-left[1], -left[2], sum(left)], [-right[1], -right[2], sum(right)],
    ]]]], jnp.float32)
    gain = TR._best_split(
        rec, hist, jnp.ones((1, 1)), None, 2, None, 1
    )[0]
    parent = np.add(left, right)

    def sq(counts):
        return sum(c * c for c in counts) / sum(counts)

    decrease = sq(left) + sq(right) - sq(parent)
    np.testing.assert_allclose(float(gain[0, 0]), 0.5 * decrease, rtol=1e-6)
    without_class_0 = (
        sq(left[1:]) + sq(right[1:]) - sq(parent[1:])
        if sum(left[1:]) and sum(right[1:]) else None
    )
    assert without_class_0 is None or not np.isclose(decrease, without_class_0)
