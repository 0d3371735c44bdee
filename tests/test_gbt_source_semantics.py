"""Gradient-boosted trees as the source defines them (Spark ML 2.4.5
``GradientBoostedTrees.boost`` under TransmogrifAI's ``OpGBTClassifier`` /
``OpGBTRegressor``): first-order regression trees with the variance gain,
the first fitted to the labels (2y - 1 for the classifier) at weight 1, each
later one to the loss's negative gradient at weight ``step_size``; a child
under ``min_instances_per_node`` ROWS makes a split invalid; a node splits
where its best valid split's variance decrease per row reaches
``min_info_gain`` and is positive; P(y = 1) = sigma(2F). The program's trees
are compared node for node with a plain numpy reference of those equations
(``benchmarks/configs/flagship_gbt.json`` states the same under ``learner``).
XGBoost's and the forest's trees are pinned from the parent commit."""
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.models import gbdt as G
from transmogrifai_tpu.models import trees as TR

BINS = 16
F32 = np.float32
STEP = 0.1


def _table(n, seed=3, f_wide=8, f_narrow=6):
    """(x, codes [N, F], y in {0, 1}): wide real columns and 0/1 columns."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f_wide + f_narrow)).astype(F32)
    x[:, f_wide:] = x[:, f_wide:] > 0.4
    logit = (x[:, 0] + x[:, 1] * x[:, 2] - x[:, f_wide] + x[:, f_wide + 3]
             + 0.5 * np.sin(3 * x[:, 4]) + 0.6 * rng.normal(size=n))
    y = (logit > 0.2).astype(F32)
    thr = TR.quantile_thresholds(x, BINS)
    codes = np.asarray(TR.bin_data(jnp.asarray(x), jnp.asarray(thr)))
    return x, codes, y


def targets(loss, ys, margin, r):
    """The round's regression targets, float32 as the program holds them."""
    if r == 0:
        return ys
    if loss == "logloss":
        return (F32(4.0) * ys / (F32(1.0) + np.exp(F32(2.0) * ys * margin))
                ).astype(F32)
    return (F32(2.0) * (ys - margin)).astype(F32)


def node_gains(codes, rows, t, min_instances, bins=BINS):
    """[F, bins-1] float64 variance decrease per row of every split of the
    node holding ``rows`` (-inf where a child has under ``min_instances``
    rows), and the node's (S, n)."""
    tt = t[rows].astype(np.float64)
    s, n = tt.sum(), float(rows.sum())
    out = np.full((codes.shape[1], bins - 1), -np.inf)
    for col in range(codes.shape[1]):
        c = codes[rows, col]
        sl = np.cumsum(np.bincount(c, tt, bins))[:-1]
        nl = np.cumsum(np.bincount(c, None, bins))[:-1].astype(np.float64)
        sr, nr = s - sl, n - nl
        with np.errstate(all="ignore"):
            gain = (sl * sl / nl + sr * sr / nr - s * s / n) / n
        ok = (nl >= min_instances) & (nr >= min_instances)
        out[col] = np.where(ok, gain, -np.inf)
    return out, s, n


def reference_gbt(codes, y, mask, *, rounds, depth, min_instances,
                  min_info_gain, loss="logloss", step=STEP):
    """Plain per-node growth by the module docstring's equations. Rows
    outside ``mask`` count nowhere and are routed like the others. Returns
    split_feat / split_bin [R, depth, 2^depth], leaf_value [R, 2^depth],
    the weights [R] and the final margin F [N]."""
    n, _f = codes.shape
    ys = (F32(2.0) * y - F32(1.0)).astype(F32) if loss == "logloss" else y
    counted = mask > 0
    margin = np.zeros(n, F32)
    feats = np.full((rounds, depth, 1 << depth), -1, np.int32)
    sbins = np.zeros((rounds, depth, 1 << depth), np.int32)
    leaves = np.zeros((rounds, 1 << depth), F32)
    weights = np.asarray([1.0] + [step] * (rounds - 1), F32)
    for r in range(rounds):
        t = targets(loss, ys, margin, r)
        heap = np.ones(n, np.int64)
        active = np.ones(n, bool)
        for level in range(depth):
            for j in np.unique(heap[active & counted]):
                rows = active & counted & (heap == j)
                gains, _s, _n = node_gains(codes, rows, t, min_instances)
                col, b = np.unravel_index(np.argmax(gains), gains.shape)
                best = gains[col, b]  # argmax: lowest column, then bin
                if not (best > 0 and best >= min_info_gain):
                    active &= heap != j
                    continue
                feats[r, level, j - (1 << level)] = col
                sbins[r, level, j - (1 << level)] = b
            node = heap - (1 << level)
            took = active & (feats[r, level][node] >= 0)
            right = took & (
                codes[np.arange(n), np.maximum(feats[r, level][node], 0)]
                > sbins[r, level][node])
            heap = heap * 2 + right
            active = took
        node = heap - (1 << depth)
        cnt = np.bincount(node[counted], None, 1 << depth)
        tot = np.bincount(node[counted], t[counted].astype(np.float64),
                          1 << depth)
        with np.errstate(all="ignore"):
            leaves[r] = np.where(cnt > 0, tot / cnt, 0.0).astype(F32)
        margin = (margin + weights[r] * leaves[r][node]).astype(F32)
    return {"split_feat": feats, "split_bin": sbins, "leaf_value": leaves,
            "tree_weights": weights, "margin": margin}


def _assert_same_trees(got: dict, want: dict, rtol=2e-5):
    np.testing.assert_array_equal(got["split_feat"], want["split_feat"])
    np.testing.assert_array_equal(got["split_bin"], want["split_bin"])
    np.testing.assert_allclose(
        np.nan_to_num(got["leaf_value"]), want["leaf_value"],
        rtol=rtol, atol=1e-6)


def _fit_lane(codes, y, mask, fgroups, *, rounds, depth, min_instances,
              min_info_gain, objective="spark:logloss"):
    trees, margin = TR.fit_boosted(
        jnp.asarray(codes), jnp.asarray(y), jnp.asarray(mask),
        num_rounds=rounds, max_depth=depth, num_bins=BINS, eta=STEP,
        reg_lambda=0.0, gamma=0.0, min_child_weight=float(min_instances),
        min_info_gain=min_info_gain, objective=objective,
        feature_groups=fgroups, info_gain_norm=2.0)
    return {f: np.asarray(getattr(trees, f)) for f in TR.Tree._fields}, (
        np.asarray(margin))


# ------------------------------------------------ node for node, the margin
@pytest.mark.parametrize("grouped", [False, True], ids=["plain", "grouped"])
@pytest.mark.parametrize("depth", [2, 4])
@pytest.mark.parametrize("rounds", [1, 2, 3])
def test_trees_equal_the_reference_node_for_node(rounds, depth, grouped):
    x, codes, y = _table(700, seed=5)
    mask = (np.random.default_rng(1).random(700) < 0.8).astype(F32)
    kw = dict(rounds=rounds, depth=depth, min_instances=8,
              min_info_gain=0.002)
    want = reference_gbt(codes, y, mask, **kw)
    got, margin = _fit_lane(
        codes, y, mask, G._feature_bin_groups(x) if grouped else None, **kw)
    _assert_same_trees(got, want)
    # the margin the fit carries from tree to tree, on EVERY row
    np.testing.assert_allclose(margin, want["margin"], rtol=2e-5, atol=2e-6)
    assert int((want["split_feat"] >= 0).sum()) >= rounds * (depth - 1)


@pytest.mark.parametrize("depth", [2, 4])
def test_regressor_trees_equal_the_reference(depth):
    """Spark's GBTRegressor: the first tree on the labels at weight 1 from
    F = 0, then targets 2 (y - F) at ``step_size``."""
    x, codes, _ = _table(600, seed=9)
    y = (np.abs(x[:, 0]) + x[:, 1] * x[:, 8] + 0.1 * x[:, 3]).astype(F32)
    ones = np.ones(600, F32)
    kw = dict(rounds=3, depth=depth, min_instances=5, min_info_gain=0.0)
    want = reference_gbt(codes, y, ones, loss="squared", **kw)
    model = G.GBTRegressor(
        max_iter=3, max_depth=depth, min_instances_per_node=5,
        step_size=STEP, max_bins=BINS).fit_arrays(x, y, ones)
    assert isinstance(model, G.GBTRegressionModel)
    arrays = model.get_arrays()
    _assert_same_trees(arrays, want)
    np.testing.assert_array_equal(arrays["tree_weights"],
                                  want["tree_weights"])
    pred, _, _ = model.predict_arrays(x)
    np.testing.assert_allclose(pred, want["margin"], rtol=2e-5, atol=2e-6)


# --------------------------------------------------------------- the rules
def test_first_tree_weighs_one_and_the_rest_step_size():
    x, codes, y = _table(500, seed=2)
    ones = np.ones(500, F32)
    model = G.GBTClassifier(
        max_iter=3, step_size=0.25, max_depth=3, max_bins=BINS,
        min_instances_per_node=5).fit_arrays(x, y, ones)
    arrays = model.get_arrays()
    np.testing.assert_array_equal(
        arrays["tree_weights"], np.asarray([1.0, 0.25, 0.25], F32))
    # the first tree is fitted to the labels 2y - 1 themselves: its leaves
    # are means of +-1, and the root's rows average 2 mean(y) - 1
    first = np.nan_to_num(arrays["leaf_value"][0])
    assert np.abs(first).max() <= 1.0
    # F = 1 * T_1 + 0.25 * T_2 + 0.25 * T_3 by the host traversal
    per_tree = np.stack([
        TR.predict_boosted_host(
            x, model.thresholds,
            TR.Tree(*(arrays[k][r:r + 1] for k in TR.Tree._fields)), 1.0, 0.0)
        for r in range(3)])
    _, _, raw = model.predict_arrays(x)
    np.testing.assert_allclose(
        raw[:, 1], per_tree[0] + 0.25 * (per_tree[1] + per_tree[2]),
        rtol=1e-6, atol=1e-7)
    assert TR.boost_tree_weights("binary:logistic", 3, 0.25).tolist() == [
        0.25] * 3


def _children_rows(codes, arrays, r, depth):
    """Rows in the two children of every node the round's tree split."""
    n = len(codes)
    node = np.zeros(n, np.int64)
    out = []
    for level in range(depth):
        sf, sb = arrays["split_feat"][r, level], arrays["split_bin"][r, level]
        took = sf[node] >= 0
        right = took & (codes[np.arange(n), np.maximum(sf[node], 0)]
                        > sb[node])
        for j in np.unique(node[took]):
            here = took & (node == j)
            out.append((int((here & ~right).sum()), int((here & right).sum())))
        node = node * 2 + right
    return out


def test_row_counts_not_hessians_stop_a_child():
    """``min_instances_per_node`` 40: a Newton learner compares it with a
    sum of p (1 - p) <= 1/4 a row and refuses every child under 160 rows."""
    x, codes, y = _table(800, seed=4)
    model = G.GBTClassifier(
        max_iter=2, max_depth=4, max_bins=BINS,
        min_instances_per_node=40).fit_arrays(x, y, np.ones(800, F32))
    arrays = model.get_arrays()
    kids = [c for r in range(2) for c in _children_rows(codes, arrays, r, 4)]
    assert kids and min(min(c) for c in kids) >= 40
    assert min(min(c) for c in kids) < 160


@pytest.mark.parametrize("mig", [0.0, 0.02, 0.2])
def test_min_info_gain_is_the_variance_decrease_per_row(mig):
    x, codes, y = _table(900, seed=6)
    ones = np.ones(900, F32)
    arrays = G.GBTClassifier(
        max_iter=2, max_depth=3, max_bins=BINS, min_instances_per_node=10,
        min_info_gain=mig).fit_arrays(x, y, ones).get_arrays()
    ys = (2 * y - 1).astype(F32)
    margin = np.zeros(900, F32)
    splits = short = 0
    for r in range(2):
        t = targets("logloss", ys, margin, r)
        node = np.zeros(900, np.int64)
        active = np.ones(900, bool)
        for level in range(3):
            sf = arrays["split_feat"][r, level]
            sb = arrays["split_bin"][r, level]
            for j in np.unique(node[active]):
                gains, _s, _n = node_gains(
                    codes, active & (node == j), t, 10)
                if sf[j] >= 0:
                    splits += 1
                    assert gains[sf[j], sb[j]] >= mig * (1 - 1e-4)
                    assert gains[sf[j], sb[j]] > 0
                else:
                    assert not gains.max() >= max(mig * (1 + 1e-4), 1e-9)
                    short += bool(gains.max() > 0)
            took = active & (sf[node] >= 0)
            right = took & (codes[np.arange(900), np.maximum(sf[node], 0)]
                            > sb[node])
            node, active = node * 2 + right, took
        margin = (margin + F32(1.0 if r == 0 else STEP)
                  * np.nan_to_num(arrays["leaf_value"][r])[node]).astype(F32)
    # both branches of the rule were met: nodes that split, and (above 0)
    # nodes whose best valid split fell short
    assert splits > 0
    assert (short > 0) == (mig == 0.2)


def test_more_than_two_classes_stay_one_vs_rest():
    """Spark's classifier is binary; this repo's one-vs-rest loop stays
    (sequential), each class a Spark GBT on its indicator."""
    x, _codes, _y = _table(300, seed=1)
    y3 = (np.arange(300) % 3).astype(np.float64)
    ones = np.ones(300, F32)
    est = G.GBTClassifier(max_iter=2, max_depth=2, max_bins=BINS)
    assert est.fit_arrays_batched_masks(x, y3, [ones], [{}])[0][0] is not None
    model = est.fit_arrays(x, y3, ones)
    assert isinstance(model, G.GBTMultiModel)
    one = G.GBTClassifier(max_iter=2, max_depth=2, max_bins=BINS).fit_arrays(
        x, (y3 == 1).astype(np.float64), ones)
    pred, prob, raw = model.predict_arrays(x)
    np.testing.assert_allclose(raw[:, 1], one.predict_arrays(x)[2][:, 1],
                               rtol=1e-6, atol=1e-7)
    p = 1.0 / (1.0 + np.exp(-2.0 * raw))
    np.testing.assert_allclose(prob, p / p.sum(axis=1, keepdims=True),
                               rtol=1e-6)
    again = G.GBTMultiModel.from_params(model.get_params(), model.get_arrays())
    np.testing.assert_array_equal(again.predict_arrays(x)[1], prob)


def test_round_one_targets_are_r_of_f_and_leaves_are_means():
    """Tree 0 is fitted to y+- itself; every leaf of tree 1 is the MEAN,
    over its rows, of r = 4 y+- / (1 + exp(2 y+- F)) at the margin tree 0
    left (weight 1.0): to 1e-6, and no Hessian anywhere."""
    x, codes, y = _table(2048, seed=21)
    ones = np.ones(2048, F32)
    arrays = G.GBTClassifier(
        max_iter=2, max_depth=3, max_bins=BINS, min_instances_per_node=10,
        min_info_gain=0.001).fit_arrays(x, y, ones).get_arrays()

    def leaf_of(r):
        node = np.zeros(2048, np.int64)
        for level in range(3):
            sf, sb = arrays["split_feat"][r, level], arrays["split_bin"][r, level]
            node = node * 2 + ((sf[node] >= 0) & (
                codes[np.arange(2048), np.maximum(sf[node], 0)] > sb[node]))
        return node

    ys = (2.0 * y - 1.0).astype(np.float64)
    leaf0, leaf1 = leaf_of(0), leaf_of(1)
    for j in np.unique(leaf0):  # tree 0: means of +-1
        assert arrays["leaf_value"][0][j] == pytest.approx(
            ys[leaf0 == j].mean(), abs=1e-6)
    f0 = 1.0 * arrays["leaf_value"][0][leaf0].astype(np.float64)
    r = 4.0 * ys / (1.0 + np.exp(2.0 * ys * f0))
    assert len(np.unique(leaf1)) >= 4
    for j in np.unique(leaf1):
        assert arrays["leaf_value"][1][j] == pytest.approx(
            r[leaf1 == j].mean(), abs=1e-6)
        # a Newton leaf of the same rows, -G/H with h = p(1 - p), is
        # another number
        p = 1.0 / (1.0 + np.exp(-f0[leaf1 == j]))
        newton = ((y[leaf1 == j] - p).sum() / (p * (1 - p)).sum())
        assert abs(newton - arrays["leaf_value"][1][j]) > 1e-3


# ------------------------------------------------- the three predict paths
@pytest.fixture(scope="module")
def fitted():
    x, codes, y = _table(640, seed=8)
    ones = np.ones(640, F32)
    kw = dict(rounds=3, depth=3, min_instances=6, min_info_gain=0.001)
    want = reference_gbt(codes, y, ones, **kw)
    model = G.GBTClassifier(
        max_iter=3, max_depth=3, max_bins=BINS, min_instances_per_node=6,
        min_info_gain=0.001).fit_arrays(x, y, ones)
    return x, model, want


def _check_predictions(pred, prob, raw, want):
    f = want["margin"].astype(np.float64)
    np.testing.assert_allclose(raw[:, 1], f, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(raw[:, 0], -raw[:, 1])
    np.testing.assert_allclose(
        prob[:, 1], 1.0 / (1.0 + np.exp(-2.0 * f)), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(prob.sum(axis=1), 1.0)
    np.testing.assert_array_equal(pred, (raw[:, 1] > 0).astype(np.float64))


def test_probability_is_sigma_2f_on_the_host_traversal(fitted):
    x, model, want = fitted
    assert model._use_host(x)
    _check_predictions(*model.predict_arrays(x), want)


def test_probability_is_sigma_2f_on_the_device_predict(fitted, monkeypatch):
    x, model, want = fitted
    monkeypatch.setenv("TPTPU_HOST_PREDICT_MAX", "0")
    assert not model._use_host(x)
    _check_predictions(*model.predict_arrays(x), want)


def test_probability_is_sigma_2f_in_the_fused_predict(fitted):
    x, model, want = fitted
    spec = model.fused_predict_spec()
    core = np.asarray(spec.core(
        jnp.asarray(x), jax.tree.map(jnp.asarray, spec.params)))
    _check_predictions(*spec.epilogue(core), want)


def test_model_round_trips_with_its_weights(fitted):
    x, model, _want = fitted
    again = G.GBTClassificationModel.from_params(
        model.get_params(), model.get_arrays())
    for a, b in zip(model.predict_arrays(x), again.predict_arrays(x)):
        np.testing.assert_array_equal(a, b)
    from transmogrifai_tpu.workflow import persistence

    assert persistence._registry()["GBTClassificationModel"] is (
        G.GBTClassificationModel)


# ------------------------------ Workflow.train -> score_function, fused plan
@pytest.fixture(scope="module")
def trained_workflow():
    from transmogrifai_tpu.dataset import Dataset
    from transmogrifai_tpu.features import from_dataset
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector
    from transmogrifai_tpu.types.columns import column_from_values
    from transmogrifai_tpu.workflow.workflow import Workflow
    import transmogrifai_tpu.types as T

    rng = np.random.default_rng(17)
    n = 256
    x1, x2 = rng.normal(size=n), rng.normal(size=n)
    city = [["bern", "kyiv", "oslo", "lome"][i % 4] for i in range(n)]
    label = (x1 + 0.5 * x2 + (np.arange(n) % 4 == 1) > 0).astype(float)
    ds = Dataset.of({
        "label": column_from_values(T.RealNN, label),
        "age": column_from_values(T.Real, x1),
        "income": column_from_values(T.Real, x2),
        "city": column_from_values(T.PickList, city),
    })
    resp, preds = from_dataset(ds, response="label")
    vec = resp.sanity_check(transmogrify(list(preds)),
                            remove_bad_features=True)
    pred = BinaryClassificationModelSelector(
        seed=7, num_folds=2,
        models=[(G.GBTClassifier(max_iter=3, max_depth=3, max_bins=BINS),
                 {"min_instances_per_node": [5]})],
    ).set_input(resp, vec).get_output()
    model = Workflow().set_result_features(pred).set_input_dataset(ds).train()
    rows = [{"age": float(a), "income": float(b), "city": c}
            for a, b, c in zip(x1, x2, city)]
    return model, rows


def test_score_function_and_the_fused_plan_agree_with_predict_arrays(
        trained_workflow, monkeypatch):
    from transmogrifai_tpu.local.scoring import score_function

    model, rows = trained_workflow
    winner = [s.best_model for s in model.fitted.values()
              if hasattr(s, "best_model")][0]
    assert isinstance(winner, G.GBTClassificationModel)
    assert winner.tree_weights.tolist() == [1.0, F32(0.1), F32(0.1)]
    staged = score_function(model)  # 64 rows: the host traversal
    out_host = [list(r.values())[0] for r in staged.batch(rows[:64])]
    assert not staged.metadata()["fused"]["dispatches"]
    monkeypatch.setenv("TPTPU_HOST_PREDICT_MAX", "4")
    fused = score_function(model)
    out_fused = [list(r.values())[0] for r in fused.batch(rows[:64])]
    assert fused.metadata()["fused"]["dispatches"] == 1
    assert not fused.metadata()["fused"]["fallbacks"]
    for a, b in zip(out_host, out_fused):
        f = a["rawPrediction_1"]
        assert b["rawPrediction_1"] == pytest.approx(f, rel=1e-5, abs=1e-6)
        for row in (a, b):
            assert row["probability_1"] == pytest.approx(
                1.0 / (1.0 + np.exp(-2.0 * row["rawPrediction_1"])), rel=1e-9)
            assert row["prediction"] == float(row["rawPrediction_1"] > 0)


# ------------------------------------- batched, sequential, sharded: one fit
def test_batched_equals_sequential_tree_for_tree():
    x, _codes, y = _table(600, seed=10)
    rng = np.random.default_rng(3)
    masks = [(rng.random(600) < 0.75).astype(F32), np.ones(600, F32)]
    points = [{"min_instances_per_node": 5, "min_info_gain": 0.001},
              {"min_instances_per_node": 40, "min_info_gain": 0.01}]
    est = G.GBTClassifier(max_iter=3, max_depth=4, max_bins=BINS)
    batched = est.fit_arrays_batched_masks(x, y, masks, points)
    for mi, mask in enumerate(masks):
        for pi, point in enumerate(points):
            one = est.with_params(**point).fit_arrays(x, y, mask)
            a, b = batched[mi][pi].get_arrays(), one.get_arrays()
            assert isinstance(batched[mi][pi], G.GBTClassificationModel)
            for key in a:
                np.testing.assert_array_equal(
                    np.nan_to_num(a[key]), np.nan_to_num(b[key]),
                    err_msg=f"{key} mask {mi} point {pi}")
    # the sweep's own outputs are the margins F; the protocol maps 2F
    stack = batched[0][0]._sweep_stack
    lane = np.asarray(stack["outputs"])[batched[0][0]._sweep_lane]
    _, prob, raw = batched[0][0].predictions_from_sweep(lane)
    np.testing.assert_allclose(prob[:, 1], 1 / (1 + np.exp(-2.0 * lane)),
                               rtol=1e-6)
    _, prob_p, raw_p = batched[0][0].predict_arrays(x)
    np.testing.assert_allclose(raw[:, 1], raw_p[:, 1], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("objective", TR.SPARK_OBJECTIVES)
def test_sharded_equals_single_tree_for_tree(objective):
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from transmogrifai_tpu.parallel import make_mesh

    x, codes, y = _table(333, seed=12)
    if objective == "spark:squarederror":
        y = (x[:, 0] + np.abs(x[:, 1])).astype(F32)
    masks = (np.random.default_rng(0).random((2, 333)) > 0.2).astype(F32)
    kw = dict(
        num_rounds=3, max_depth=3, num_bins=BINS, eta=STEP, reg_lambda=0.0,
        gamma=0.0, min_child_weight=np.asarray([4.0, 12.0], F32),
        min_info_gain=0.001, objective=objective, info_gain_norm=2.0)
    single, m1 = TR.fit_boosted_batched(codes, y, masks, **kw)
    sharded, m2 = TR.fit_boosted_batched(
        codes, y, masks, mesh=make_mesh(n_data=8, n_model=1), **kw)
    np.testing.assert_array_equal(np.asarray(single.split_feat),
                                  np.asarray(sharded.split_feat))
    np.testing.assert_array_equal(np.asarray(single.split_bin),
                                  np.asarray(sharded.split_bin))
    np.testing.assert_allclose(
        np.nan_to_num(np.asarray(single.leaf_value)),
        np.nan_to_num(np.asarray(sharded.leaf_value)), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2)[:, :333],
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------ spans and the tree ledger
def test_fit_dispatch_says_the_objective_and_the_ledger_counts_rounds():
    from transmogrifai_tpu.telemetry import spans

    x, _codes, y = _table(400, seed=14)
    ones = np.ones(400, F32)
    before = TR.hist_slot_stats().snapshot()
    mark = len(spans.snapshot_events())
    gbt = G.GBTClassifier(max_iter=3, max_depth=2, max_bins=BINS
                          ).fit_arrays_batched(
        x, y, ones, [{"min_instances_per_node": 5},
                     {"min_instances_per_node": 9}])
    xgb = G.XGBoostClassifier(num_round=2, max_depth=2, max_bins=BINS, eta=0.3
                              ).fit_arrays_batched(x, y, ones, [{"gamma": 0.0}])
    # written on the first read of a fit's outputs, as histSlotsLive is
    for model in (gbt[0], xgb[0]):
        G.await_stack_outputs(model._sweep_stack)
    events = spans.snapshot_events()[mark:]
    got = [e["args"] for e in events if e["name"] == "tree/fit_dispatch"]
    assert [(a["objective"], a["tree_weights"], a["rounds"]) for a in got] == [
        ("spark:logloss", "1 0.1", 3), ("binary:logistic", "0.3 0.3", 2)]
    waits = [e["args"] for e in events if e["name"] == "tree/await_outputs"
             and "slots_built" in e.get("args", {})]
    # 2 lanes x (1 round on the label + 2 on a pseudo-residual); a Newton
    # fit counts neither and says nothing
    assert (waits[0]["boost_rounds_label"],
            waits[0]["boost_rounds_residual"]) == (2, 4)
    assert "boost_rounds_label" not in waits[1]
    now = TR.hist_slot_stats().snapshot()
    assert now["boostRoundsLabel"] - before["boostRoundsLabel"] == 2
    assert now["boostRoundsResidual"] - before["boostRoundsResidual"] == 4


# ------------------------------------------------ XGBoost: nothing moved
def test_xgboost_classifier_is_unchanged_bit_for_bit(monkeypatch):
    """The estimator's trees are those of the unchanged Newton objective
    called directly, every tree weighs ``eta`` and the margin is
    base + eta * (the pairwise sum of the trees), bit for bit, on the host
    traversal and the device program alike."""
    x, codes, y = _table(700, seed=5)
    ones = np.ones(700, F32)
    model = G.XGBoostClassifier(
        num_round=3, max_depth=4, eta=0.3, gamma=0.1, min_child_weight=2.0,
        max_bins=BINS).fit_arrays(x, y, ones)
    assert type(model) is G.BoostedBinaryModel and model._LINK == 1.0
    arrays = model.get_arrays()
    assert "tree_weights" not in arrays
    trees, _ = TR.fit_boosted(
        jnp.asarray(codes), jnp.asarray(y), jnp.asarray(ones), num_rounds=3,
        max_depth=4, num_bins=BINS, eta=0.3, reg_lambda=1.0, gamma=0.1,
        min_child_weight=2.0, objective="binary:logistic",
        feature_groups=G._feature_bin_groups(x))
    for key in TR.Tree._fields:
        np.testing.assert_array_equal(arrays[key], np.asarray(getattr(trees, key)),
                                      err_msg=key)
    per_tree = jax.vmap(lambda t: TR.predict_tree(jnp.asarray(codes), t))(trees)
    want = np.asarray(jnp.float32(0.0) + jnp.float32(0.3) * TR.sum_trees(per_tree))
    monkeypatch.setenv("TPTPU_HOST_PREDICT_MAX", "0")
    _, prob, raw = model.predict_arrays(x)
    np.testing.assert_array_equal(raw[:, 1], want.astype(np.float64))
    np.testing.assert_array_equal(
        prob[:, 1], 1.0 / (1.0 + np.exp(-want.astype(np.float64))))
    monkeypatch.delenv("TPTPU_HOST_PREDICT_MAX")
    model._dev_cache = None
    _, _, raw_host = model.predict_arrays(x)
    np.testing.assert_allclose(raw_host[:, 1], raw[:, 1], rtol=1e-6, atol=1e-7)


# --------------------------- the other learners, pinned from the parent commit
def _digest(arrays, keys):
    h = hashlib.sha256()
    for key in keys:
        h.update(np.ascontiguousarray(arrays[key]).tobytes())
    return h.hexdigest()[:16]


PINNED = {
    # (split_feat + split_bin digest, sum of |leaf|) at commit 8e4bcb6
    "xgb_classifier": ("515c7f0ced0ad43b", 32.649837493896484),
    "xgb_regressor": ("281d366c6072c428", 22.1898136138916),
    "forest": ("198c3af742be8454", 23.88396453857422),
}


def _other_learners():
    x, _codes, y = _table(700, seed=5)
    ones = np.ones(700, F32)
    yr = (np.abs(x[:, 0]) + x[:, 1]).astype(np.float64)
    return {
        "xgb_classifier": (G.XGBoostClassifier(
            num_round=3, max_depth=4, eta=0.3, gamma=0.1,
            min_child_weight=2.0, max_bins=BINS), x, y, ones, ""),
        "xgb_regressor": (G.XGBoostRegressor(
            num_round=3, max_depth=3, eta=0.2, max_bins=BINS),
            x, yr, ones, ""),
        "forest": (G.RandomForestClassifier(
            num_trees=3, max_depth=4, min_instances_per_node=5,
            min_info_gain=0.001, max_bins=BINS, seed=7), x, y, ones, "c0__"),
    }


@pytest.mark.parametrize("name", sorted(PINNED))
def test_other_learners_trees_are_the_parents(name):
    est, x, y, ones, prefix = _other_learners()[name]
    arrays = est.fit_arrays(x, y, ones).get_arrays()
    digest, leaf_sum = PINNED[name]
    assert _digest(arrays, (prefix + "split_feat", prefix + "split_bin")
                   ) == digest
    got = float(np.abs(np.nan_to_num(arrays[prefix + "leaf_value"])).sum())
    assert got == pytest.approx(leaf_sum, rel=1e-6)
