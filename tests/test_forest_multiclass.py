"""The random forest on a label of K classes is ONE forest whose nodes hold
the K class counts (``gbdt.FOREST_MULTICLASS``): the program against the
benchmark's plain reference (``benchmarks/checks/forest_multiclass_winner.py``,
which imports nothing from the program) node for node at K in {3, 7}; K = 2
through the same code against the learner the binary forest always was;
the kernel's statistic axis; the scoring paths of a fitted multiclass
workflow."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.checks import forest_multiclass_winner as REF  # noqa: E402
from benchmarks.checks import xgb_winner  # noqa: E402
from transmogrifai_tpu.models import gbdt  # noqa: E402
from transmogrifai_tpu.models import hist_pallas as HP  # noqa: E402
from transmogrifai_tpu.models import trees as TR  # noqa: E402

BINS = 32


def _table(classes, n=2600, f=14, seed=5):
    """A seeded plane (real columns, a skewed one, 0/1 columns) and a label
    of ``classes`` classes with signal in most columns."""
    rng = np.random.default_rng(seed + classes)
    x = rng.normal(size=(n, f)).astype(np.float32)
    x[:, 3] = np.exp(x[:, 3])
    x[:, f - 4:] = x[:, f - 4:] > 0.4
    score = np.stack(
        [x[:, k % f] * (1.0 + 0.2 * k) - 0.35 * k for k in range(classes)], 1)
    y = (score + rng.gumbel(size=score.shape)).argmax(axis=1)
    return x, y.astype(np.float64)


def _fit(x, y, masks, params, classes):
    """The program's forests of every (mask, point) lane through the
    sweep's entry (``fit_forest_batched``), on its own bin codes."""
    est = gbdt.RandomForestClassifier(
        num_trees=params["num_trees"], max_depth=params["max_depth"],
        max_bins=BINS, seed=params["seed"])
    thresholds, binned, fgroups = est._binned(x)
    statics = est._forest_statics(est.get_params(), x.shape[1], 1.0)
    trees, outs, slots = TR.fit_forest_batched(
        binned, np.asarray(y, np.float32), jnp.asarray(masks),
        num_trees=params["num_trees"], max_depth=params["max_depth"],
        num_bins=BINS, min_instances=params["min_instances_per_node"],
        min_info_gain=params["min_info_gain"], seed=params["seed"],
        lowp=True, feature_groups=fgroups, num_classes=classes,
        return_outputs=True, return_slots=True, **statics)
    return thresholds, jax.tree.map(np.asarray, trees), np.asarray(outs)


@pytest.mark.parametrize("classes", [3, 7])
def test_program_equals_the_plain_reference_node_for_node(classes):
    """Splits equal exactly (the sums are integers, the gain the same
    float32 expression); leaves and probabilities within 1e-6 (one float32
    division a value on either side, then a mean over two trees: a last-bit
    difference of the two compilers' division is 6e-8). The reference's own
    walk along the program's routing (the source's form of the Gini gain)
    finds nothing."""
    x, y = _table(classes)
    n = len(y)
    rng = np.random.default_rng(11)
    masks = np.stack([np.ones(n), rng.random(n) < 0.75]).astype(np.float32)
    params = {"num_trees": 2, "max_depth": 5, "max_bins": BINS, "seed": 42,
              "min_instances_per_node": 10, "min_info_gain": 0.001,
              "feature_subset_strategy": "auto", "subsampling_rate": 1.0}
    thresholds, trees, outs = _fit(x, y, masks, params, classes)
    assert trees.leaf_value.shape == (2, 2, 32, classes)
    assert outs.shape == (2, classes, n)
    thr = xgb_winner.quantile_thresholds(x, BINS)
    assert np.array_equal(thr, thresholds)
    codes = xgb_winner.bin_codes(x, thr)
    splits = 0
    for lane, mask in enumerate(masks):
        mine, _, prob, _ = REF.forest(codes, y, mask, params, classes)
        assert np.array_equal(mine["split_feat"], trees.split_feat[lane])
        assert np.array_equal(mine["split_bin"], trees.split_bin[lane])
        splits += int((mine["split_feat"] >= 0).sum())
        held = np.isfinite(trees.leaf_value[lane]).all(axis=-1)
        assert np.abs(
            np.where(held[..., None], trees.leaf_value[lane], 0.0)
            - np.where(held[..., None], mine["leaf_value"], 0.0)
        ).max() <= 1e-6
        assert np.abs(outs[lane].T - prob).max() <= 1e-6
        # the class shares of a leaf sum to one
        assert np.abs(trees.leaf_value[lane][held].sum(-1) - 1).max() <= 1e-6
        given = {"split_feat": trees.split_feat[lane],
                 "split_bin": trees.split_bin[lane],
                 "leaf_value": trees.leaf_value[lane]}
        _, found, prob2, theirs = REF.forest(
            codes, y, mask, params, classes, trees=given)
        assert found["node_subset_violations"] == 0
        assert found["stop_rule_violations"] == 0
        assert found["split_gain_gap"] <= 1e-6
        assert found["leaf_value_gap"] <= 1e-6
        assert np.abs(theirs - prob2).max() <= 1e-6
    assert splits >= 20, "the trees have to grow for this to say anything"


def test_naming_the_classes_otherwise_grows_the_same_nodes():
    """The K-class Gini is symmetric in the classes: under permuted class
    ids every node splits on the column and bin it split on, and a leaf's
    vector is the same shares under the new names (the multiclass cell of
    the benchmark leans on this: its seeds name the classes)."""
    classes = 7
    x, y = _table(classes)
    order = np.random.default_rng(11).permutation(classes)
    params = {"num_trees": 2, "max_depth": 6, "seed": 42,
              "min_instances_per_node": 10, "min_info_gain": 0.001}
    masks = np.ones((1, len(y)), np.float32)
    _, plain, _ = _fit(x, y, masks, params, classes)
    _, named, _ = _fit(x, order[y.astype(int)].astype(np.float64), masks,
                       params, classes)
    np.testing.assert_array_equal(plain.split_feat, named.split_feat)
    np.testing.assert_array_equal(plain.split_bin, named.split_bin)
    # leaf [..., nodes, K]: class c's share now sits at order[c]
    np.testing.assert_allclose(
        np.take(named.leaf_value, order, axis=-1), plain.leaf_value,
        rtol=0, atol=1e-6)


def _rehearsal_plane():
    """``flagship_rf``'s rehearsal shape: the flagship table at 2,048 rows,
    its training rows' plane from the benchmark's own vectorizers."""
    from benchmarks.lib import datagen, reference

    table = datagen.flagship_table(2048, 2147483777)
    null = reference.NULL
    columns = []
    for kind, count in (("real", 10), ("int", 5), ("bin", 3)):
        for j in range(count):
            columns += [(f"{kind}_{j}", None, None), (f"{kind}_{j}", null, None)]
    for j, levels in enumerate(datagen.PICK_LEVELS):
        columns += [(f"pick_{j}", f"P{j}{c}", None) for c in range(levels)]
        columns.append((f"pick_{j}", null, None))
    columns += [("text_0", None, f"hash_{b}") for b in range(0, 512, 4)]
    rows = reference.train_rows(2048, 2147483777)
    return (reference.plane(table, rows, columns),
            table["label"][rows].astype(np.float64))


def test_two_classes_through_the_class_axis_are_the_binary_forest():
    """K = 2 runs the K-class code (one value channel, the class-1
    indicator) and gives, bit for bit, the forest this learner was before
    it had a class axis: a regression forest of the 0/1 target whose
    variance decrease 2 bg / W is held to HALF ``min_info_gain`` (the Gini
    of a 0/1 target is twice its variance: the old rule's 4 bg / W)."""
    x, y = _rehearsal_plane()
    n = len(y)
    rng = np.random.default_rng(3)
    masks = np.stack([np.ones(n), rng.random(n) < 0.75]).astype(np.float32)
    est = gbdt.RandomForestClassifier(num_trees=2, max_bins=BINS)
    _thr, binned, fgroups = est._binned(x)
    grown = 0
    for depth in (3, 4):
        for min_inst in (10.0, 100.0):
            kw = dict(
                num_trees=2, max_depth=depth, num_bins=BINS,
                min_instances=min_inst, seed=42, lowp=True,
                feature_groups=fgroups, bootstrap=True,
                feature_subset=int(np.ceil(np.sqrt(x.shape[1]))),
                return_outputs=True)
            new_t, new_o = TR.fit_forest_batched(
                binned, y.astype(np.float32), jnp.asarray(masks),
                min_info_gain=0.001, info_gain_norm=TR.GINI, num_classes=2,
                **kw)
            old_t, old_o = TR.fit_forest_batched(
                binned, (y == 1).astype(np.float32), jnp.asarray(masks),
                min_info_gain=0.0005, info_gain_norm=TR.VARIANCE, **kw)
            for a, b in zip(jax.tree.leaves(new_t), jax.tree.leaves(old_t)):
                a, b = np.asarray(a), np.asarray(b)
                assert a.shape == b.shape and a.dtype == b.dtype
                assert np.array_equal(a, b, equal_nan=True)
            assert np.array_equal(np.asarray(new_o), np.asarray(old_o))
            grown += int((np.asarray(new_t.split_feat) >= 0).sum())
    assert grown >= 16


@pytest.mark.parametrize("lowp", [True, False])
def test_the_kernel_builds_every_statistic_channel(lowp):
    """The bin-loop kernel (interpreted) on a [K, V, N] statistic against
    the scatter builder: V + 1 channels, each the two-channel kernel's own
    sums for that statistic."""
    rng = np.random.default_rng(9)
    n, f, k, v, m, b = 700, 11, 2, 4, 8, 8
    binned = jnp.asarray(rng.integers(0, b, size=(n, f)), jnp.int32)
    node = jnp.asarray(rng.integers(-1, m, size=(k, n)), jnp.int32)
    grad = rng.integers(-3, 4, size=(k, v, n)).astype(np.float32)
    if not lowp:
        grad = grad + rng.normal(size=grad.shape).astype(np.float32)
    hess = rng.integers(0, 3, size=(k, n)).astype(np.float32)
    got = HP.build_histogram_pallas_binloop(
        binned, node, jnp.asarray(grad), jnp.asarray(hess), m, b,
        lowp=lowp, interpret=True)
    want = HP.build_histogram_scatter_batched(
        binned, node, jnp.asarray(grad), jnp.asarray(hess), m, b)
    assert got.shape == want.shape == (k, m, f, b, v + 1)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= (
        0.0 if lowp else 2e-4)
    gemm = HP.build_histogram_gemm(
        HP.one_hot_codes(binned, b, lowp), node, jnp.asarray(grad),
        jnp.asarray(hess), m, b, lowp=lowp)
    assert np.abs(np.asarray(gemm) - np.asarray(want)).max() <= (
        0.0 if lowp else 2e-4)
    for ch in range(v):
        pair = HP.build_histogram_pallas_binloop(
            binned, node, jnp.asarray(grad[:, ch]), jnp.asarray(hess), m, b,
            lowp=lowp, interpret=True)
        assert np.array_equal(np.asarray(pair[..., 0]), np.asarray(got[..., ch]))
        assert np.array_equal(np.asarray(pair[..., 1]), np.asarray(got[..., v]))


def test_predict_paths_carry_the_class_axis():
    """Host traversal, the banked device program, the sweep's output
    program and the fit program's own outputs give the same [rows, K]."""
    x, y = _table(5, n=1500)
    est = gbdt.RandomForestClassifier(
        num_trees=3, max_depth=4, max_bins=16, min_instances_per_node=5)
    model = est.fit_arrays(x, y, np.ones(len(y), np.float32))
    assert np.asarray(model.trees.leaf_value).shape == (3, 16, 5)
    pred, prob, raw = model.predict_arrays(x)          # the host's path
    assert prob.shape == (len(y), 5) and np.abs(prob.sum(1) - 1).max() < 1e-6
    assert np.array_equal(pred, prob.argmax(axis=1))
    assert (pred == y).mean() > 0.5
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(model.thresholds))
    dev = np.asarray(TR.predict_forest(binned, model._dev(model.trees)))
    assert dev.shape == (5, len(y))
    assert np.abs(dev.T - raw).max() <= 1e-6
    stack = jax.tree.map(lambda a: jnp.asarray(a)[None], model.trees)
    swept = np.asarray(TR.sweep_forest_outputs(
        jnp.asarray(x), jnp.asarray(model.thresholds), stack,
        jnp.ones(1), jnp.zeros(1)))
    assert swept.shape == (1, 5, len(y))
    assert np.abs(swept[0].T - raw).max() <= 1e-6
    assert np.array_equal(model.predictions_from_sweep(swept[0])[0], pred)
    # persistence: one forest under the prefix the binary model had
    again = gbdt.ForestClassifierModel.from_params({}, model.get_arrays())
    assert np.array_equal(again.predict_arrays(x)[1], prob)
    with pytest.raises(ValueError, match="one-vs-rest"):
        gbdt.ForestClassifierModel.from_params(
            {}, {**model.get_arrays(), "c1__split_feat": 0})


@pytest.fixture(scope="module")
def multiclass_workflow():
    import transmogrifai_tpu.types as T
    from transmogrifai_tpu.dataset import Dataset
    from transmogrifai_tpu.features import from_dataset
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.selector import MultiClassificationModelSelector
    from transmogrifai_tpu.selector.model_selector import make_candidates
    from transmogrifai_tpu.selector.validators import TrainValidationSplit
    from transmogrifai_tpu.types.columns import column_from_values
    from transmogrifai_tpu.workflow.workflow import Workflow

    rng = np.random.default_rng(23)
    n = 600
    a, b = rng.normal(size=n), rng.normal(size=n)
    city = [["bern", "kyiv", "oslo"][i % 3] for i in range(n)]
    label = np.select([a > 0.6, b > 0.3], [2.0, 1.0], 0.0)
    ds = Dataset.of({
        "label": column_from_values(T.RealNN, label),
        "a": column_from_values(T.Real, a),
        "b": column_from_values(T.Real, b),
        "city": column_from_values(T.PickList, city),
    })
    resp, preds = from_dataset(ds, response="label")
    models = make_candidates("MultiClassification", ["OpRandomForestClassifier"])
    for _est, grid in models:
        grid.update(num_trees=[3], max_depth=[3, 4], min_info_gain=[0.001],
                    min_instances_per_node=[5])
    selector = MultiClassificationModelSelector(
        seed=5, models=models, validator=TrainValidationSplit(seed=5))
    pred = selector.set_input(resp, transmogrify(list(preds))).get_output()
    model = Workflow().set_result_features(pred).set_input_dataset(ds).train()
    rows = [{"a": float(u), "b": float(v), "city": c}
            for u, v, c in zip(a, b, city)]
    return model, ds, pred, rows, label


def test_the_multiclass_selector_runs_one_forest_a_lane(multiclass_workflow):
    model, ds, pred, _rows, label = multiclass_workflow
    sel = model.summary_json()["modelSelectorSummary"]
    assert sel["bestModelType"] == "RandomForestClassifier"
    assert sel["evaluationMetric"] == "F1"
    assert len(sel["validationResults"]) == 2
    assert not any(a["excluded"] for a in sel["candidateAttempts"])
    assert sel["trainEvaluation"]["F1"] > 0.8
    winner = next(s for s in model.fitted.values()
                  if hasattr(s, "best_model")).best_model
    assert isinstance(winner, gbdt.ForestClassifierModel)
    assert np.asarray(winner.trees.leaf_value).shape[-1] == 3
    assert set(winner.get_arrays()) == {
        "thresholds", "c0__split_feat", "c0__split_bin", "c0__leaf_value"}
    scored = model.score(dataset=ds)[pred.name]
    assert np.asarray(scored.probability).shape == (len(label), 3)


def test_score_function_on_a_multiclass_forest_workflow(multiclass_workflow):
    from transmogrifai_tpu.local.scoring import score_function

    model, ds, pred, rows, label = multiclass_workflow
    fn = score_function(model)
    one = fn(rows[0])[pred.name]
    keys = {k for k in one if k.startswith("probability")}
    assert len(keys) == 3 and "prediction" in one
    batch = fn.batch(rows[:64])
    got = np.asarray([r[pred.name]["prediction"] for r in batch])
    want = np.asarray(model.score(dataset=ds)[pred.name].prediction)[:64]
    assert np.array_equal(got, want)
    assert (got == label[:64]).mean() > 0.8
