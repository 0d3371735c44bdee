"""SanityChecker tests — mini BadFeatureZoo (parity: core/.../preparators/
BadFeatureZooTest.scala approach: construct known-leaky/known-junk features
and assert they are caught)."""
import numpy as np
import pytest

import transmogrifai_tpu.types as T
from transmogrifai_tpu.dataset import Dataset
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.prep import SanityChecker
from transmogrifai_tpu.stages.metadata import (
    NULL_STRING,
    OTHER_STRING,
    ColumnMeta,
    VectorMetadata,
)
from transmogrifai_tpu.types.columns import NumericColumn, VectorColumn
from transmogrifai_tpu.utils import stats as S


def _vec_ds(x, metas, y, name="vec", label="label"):
    meta = VectorMetadata(name, tuple(
        ColumnMeta(**{**m, "index": i}) for i, m in enumerate(metas)
    ))
    return Dataset.of({
        label: NumericColumn(T.RealNN, np.asarray(y, dtype=np.float64),
                             np.ones(len(y), dtype=bool)),
        name: VectorColumn(T.OPVector, np.asarray(x, dtype=np.float32), meta),
    })


def _checker_inputs(name="vec", label="label"):
    lbl = FeatureBuilder.RealNN(label).as_response()
    vec = FeatureBuilder.OPVector(name).as_predictor()
    return lbl, vec


def _col(parent, **kw):
    return {"parent_names": (parent,), "parent_type": "Real", **kw}


# ------------------------------ stats plane ---------------------------------
def test_correlation_matrix_basic():
    rng = np.random.default_rng(0)
    a = rng.normal(size=500)
    b = 2 * a + 0.001 * rng.normal(size=500)
    c = rng.normal(size=500)
    corr = S.correlation_matrix(np.stack([a, b, c], axis=1))
    assert corr[0, 1] > 0.999
    assert abs(corr[0, 2]) < 0.2
    np.testing.assert_allclose(np.diag(corr), 1.0)


def test_correlation_zero_variance_is_zero():
    x = np.stack([np.ones(10), np.arange(10.0)], axis=1)
    corr = S.correlation_matrix(x)
    assert corr[0, 1] == 0.0


def test_cramers_v_perfect_and_independent():
    perfect = np.array([[50.0, 0.0], [0.0, 50.0]])
    assert S.cramers_v(perfect) == pytest.approx(1.0)
    indep = np.array([[25.0, 25.0], [25.0, 25.0]])
    assert S.cramers_v(indep) == pytest.approx(0.0)


def test_spearman_monotonic():
    x = np.arange(100.0)
    y = np.exp(x / 10.0)  # monotonic, nonlinear
    corr = S.spearman_correlation_matrix(x[:, None], y)
    assert corr[0, 1] == pytest.approx(1.0)


def test_association_rule_confidence():
    cont = np.array([[30.0, 0.0], [10.0, 10.0]])
    conf, support = S.association_rule_confidence(cont)
    assert conf[0] == pytest.approx(1.0)
    assert support[0] == pytest.approx(0.6)


# --------------------------- sanity checker zoo -----------------------------
def test_leaky_label_copy_dropped(rng):
    y = rng.integers(0, 2, 400).astype(float)
    good = rng.normal(size=400)
    x = np.stack([y, good], axis=1)  # col 0 IS the label
    ds = _vec_ds(x, [_col("leak"), _col("good")], y)
    lbl, vec = _checker_inputs()
    est = SanityChecker(remove_bad_features=True).set_input(lbl, vec)
    model = est.fit(ds)
    assert model.indices_to_keep == [1]
    summary = est.metadata["sanityCheckerSummary"]
    assert summary["numDropped"] == 1
    dropped = [c for c in summary["columns"] if c["dropped"]][0]
    assert any("corrLabel" in r for r in dropped["reasons"])


def test_constant_column_dropped(rng):
    y = rng.integers(0, 2, 300).astype(float)
    x = np.stack([np.full(300, 7.0), rng.normal(size=300)], axis=1)
    ds = _vec_ds(x, [_col("const"), _col("ok")], y)
    lbl, vec = _checker_inputs()
    model = SanityChecker(remove_bad_features=True).set_input(lbl, vec).fit(ds)
    assert model.indices_to_keep == [1]


def test_duplicate_feature_drops_later(rng):
    y = rng.integers(0, 2, 300).astype(float)
    a = rng.normal(size=300)
    x = np.stack([a, a.copy(), rng.normal(size=300)], axis=1)
    ds = _vec_ds(x, [_col("a"), _col("a2"), _col("b")], y)
    lbl, vec = _checker_inputs()
    model = SanityChecker(remove_bad_features=True).set_input(lbl, vec).fit(ds)
    assert model.indices_to_keep == [0, 2]


def test_categorical_leak_drops_whole_group(rng):
    n = 400
    y = rng.integers(0, 2, n).astype(float)
    # pivot group "cat" perfectly encodes the label
    cat_a = (y == 0).astype(float)
    cat_b = (y == 1).astype(float)
    other = np.zeros(n)
    good = rng.normal(size=n)
    x = np.stack([cat_a, cat_b, other, good], axis=1)
    metas = [
        _col("cat", grouping="cat", indicator_value="A", parent_type="PickList"),
        _col("cat", grouping="cat", indicator_value="B", parent_type="PickList"),
        _col("cat", grouping="cat", indicator_value=OTHER_STRING, parent_type="PickList"),
        _col("good"),
    ]
    ds = _vec_ds(x, metas, y)
    lbl, vec = _checker_inputs()
    est = SanityChecker(remove_bad_features=True).set_input(lbl, vec)
    model = est.fit(ds)
    assert model.indices_to_keep == [3]  # whole group removed


def test_good_features_kept(rng):
    n = 500
    y = rng.integers(0, 2, n).astype(float)
    x = np.stack([
        y * 0.4 + rng.normal(size=n),  # informative, not leaky
        rng.normal(size=n),
    ], axis=1)
    ds = _vec_ds(x, [_col("f1"), _col("f2")], y)
    lbl, vec = _checker_inputs()
    model = SanityChecker(remove_bad_features=True).set_input(lbl, vec).fit(ds)
    assert model.indices_to_keep == [0, 1]


def test_remove_bad_features_false_keeps_all(rng):
    y = rng.integers(0, 2, 200).astype(float)
    x = np.stack([y, rng.normal(size=200)], axis=1)
    ds = _vec_ds(x, [_col("leak"), _col("good")], y)
    lbl, vec = _checker_inputs()
    est = SanityChecker(remove_bad_features=False).set_input(lbl, vec)
    model = est.fit(ds)
    out = model.transform(ds)[est.output_name]
    assert out.values.shape[1] == 2  # reported but not removed
    assert est.metadata["sanityCheckerSummary"]["numDropped"] == 1


def test_transform_removes_and_subsets_metadata(rng):
    y = rng.integers(0, 2, 200).astype(float)
    x = np.stack([y, rng.normal(size=200)], axis=1)
    ds = _vec_ds(x, [_col("leak"), _col("good")], y)
    lbl, vec = _checker_inputs()
    est = SanityChecker(remove_bad_features=True).set_input(lbl, vec)
    out = est.fit(ds).transform(ds)[est.output_name]
    assert out.values.shape == (200, 1)
    assert [c.parent_names for c in out.metadata.columns] == [("good",)]
    assert out.metadata.columns[0].index == 0


def test_titanic_transmogrify_plus_sanity_check(titanic_path):
    from transmogrifai_tpu.features import from_dataset
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.readers import infer_csv_dataset
    from transmogrifai_tpu.readers.core import DatasetReader
    from transmogrifai_tpu.workflow.dag import raw_features_of
    from transmogrifai_tpu.workflow.fit import fit_and_transform_dag

    ds = infer_csv_dataset(titanic_path)
    resp, preds = from_dataset(ds, response="Survived")
    preds = [p for p in preds if p.name != "PassengerId"]
    vector = transmogrify(preds)
    checker = SanityChecker(remove_bad_features=True)
    checked = resp.transform_with(checker, vector)
    raw = DatasetReader(ds).generate_dataset(raw_features_of([checked]))
    data, fitted = fit_and_transform_dag(raw, [checked])
    out = data[checked.name]
    before = data[vector.name].values.shape[1]
    after = out.values.shape[1]
    assert 0 < after <= before
    assert np.isfinite(np.asarray(out.values)).all()
    summary = checker.metadata["sanityCheckerSummary"]
    assert summary["numColumns"] == before


# ---------------- sampling caps (SanityChecker.scala:356-361,562-564) -------
def test_sample_fraction_clamps():
    est = SanityChecker()
    # small data: lower limit forces full fraction
    assert est._sample_fraction(500) == 1.0
    # above the upper limit: fraction caps the checked rows at the limit
    assert est._sample_fraction(4_000_000) == pytest.approx(0.25)
    # check_sample below the lower-limit floor gets raised to it
    est2 = SanityChecker(check_sample=0.0001)
    assert est2._sample_fraction(100_000) == pytest.approx(0.01)
    # explicit fraction honored when inside the clamp window
    est3 = SanityChecker(check_sample=0.5)
    assert est3._sample_fraction(100_000) == pytest.approx(0.5)


def test_sampled_check_is_deterministic_and_bounded(rng):
    n = 5000
    y = rng.integers(0, 2, n).astype(float)
    leak = y + rng.normal(scale=1e-4, size=n)
    good = rng.normal(size=n)
    x = np.stack([leak, good], axis=1)
    metas = [_col("leak"), _col("good")]
    ds = _vec_ds(x, metas, y)
    lbl, vec = _checker_inputs()
    est = SanityChecker(
        remove_bad_features=True, check_sample=0.1,
        sample_lower_limit=100, sample_upper_limit=1000,
    ).set_input(lbl, vec)
    model = est.fit(ds)
    summary = est.metadata["sanityCheckerSummary"]
    assert summary["numRows"] == 500  # 0.1 * 5000, inside [100, 1000]
    assert model.indices_to_keep == [1]  # leak caught on the sample
    # same seed -> same sample -> same decisions
    est2 = SanityChecker(
        remove_bad_features=True, check_sample=0.1,
        sample_lower_limit=100, sample_upper_limit=1000,
    ).set_input(lbl, vec)
    assert est2.fit(ds).indices_to_keep == model.indices_to_keep


# --------- text shared-hash protection (DerivedFeatureFilterUtils) ----------
def _hash_block_with_leaky_pivot(rng, n=400):
    y = rng.integers(0, 2, n).astype(float)
    pivot_a = (y == 0).astype(float)  # leaky indicator, parent "desc"
    hash_0 = rng.normal(size=n)       # shared-hash block, same parent
    hash_1 = rng.normal(size=n)
    good = rng.normal(size=n)
    x = np.stack([pivot_a, hash_0, hash_1, good], axis=1)
    metas = [
        _col("desc", grouping="desc", indicator_value="A", parent_type="Text"),
        _col("desc", parent_type="Text", descriptor_value="hash_0"),
        _col("desc", parent_type="Text", descriptor_value="hash_1"),
        _col("good"),
    ]
    return _vec_ds(x, metas, y), y


def test_leaky_pivot_takes_sibling_hash_block_by_default(rng):
    ds, _ = _hash_block_with_leaky_pivot(rng)
    lbl, vec = _checker_inputs()
    est = SanityChecker(remove_bad_features=True).set_input(lbl, vec)
    model = est.fit(ds)
    # reference default (protectTextSharedHash=false): parent-level removal
    # takes the hash block down with the leaky pivot
    assert model.indices_to_keep == [3]


def test_protect_text_shared_hash_keeps_hash_block(rng):
    ds, _ = _hash_block_with_leaky_pivot(rng)
    lbl, vec = _checker_inputs()
    est = SanityChecker(
        remove_bad_features=True, protect_text_shared_hash=True
    ).set_input(lbl, vec)
    model = est.fit(ds)
    # hashes survive; the leaky pivot still goes
    assert model.indices_to_keep == [1, 2, 3]


def test_correlation_exclusion_hashed_text(rng):
    n = 400
    y = rng.integers(0, 2, n).astype(float)
    leaky_hash = y + rng.normal(scale=1e-4, size=n)  # a hash col that leaks
    good = rng.normal(size=n)
    x = np.stack([leaky_hash, good], axis=1)
    metas = [
        _col("desc", parent_type="Text", descriptor_value="hash_0"),
        _col("good"),
    ]
    ds = _vec_ds(x, metas, y)
    lbl, vec = _checker_inputs()
    # excluded from correlation checks -> survives despite the leak
    est = SanityChecker(
        remove_bad_features=True, correlation_exclusion="HashedText"
    ).set_input(lbl, vec)
    assert est.fit(ds).indices_to_keep == [0, 1]
    # default NoExclusion catches it
    est2 = SanityChecker(remove_bad_features=True).set_input(lbl, vec)
    assert est2.fit(ds).indices_to_keep == [1]


# ------------------------------ spans -----------------------------------------
def test_fit_is_broken_down_into_five_spans_under_its_train_fit(rng):
    """``Workflow.train()`` opens ``train/fit`` [stage=SanityChecker]; the
    checker's fit opens its five parts directly under it, on one thread, in
    order, with their attributes, and leaves little of the fit to itself."""
    from transmogrifai_tpu.features import from_dataset
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.telemetry import spans as tspans
    from transmogrifai_tpu.types.columns import column_from_values
    from transmogrifai_tpu.workflow.workflow import Workflow

    n = 300
    ds = Dataset.of({
        "label": column_from_values(T.RealNN, rng.integers(0, 2, n).tolist()),
        "age": column_from_values(T.Real, rng.normal(40.0, 9.0, n).tolist()),
        "city": column_from_values(
            T.PickList, [["ankara", "bern", "cairo"][i % 3] for i in range(n)]
        ),
    })
    label, predictors = from_dataset(ds, response="label")
    checked = label.sanity_check(transmogrify(predictors), remove_bad_features=True)
    tspans.reset_for_tests()
    Workflow().set_result_features(checked).set_input_dataset(ds).train()
    recs = tspans.snapshot_events()
    (fit,) = [r for r in recs if r["name"] == "train/fit"
              and r["args"]["stage"] == "SanityChecker"]
    parts = sorted(
        (r for r in recs if r["name"].startswith("sanity/")),
        key=lambda r: r["ts"],
    )
    assert [r["name"] for r in parts] == [
        "sanity/matrix", "sanity/stats", "sanity/categorical",
        "sanity/rules", "sanity/report",
    ]
    assert all(r["parent"] == fit["id"] and r["trace"] == fit["trace"]
               for r in parts)
    args = {r["name"]: r.get("args", {}) for r in parts}
    d = args["sanity/matrix"]["cols"]
    assert args["sanity/matrix"] == {
        "rows_in": n, "rows": n, "cols": d, "bytes": n * d * 8,
    }
    assert args["sanity/stats"] == {"kind": "pearson"}
    # the city pick list is one group of indicator columns (three cities,
    # other, null)
    assert args["sanity/categorical"]["groups"] >= 1
    assert args["sanity/categorical"]["cols"] >= 3
    assert "sanity/rules" not in {k for k, v in args.items() if v}
    covered = sum(r["dur"] for r in parts)
    assert covered <= fit["dur"] and fit["dur"] - covered < 0.5 * fit["dur"]


def test_sampled_fit_states_rows_in_and_rows(rng):
    """The matrix span says how many rows came in and how many the
    statistics read after the seeded sample."""
    from transmogrifai_tpu.telemetry import spans as tspans

    n = 2000
    y = rng.integers(0, 2, n).astype(float)
    x = np.stack([y * 0.3 + rng.normal(size=n), rng.normal(size=n)], axis=1)
    ds = _vec_ds(x, [_col("f1"), _col("f2")], y)
    lbl, vec = _checker_inputs()
    est = SanityChecker(
        sample_upper_limit=1500, correlation_type="spearman"
    ).set_input(lbl, vec)
    tspans.reset_for_tests()
    est.fit(ds)
    args = {r["name"]: r.get("args", {}) for r in tspans.snapshot_events()}
    assert args["sanity/matrix"] == {
        "rows_in": 2000, "rows": 1500, "cols": 2, "bytes": 1500 * 2 * 8,
    }
    assert args["sanity/stats"] == {"kind": "spearman"}
    # a dense vector with no pick-list groups: nothing categorical
    assert args["sanity/categorical"] == {"groups": 0, "cols": 0}


def test_continuous_label_builds_no_label_one_hot(rng):
    """A continuous label has about as many distinct values as rows: the
    checker skips the categorical stage for it without building a
    [rows, distinct values] label one-hot (the 1,000,000-row regression
    table's would be 8 TB). Peak host allocation stays far under the
    20,000 × 20,000 float64 such a one-hot would take."""
    import tracemalloc

    n = 20_000
    y = rng.gamma(4.0, 10.0, n)
    cat_a = (rng.random(n) < 0.5).astype(float)
    x = np.stack([cat_a, 1.0 - cat_a, y * 0.01 + rng.normal(size=n)], axis=1)
    metas = [
        _col("cat", grouping="cat", indicator_value="A", parent_type="PickList"),
        _col("cat", grouping="cat", indicator_value="B", parent_type="PickList"),
        _col("good"),
    ]
    ds = _vec_ds(x, metas, y)
    lbl, vec = _checker_inputs()
    est = SanityChecker(remove_bad_features=True).set_input(lbl, vec)
    tracemalloc.start()
    try:
        model = est.fit(ds)
        _now, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 200 * 2**20, peak
    assert 2 in model.indices_to_keep
