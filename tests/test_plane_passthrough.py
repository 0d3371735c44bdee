"""The plane reaches the fits where it lies: a row mask that keeps every
row hands the families ``x`` and ``y`` themselves (no gather, the caller's
layout), a column-major plane uploads as its transpose view, and so tree
fit's bin cache hits on a second sweep of the same plane."""
import numpy as np
import pytest

from transmogrifai_tpu.compiler import dispatch
from transmogrifai_tpu.models import gbdt
from transmogrifai_tpu.models import trees as TR
from transmogrifai_tpu.selector import (
    BinaryClassificationModelSelector,
    MultiClassificationModelSelector,
)
from transmogrifai_tpu.selector.combiner import SelectedModelCombiner
from transmogrifai_tpu.selector.model_selector import (
    BINARY_CLASSIFICATION_MODELS,
    keep_rows,
    make_candidates,
)
from transmogrifai_tpu.selector.validators import TrainValidationSplit
from transmogrifai_tpu.telemetry import spans as tspans

N = 400
#: grid values small enough for a test, for whichever family has the key
SMALL = {
    "max_depth": [3], "num_trees": [3], "max_iter": [2], "num_round": [2],
    "min_info_gain": [0.001], "min_instances_per_node": [10],
    "reg_param": [0.01, 0.1], "elastic_net_param": [0.1],
}
ORDERS = {"C": np.ascontiguousarray, "F": np.asfortranarray}


def _table(n=N, seed=0):
    """Non-negative features (NaiveBayes takes no others): four real
    columns and three 0/1 ones."""
    rng = np.random.default_rng(seed)
    x = np.concatenate(
        [np.abs(rng.normal(size=(n, 4))), rng.integers(0, 2, (n, 3))], axis=1
    ).astype(np.float32)
    y = (x[:, 0] + x[:, 4] + 0.5 * rng.normal(size=n) > 1.2).astype(np.float32)
    return x, y


def _selector(names, seed=3):
    models = make_candidates("BinaryClassification", list(names))
    for _est, grid in models:
        grid.update({k: v for k, v in SMALL.items() if k in grid})
    return BinaryClassificationModelSelector(
        seed=seed, models=models, validator=TrainValidationSplit(seed=seed)
    )


def _sweep(names, x, y, mask):
    """(selected model, the sweep's span records) of a fresh selector."""
    gbdt._BINNED_CACHE.clear()
    tspans.reset_for_tests()
    selected = _selector(names).fit_arrays(x, y, mask)
    return selected, list(tspans.snapshot_events())


def _args(records, name):
    return [r.get("args", {}) for r in records if r["name"] == name]


def _told(selected):
    """What a sweep gave, without the uids a fresh selector draws."""
    s = selected.summary
    return {
        "best": (s["bestModelType"], s["bestGrid"]),
        "validation": [
            (r["modelName"], r["grid"], r["metricValues"], r["metricMean"])
            for r in s["validationResults"]
        ],
        "train": s["trainEvaluation"],
        "splitter": s["splitterSummary"],
    }


@pytest.fixture(autouse=True)
def _scatter_histograms_and_a_clean_cache(monkeypatch):
    monkeypatch.setattr(TR, "_resolved_impl", lambda: "scatter")
    yield
    gbdt._BINNED_CACHE.clear()
    dispatch.clear_prefetch()


# ------------------------------------------------ (a) the pass-through
@pytest.mark.parametrize("order", sorted(ORDERS))
@pytest.mark.parametrize("family", sorted(BINARY_CLASSIFICATION_MODELS))
def test_a_mask_of_ones_passes_the_plane_through_and_fits_the_same(
    family, order
):
    x0, y0 = _table()
    x, y = ORDERS[order](x0), y0.copy()
    assert dispatch.host_layout(x) == order
    passed, recs = _sweep([family], x, y, np.ones(N, np.float32))
    # nobody wrote into the caller's arrays, and nobody copied them
    assert np.array_equal(x, x0) and np.array_equal(y, y0)
    assert _args(recs, "selector/row_select") == [
        {"rows_in": N, "rows_out": N, "bytes_copied": 0}
    ]

    # the same rows behind a mask that drops one: the gather path
    extra = np.full((1, x.shape[1]), 7.0, np.float32)
    xg = ORDERS[order](np.concatenate([x0, extra]))
    yg = np.concatenate([y0, [1.0]]).astype(np.float32)
    mask = np.concatenate([np.ones(N), [0.0]]).astype(np.float32)
    gathered, recs = _sweep([family], xg, yg, mask)
    assert _args(recs, "selector/row_select") == [{
        "rows_in": N + 1, "rows_out": N,
        "bytes_copied": N * x.shape[1] * 4 + N * 4,
    }]

    assert _told(passed) == _told(gathered)
    a, b = passed.best_model.get_arrays(), gathered.best_model.get_arrays()
    assert sorted(a) == sorted(b)
    for key in a:
        assert np.array_equal(np.asarray(a[key]), np.asarray(b[key])), key
    # and the fitted winner scores rows of either layout alike
    pa, pb = passed.predict_arrays(x), gathered.predict_arrays(x0)
    for u, v in zip(pa, pb):
        assert (u is None and v is None) or np.array_equal(u, v)


def test_keep_rows_hands_back_the_same_objects_or_a_row_major_gather():
    x, y = _table(50)
    xf = np.asfortranarray(x)
    xk, yk, copied = keep_rows(xf, y, np.ones(50, bool))
    assert xk is xf and yk is y and copied == 0
    keep = np.ones(50, bool)
    keep[[3, 17]] = False
    xk, yk, copied = keep_rows(xf, y, keep)
    assert xk.flags.c_contiguous and xk.shape == (48, 7)
    assert np.array_equal(xk, x[keep]) and np.array_equal(yk, y[keep])
    assert copied == xk.nbytes + yk.nbytes == 48 * 7 * 4 + 48 * 4


@pytest.mark.parametrize("rare", [0, 2])
def test_the_data_cutter_copies_only_where_it_drops_a_label(rare):
    """``rare`` rows of a third label that the cutter's ``min_label_fraction``
    drops: none, and the multiclass sweep runs on ``x`` itself."""
    x, y = _table()
    y = y.copy()
    y[:rare] = 2.0
    models = make_candidates("MultiClassification", ["OpDecisionTreeClassifier"])
    for _est, grid in models:
        grid.update({k: v for k, v in SMALL.items() if k in grid})
    selector = MultiClassificationModelSelector(
        seed=3, models=models, validator=TrainValidationSplit(seed=3)
    )
    selector.splitter.min_label_fraction = 0.05
    gbdt._BINNED_CACHE.clear()
    tspans.reset_for_tests()
    selector.fit_arrays(x, y, np.ones(N, np.float32))
    (sel,) = _args(tspans.snapshot_events(), "selector/row_select")
    kept = N - rare
    assert sel == {
        "rows_in": N, "rows_out": kept,
        "bytes_copied": 0 if not rare else kept * 7 * 4 + kept * 4,
    }
    preps = _args(tspans.snapshot_events(), "tree/bin_prepare")
    assert [a["cache"] for a in preps][0] == "miss"
    (entry,) = gbdt._BINNED_CACHE.values()
    assert (entry[0] is x) == (not rare)


# ------------------------------------------------- (b) the cache's hits
@pytest.mark.parametrize("order", sorted(ORDERS))
def test_a_second_sweep_of_one_plane_hits_the_bin_cache(order):
    x0, y = _table()
    x = ORDERS[order](x0)
    mask = np.ones(N, np.float32)
    first, recs = _sweep(["OpXGBoostClassifier"], x, y, mask)
    assert [a["cache"] for a in _args(recs, "tree/bin_prepare")] == ["miss"]
    assert _args(recs, "tree/upload")[0]["layout"] == order

    def again(plane):
        tspans.reset_for_tests()
        before = gbdt.bin_cache_stats().snapshot()
        selected = _selector(["OpXGBoostClassifier"]).fit_arrays(plane, y, mask)
        recs = list(tspans.snapshot_events())
        after = gbdt.bin_cache_stats().snapshot()
        return selected, recs, {k: after[k] - before[k] for k in (
            "binCacheLookups", "binCacheHits")}, after["binCacheEntries"]

    second, recs, delta, entries = again(x)
    (prep,) = _args(recs, "tree/bin_prepare")
    assert prep["cache"] == "hit" and prep["cache_entries"] == 1
    assert delta == {"binCacheLookups": 1, "binCacheHits": 1} and entries == 1
    # the hit did none of a miss's work, and gave the same sweep
    names = {r["name"] for r in recs}
    assert not names & {"tree/upload", "tree/thresholds", "tree/bin_dispatch"}
    assert _told(second) == _told(first)
    # the contract is identity of an unmutated buffer: an equal copy misses
    _third, recs, delta, entries = again(x.copy(order="K"))
    assert [a["cache"] for a in _args(recs, "tree/bin_prepare")] == ["miss"]
    assert delta == {"binCacheLookups": 1, "binCacheHits": 0} and entries == 2


def test_the_combiners_two_selectors_share_one_cache_entry():
    x, y = _table()
    combiner = SelectedModelCombiner(
        _selector(["OpXGBoostClassifier"]), _selector(["OpGBTClassifier"], 4)
    )
    combiner.set_input(*_input_features())
    gbdt._BINNED_CACHE.clear()
    tspans.reset_for_tests()
    combiner.fit_arrays(np.asfortranarray(x), y, np.ones(N, np.float32))
    recs = list(tspans.snapshot_events())
    assert [a["cache"] for a in _args(recs, "tree/bin_prepare")] == [
        "miss", "hit"]
    assert len(gbdt._BINNED_CACHE) == 1
    assert [a["bytes_copied"] for a in _args(recs, "selector/row_select")] == [
        0, 0]


def _input_features():
    """A response and a vector feature to wire the combiner with (it hands
    them to its two selectors); ``fit_arrays`` is then driven directly."""
    from transmogrifai_tpu import testkit
    from transmogrifai_tpu.features import from_dataset
    from transmogrifai_tpu.ops import transmogrify

    resp, preds = from_dataset(testkit.flagship_dataset(20, 1), response="label")
    return resp, transmogrify(list(preds))


# ---------------------------------------------------- (c) the upload
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_device_f32_of_a_column_major_plane_equals_its_row_major_copys(dtype):
    x = _table(257, seed=5)[0].astype(dtype)
    xf = np.asfortranarray(x)
    assert (dispatch.host_layout(x), dispatch.host_layout(xf)) == ("C", "F")
    # what is one row or one column wide is both orders at once: as before
    assert dispatch.host_layout(xf[:, :1]) == dispatch.host_layout(x[0]) == "C"
    a, b = dispatch.device_f32(x), dispatch.device_f32(xf)
    assert a.shape == b.shape == x.shape and a.dtype == b.dtype == np.float32
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(b), x.astype(np.float32))
    # the prefetch takes the same road, and is picked up
    dispatch.prefetch_f32(xf)
    assert dispatch.prefetch_pending(xf)
    assert np.array_equal(np.asarray(dispatch.device_f32(xf)), np.asarray(a))


@pytest.mark.parametrize("which", ["device", "host"])
def test_tree_upload_names_the_layout_and_the_bins_do_not_depend_on_it(
    which, monkeypatch
):
    monkeypatch.setattr(
        gbdt, "_DEVICE_STATS_MIN_VALUES", 0 if which == "device" else 1 << 62
    )
    x = _table(1_000, seed=7)[0]
    got = {}
    for order, lay in ORDERS.items():
        gbdt._BINNED_CACHE.clear()
        tspans.reset_for_tests()
        thresholds, codes, groups = gbdt.XGBoostClassifier(max_bins=8)._binned(
            lay(x))
        recs = list(tspans.snapshot_events())
        (up,) = _args(recs, "tree/upload")
        assert up["layout"] == order and up["prefetched"] is False
        assert _args(recs, "tree/thresholds")[0]["route"] == which
        got[order] = (thresholds, np.asarray(codes), groups)
    assert np.array_equal(got["C"][0], got["F"][0])
    assert np.array_equal(got["C"][1], got["F"][1])
    for gc, gf in zip(got["C"][2], got["F"][2]):
        assert np.array_equal(np.asarray(gc), np.asarray(gf))
