"""The tree fit's bin preparation by its device route
(``trees.bin_column_stats`` + ``trees.thresholds_from_order_stats``) against
its host route (``trees.quantile_thresholds``, ``gbdt._feature_bin_groups``):
equal bit for bit, and chosen from the input alone."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import gbdt
from transmogrifai_tpu.models import trees as TR
from transmogrifai_tpu.telemetry import spans as tspans

F = 70  # two whole chunks of 32 columns and a clamped one


def _bits(a):
    return np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)


def _hard_table(n: int, seed: int) -> np.ndarray:
    """Columns that a sort, a compare or an interpolation could get wrong."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, F)).astype(np.float32)
    x[:, 1] = rng.integers(0, 2, n)                 # 0/1 indicator
    x[:, 2] = 3.0                                   # constant
    x[:, 3] = np.round(x[:, 3])                     # ties, and -0.0 among +0.0
    x[:, 4] *= np.float32(1e30)                     # near the float32 range
    x[:, 5] *= np.float32(1e-42)                    # subnormals
    x[::3, 6], x[1::3, 6] = np.inf, -np.inf         # inf - inf edges are NaN
    x[:, 7] = -0.0                                  # one sign of zero
    x[:, 8] = rng.integers(0, 2, n)                 # 0/1 but for a subnormal
    x[0, 8] = np.float32(1e-42)
    x[:, 9] = rng.integers(0, 2, n)                 # 0/1 and non-finite
    x[0, 9] = np.inf
    x[:, 10] = rng.integers(-3, 4, n)               # few values, many ties
    x[:, 11] = np.where(rng.random(n) < 0.5, 0.0, -0.0)
    x[:, 69] = rng.integers(0, 2, n)                # in the clamped chunk
    return x


def _device_route(x, max_bins):
    stats, binary, any_nan = jax.device_get(
        TR.bin_column_stats(jnp.asarray(x), max_bins=max_bins)
    )
    thr = TR.thresholds_from_order_stats(stats, x.shape[0])
    return thr, binary, bool(any_nan)


@pytest.mark.parametrize("max_bins", [2, 8, 32])
@pytest.mark.parametrize("n", [1, 2, 33, 1_000, 4_097])
def test_device_thresholds_equal_numpys_bit_for_bit(n, max_bins):
    x = _hard_table(n, seed=n + max_bins)
    thr, _binary, any_nan = _device_route(x, max_bins)
    assert not any_nan
    assert thr.shape == (F, max_bins - 1) and thr.dtype == np.float32
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # inf - inf
        ref = np.quantile(x.astype(np.float64), qs, axis=0).T.astype(np.float32)
        host = TR.quantile_thresholds(x, max_bins)
    # a zero edge is +0.0 by either route (trees._positive_zeros): among
    # mixed zeros numpy's own sign follows its partition's internals
    assert np.array_equal(_bits(thr), _bits(ref + np.float32(0.0)))
    assert np.array_equal(_bits(thr), _bits(host))
    assert not np.signbit(thr[thr == 0]).any()
    if n >= 33 and max_bins >= 8:
        assert np.isnan(thr[6]).any(), "numpy's inf - inf, kept"


@pytest.mark.parametrize("n", [1, 33, 4_097])
def test_device_column_flags_equal_the_hosts(n):
    x = _hard_table(n, seed=n)
    _thr, binary, _nan = _device_route(x, 32)
    groups = gbdt._feature_bin_groups(x)
    narrow = np.zeros(F, bool)
    narrow[np.asarray(groups[0])] = True
    assert np.array_equal(binary, narrow)
    assert narrow[[1, 7, 9, 11, 69]].all() and not narrow[8], "by bits, not =="
    mine = gbdt._groups_from_flags(binary)
    for a, b in zip(mine, groups):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert gbdt._groups_from_flags(np.zeros(F, bool)) is None


def test_nan_is_reported_and_not_sorted_into_an_answer():
    x = _hard_table(33, seed=5)
    x[4, 12] = np.nan
    assert _device_route(x, 8)[2]


def _fit_table(n=600, seed=0):
    rng = np.random.default_rng(seed)
    x = np.concatenate(
        [rng.normal(size=(n, 4)), rng.integers(0, 2, (n, 3))], axis=1
    ).astype(np.float32)
    y = (x[:, 0] + x[:, 4] + rng.normal(size=n) > 0.5).astype(np.float64)
    return x, y


@pytest.fixture
def route(monkeypatch):
    """Force the size rule one way: ``route("device")`` / ``route("host")``."""

    def choose(which):
        monkeypatch.setattr(
            gbdt, "_DEVICE_STATS_MIN_VALUES", 0 if which == "device" else 1 << 62
        )
        gbdt._BINNED_CACHE.clear()

    yield choose
    gbdt._BINNED_CACHE.clear()


def test_binned_and_the_fits_are_the_same_by_either_route(route):
    x, y = _fit_table()
    mask = np.ones(len(y), np.float32)
    got = {}
    for which in ("device", "host"):
        route(which)
        tspans.reset_for_tests()
        thresholds, codes, groups = gbdt.XGBoostClassifier(max_bins=8)._binned(x)
        (span,) = [r for r in tspans.snapshot_events()
                   if r["name"] == "tree/thresholds"]
        assert span["args"]["route"] == which
        boosted = gbdt.XGBoostClassifier(
            num_round=3, max_depth=3, max_bins=8
        ).fit_arrays(x, y, mask)
        forest = gbdt.RandomForestClassifier(
            num_trees=3, max_depth=4, max_bins=8
        ).fit_arrays(x, y, mask)
        got[which] = (
            thresholds, np.asarray(codes), [np.asarray(g) for g in groups],
            [np.asarray(a) for a in jax.tree.leaves(boosted.trees)],
            [np.asarray(a) for a in jax.tree.leaves(forest.trees)],
        )
    dev, host = got["device"], got["host"]
    assert np.array_equal(_bits(dev[0]), _bits(host[0]))
    assert np.array_equal(dev[1], host[1])
    for part in (2, 3, 4):
        assert len(dev[part]) == len(host[part]) > 0
        for a, b in zip(dev[part], host[part]):
            # an empty leaf's value is NaN by either route
            assert np.array_equal(a, b, equal_nan=a.dtype.kind == "f")


def _one_miss(x, max_bins=8):
    """(the miss's ``tree/thresholds`` attributes, the ledger's change)."""
    gbdt._BINNED_CACHE.clear()
    tspans.reset_for_tests()
    before = gbdt.bin_cache_stats().snapshot()
    gbdt.XGBoostClassifier(max_bins=max_bins)._binned(x)
    now = gbdt.bin_cache_stats().snapshot()
    (span,) = [r for r in tspans.snapshot_events()
               if r["name"] == "tree/thresholds"]
    delta = {k: now[k] - before[k] for k in ("thresholdsDevice", "thresholdsHost")}
    return span["args"], delta


def test_a_large_plane_takes_the_device_route(route):
    route("device")
    args, delta = _one_miss(_fit_table(seed=3)[0])
    assert args["route"] == "device" and "why" not in args
    assert delta == {"thresholdsDevice": 1, "thresholdsHost": 0}


def _mesh_of_one():
    from transmogrifai_tpu.parallel.mesh import make_mesh

    return make_mesh(n_data=1, n_model=1, devices=jax.devices()[:1])


@pytest.mark.parametrize("why", ["small", "nan", "mesh"])
def test_host_route_and_its_reason(route, why):
    from transmogrifai_tpu.parallel.mesh import use_execution_mesh

    x = _fit_table(seed=4)[0]
    if why == "small":
        # the rule as it ships: the suite's planes are far under it
        assert x.size < gbdt._DEVICE_STATS_MIN_VALUES
        gbdt._BINNED_CACHE.clear()
    else:
        route("device")
    if why == "nan":
        x[7, 2] = np.nan
    with use_execution_mesh(_mesh_of_one() if why == "mesh" else None):
        args, delta = _one_miss(x)
    assert (args["route"], args["why"]) == ("host", why)
    assert delta == {"thresholdsDevice": 0, "thresholdsHost": 1}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        want = TR.quantile_thresholds(x, 8)
    (entry,) = gbdt._BINNED_CACHE.values()
    assert np.array_equal(_bits(entry[1]), _bits(want))


def test_the_program_holds_no_float64_and_no_callback():
    from transmogrifai_tpu.analysis.program import audit_programs

    report = audit_programs(names=["bin_column_stats"])
    assert [f.render() for f in report.findings] == []
    text = TR.bin_column_stats.lower(
        jax.ShapeDtypeStruct((4_097, F), jnp.float32), max_bins=32
    ).as_text()
    assert "f64" not in text and "callback" not in text
    assert text.count("stablehlo.sort") == 1, "one sort, inside the chunk loop"
