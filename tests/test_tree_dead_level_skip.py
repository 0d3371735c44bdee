"""Levels where no lane can split skip ``tree/partition``'s two row-wide
passes (models/trees.py, ``_compact`` and ``_route``): the compaction of
the rows' nodes once the level above split nothing, the routing of the
rows once this level splits nothing. What the skipped pass would give is
returned in its place, so the trees, every row's leaf slot and the outputs
are those of a fit that runs every pass; ``HistSlots.partition_run`` /
``partition_skipped`` count the passes that ran and those left out, per
tree and level."""
import functools
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.models import trees as TR

F32 = np.float32
BINS = 8


def _stump_table(n: int, seed: int = 4):
    """(binned [N, 3], a [N]): column 0 is the 2-bin ``a`` that tells the
    label apart alone; columns 1-2 are noise. Split on ``a`` every child is
    pure, so nothing past the root gains."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 2, size=n)
    binned = np.stack(
        [a, rng.integers(0, BINS, size=n), rng.integers(0, BINS, size=n)],
        axis=1,
    ).astype(np.int32)
    return jnp.asarray(binned), a


def _masks(n: int, seed: int = 9):
    rng = np.random.default_rng(seed)
    return np.stack([np.ones(n), rng.random(n) < 2 / 3]).astype(F32)


@pytest.fixture()
def every_pass_runs(monkeypatch):
    """Call it to make the fits traced after it take the computing branch
    of every ``lax.cond`` the grower itself issues (its own skips, the
    occupancy and early-exit branches as well): what the program computed
    before any level skipped its row-wide passes. The executable bank is
    off and the call drops what was traced before it."""
    monkeypatch.setenv("TPTPU_AOT", "0")
    cond = jax.lax.cond

    def computing(pred, true_fun, false_fun, *operands, **kw):
        if sys._getframe(1).f_globals.get("__name__") == TR.__name__:
            return true_fun(*operands)
        return cond(pred, true_fun, false_fun, *operands, **kw)

    def switch():
        monkeypatch.setattr(jax.lax, "cond", computing)
        jax.clear_caches()

    return switch


def _forest(binned, y, depth, classes=0):
    trees, outs, slots = TR.fit_forest_batched(
        binned, jnp.asarray(y, jnp.float32), _masks(binned.shape[0]),
        num_trees=2, max_depth=depth, num_bins=BINS, min_instances=1.0,
        min_info_gain=0.001, seed=11, num_classes=classes,
        info_gain_norm=TR.GINI, return_outputs=True, return_slots=True,
    )
    return jax.tree.map(np.asarray, (trees, outs, slots))


# the binary forest (a) and a seven-class one (d): classes 3 and 5 only,
# each a child of the root's split
STUMPS = {"binary": (0, lambda a: a), "seven_classes": (7, lambda a: 5 - 2 * a)}


@pytest.mark.parametrize("label", sorted(STUMPS))
def test_a_stump_at_depth_12_is_the_stump_at_depth_2(label):
    classes, of = STUMPS[label]
    binned, a = _stump_table(1024)
    y = of(a)
    deep, deep_outs, slots = _forest(binned, y, 12, classes)
    flat, flat_outs, flat_slots = _forest(binned, y, 2, classes)

    feats, bins = deep.split_feat, deep.split_bin  # [K, T, 12, 4096]
    assert (feats[:, :, 0, 0] == 0).all(), "every root splits on a"
    np.testing.assert_array_equal(feats[:, :, :2, :4], flat.split_feat)
    np.testing.assert_array_equal(bins[:, :, :2, :4], flat.split_bin)
    assert (feats[:, :, 1:] == -1).all() and (bins[:, :, 1:] == 0).all()
    # dead rows continue left: leaf s at depth 2 is leaf s << 10 at depth 12
    np.testing.assert_array_equal(
        deep.leaf_value[:, :, np.arange(4) << 10], flat.leaf_value
    )
    np.testing.assert_array_equal(deep_outs, flat_outs)

    # per tree: level 0 runs both passes, level 1 compacts and splits
    # nothing, every level under it skips both
    want = np.zeros(12, np.int32)
    want[:2] = (2, 1)
    np.testing.assert_array_equal(slots.partition_run, [want, want])
    np.testing.assert_array_equal(slots.partition_skipped, [2 - want] * 2)
    assert (slots.partition_run.sum(axis=1) == 3).all()
    assert (slots.partition_skipped.sum(axis=1) == 21).all()
    np.testing.assert_array_equal(flat_slots.partition_run, [[2, 1]] * 2)
    # the histogram counts see no live node past level 1 either way (the
    # slot width a build is made at is each program's own)
    for field in ("live", "nodes_built", "nodes_derived"):
        got = getattr(slots, field)
        np.testing.assert_array_equal(got[:, :2], getattr(flat_slots, field))
        assert (got[:, 2:] == 0).all(), field
    assert (slots.built[:, 2:] == 0).all()


@pytest.mark.parametrize("label", sorted(STUMPS))
def test_a_stump_grows_what_every_pass_computing_grows(label, every_pass_runs):
    classes, of = STUMPS[label]
    binned, a = _stump_table(1024, seed=6)
    got = _forest(binned, of(a), 12, classes)
    every_pass_runs()
    want = _forest(binned, of(a), 12, classes)
    for x, w in zip(got[0], want[0]):
        np.testing.assert_array_equal(x, w)
    np.testing.assert_array_equal(got[1], want[1])
    # (the counts read the predicates, whichever branch was traced)
    assert got[2].partition_skipped.sum() == 2 * 21


def _two_lanes(n: int = 2048, seed: int = 5):
    """Lane 0 fits ``a`` alone: a stump, dead at level 1. Lane 1 fits
    4a + 2b + c: three levels of splits, dead at level 3."""
    rng = np.random.default_rng(seed)
    cols = rng.integers(0, 2, size=(n, 3))
    a, b, c = cols.T
    grad = -np.stack([a, 4 * a + 2 * b + c]).astype(F32)
    return jnp.asarray(cols.astype(np.int32)), grad


def _grow(binned, grad, depth=6):
    k = grad.shape[0]
    fn = jax.jit(functools.partial(
        TR._grow_tree_impl, max_depth=depth, num_bins=2, reg_lambda=0.0,
        gamma=0.0, min_child_weight=1.0, min_info_gain=1e-6,
        hist_impl="scatter",
    ))
    out = fn(
        binned, jnp.asarray(grad), jnp.ones((k, binned.shape[0]), F32),
        jnp.ones((k, binned.shape[0]), F32),
        jnp.ones((k, binned.shape[1]), F32),
    )
    return jax.tree.map(np.asarray, out)


@pytest.mark.parametrize("lane", [0, 1])
def test_mixed_lanes_skip_only_once_the_last_lane_dies(lane):
    binned, grad = _two_lanes()
    tree, node, slots = _grow(binned, grad)
    # lane 1 splits at levels 0-2: nothing is skipped before level 3
    np.testing.assert_array_equal(slots.partition_run, [2, 2, 2, 1, 0, 0])
    np.testing.assert_array_equal(slots.partition_skipped, [0, 0, 0, 1, 2, 2])
    alone_tree, alone_node, alone_slots = _grow(binned, grad[lane:lane + 1])
    for x, w in zip(tree, alone_tree):
        np.testing.assert_array_equal(x[lane], w[0])
    np.testing.assert_array_equal(node[lane], alone_node[0])
    splits = (tree.split_feat[lane] >= 0).sum(axis=1)
    np.testing.assert_array_equal(
        splits, [[1, 0, 0, 0, 0, 0], [1, 2, 4, 0, 0, 0]][lane]
    )
    np.testing.assert_array_equal(
        alone_slots.partition_run,
        [[2, 1, 0, 0, 0, 0], [2, 2, 2, 1, 0, 0]][lane],
    )


def test_mixed_lanes_grow_what_every_pass_computing_grows(every_pass_runs):
    binned, grad = _two_lanes(seed=8)
    got = _grow(binned, grad)
    every_pass_runs()
    want = _grow(binned, grad)
    for x, w in zip(got[:2], want[:2]):
        jax.tree.map(np.testing.assert_array_equal, x, w)
    assert got[2].partition_skipped.sum() == 5


def _bushy(n: int = 3072, seed: int = 3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 6)).astype(F32)
    t = x[:, 0] + x[:, 1] * x[:, 2] + 0.5 * np.sin(3 * x[:, 3])
    t = t + 0.3 * rng.normal(size=n)
    thr = TR.quantile_thresholds(x, BINS)
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
    return binned, (t > np.median(t)).astype(F32)


@pytest.mark.parametrize(
    "objective,kw",
    [
        # Spark's GBT: first order, variance gain, row-count stops
        ("spark:logloss", dict(reg_lambda=0.0, min_child_weight=5.0,
                               min_info_gain=0.001,
                               info_gain_norm=TR.VARIANCE)),
        # XGBoost's Newton trees
        ("binary:logistic", dict()),
    ],
)
def test_boosting_on_bushy_data_skips_nothing(objective, kw):
    binned, y = _bushy()
    trees, _margin, slots = TR.fit_boosted_batched(
        binned, y, _masks(binned.shape[0]), num_rounds=2, max_depth=6,
        num_bins=BINS, eta=0.1, objective=objective, return_slots=True, **kw,
    )
    slots = jax.tree.map(np.asarray, slots)
    # every level of both rounds splits somewhere, so routes its rows
    assert (np.asarray(trees.split_feat)[..., -1, :] >= 0).any(axis=-1).all()
    assert slots.partition_skipped.shape == (2, 6)
    assert (slots.partition_skipped == 0).all()
    assert (slots.partition_run == 2).all()


def test_await_outputs_lands_the_pass_counts_on_span_and_ledger():
    from transmogrifai_tpu.models import gbdt
    from transmogrifai_tpu.telemetry import export as texport
    from transmogrifai_tpu.telemetry import spans as tspans

    binned, a = _stump_table(1024)
    trees, outs, slots = TR.fit_forest_batched(
        binned, jnp.asarray(a, jnp.float32), _masks(1024), num_trees=2,
        max_depth=6, num_bins=BINS, min_info_gain=0.001, seed=11,
        info_gain_norm=TR.GINI, return_outputs=True, return_slots=True,
    )
    tspans.reset_for_tests()
    before = TR.hist_slot_stats().snapshot()
    gbdt.await_stack_outputs({"outputs": outs, "hist_slots": slots})
    (rec,) = [
        r["args"] for r in tspans.snapshot_events()
        if r["name"] == "tree/await_outputs"
    ]
    # two stumps of six levels: 3 passes of 12 each
    assert (rec["partition_run"], rec["partition_skipped"]) == (6, 18)
    now = TR.hist_slot_stats().snapshot()
    assert now["partitionPassesRun"] - before["partitionPassesRun"] == 6
    assert (
        now["partitionPassesSkipped"] - before["partitionPassesSkipped"] == 18
    )
    text = texport.render_prometheus()
    assert (
        f"tptpu_tree_partition_passes_skipped {now['partitionPassesSkipped']}"
        in text
    )


def test_the_sharded_path_runs_every_pass():
    """Compaction psums its occupancy over the mesh, so no pass may sit
    under a data-dependent branch there: a stump runs both passes at every
    level and grows the single device's trees."""
    from transmogrifai_tpu.parallel import make_mesh
    from transmogrifai_tpu.telemetry import spans as tspans

    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    binned, a = _stump_table(1024)
    kw = dict(
        num_trees=2, max_depth=4, num_bins=BINS, min_info_gain=0.001,
        seed=11, info_gain_norm=TR.GINI, return_outputs=True,
    )
    target = jnp.asarray(a, jnp.float32)
    tspans.reset_for_tests()
    trees, outs = TR.fit_forest_batched(
        binned, target, _masks(1024), mesh=make_mesh(n_data=2, n_model=1),
        **kw,
    )
    (rec,) = [
        r["args"] for r in tspans.snapshot_events()
        if r["name"] == "tree/await_outputs"
    ]
    assert (rec["partition_run"], rec["partition_skipped"]) == (2 * 4 * 2, 0)
    single, single_outs = TR.fit_forest_batched(
        binned, target, _masks(1024), **kw
    )
    for x, w in zip(trees, single):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(outs), np.asarray(single_outs))
