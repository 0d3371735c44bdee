"""A split's two children share one histogram build (models/trees.py): past
the root a level builds ONE child of every sibling pair, the lighter, and
takes the other as parent - sibling in float32. Trees grown that way must
equal trees whose every node is built: bit for bit where the sums are exact
in float32, split for split where they are real numbers. "Every node
built" is the same program with no room to keep a level's histograms
(``hist_pallas._PARENT_HIST_BUDGET_ELEMS`` replaced, as the ladder tests
replace ``_width_ladder``): the program has no switch for it."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.models import hist_pallas as HP
from transmogrifai_tpu.models import trees as TR

BINS = 8
DEPTH = 7
F32 = np.float32


def _table(kind: str, n: int, seed: int = 3):
    """(binned [N, F], target [N]): ``balanced`` fills every level;
    ``skewed`` has one column that cuts 1% of the rows off at a time (a
    99:1 split at the root, then again under it)."""
    rng = np.random.default_rng(seed)
    f = 6
    x = rng.normal(size=(n, f)).astype(F32)
    if kind == "balanced":
        t = x[:, 0] + x[:, 1] * x[:, 2] + 0.5 * np.sin(3 * x[:, 3])
        t = t + 0.3 * rng.normal(size=n)
    else:
        rare = rng.random(n) < 0.01
        x[:, 0] = np.where(rare, 3.0 + rng.random(n), -1.0)
        t = 8.0 * rare + 0.4 * x[:, 1] + 0.2 * x[:, 2] * x[:, 3]
        t = t + 0.1 * rng.normal(size=n)
    thr = TR.quantile_thresholds(x, BINS)
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
    return binned, t.astype(F32)


def _lanes(n: int, seed: int = 9):
    """Three lanes: every row; a fold (a third of the rows masked out); a
    quarter of the rows under a large child weight, which stops early."""
    rng = np.random.default_rng(seed)
    masks = np.ones((3, n), F32)
    masks[1] = rng.random(n) < 2 / 3
    masks[2, n // 4:] = 0.0
    return masks, np.asarray([1.0, 2.0, 24.0], F32)


def _values(t, values):
    if values == "half":
        # one round of binary:logistic: every sum exact in float32
        return (
            np.where(t > np.median(t), -0.5, 0.5).astype(F32),
            np.full(len(t), 0.25, F32),
        )
    return -t, np.ones(len(t), F32)


def _grow(binned, grad, hess, masks, impl, mcw, depth=DEPTH, **kw):
    k = masks.shape[0]
    fn = jax.jit(functools.partial(
        TR._grow_tree_impl, max_depth=depth, num_bins=BINS, reg_lambda=1.0,
        gamma=0.0, min_child_weight=mcw, hist_impl=impl, **kw,
    ))
    tree, node, slots = fn(
        binned, jnp.asarray(np.stack([grad] * k)),
        jnp.asarray(np.stack([hess] * k)), jnp.asarray(masks),
        jnp.ones((k, binned.shape[1]), jnp.float32),
    )
    return jax.tree.map(np.asarray, (tree, node, slots))


@pytest.fixture()
def every_node_built(monkeypatch):
    """Call it to leave the fits that follow no room for their parents'
    histograms. The literal is read while a program is traced and is no
    key of any program cache: the executable bank is off in these tests
    and the call drops what was traced before it."""
    monkeypatch.setenv("TPTPU_AOT", "0")

    def switch():
        monkeypatch.setattr(HP, "_PARENT_HIST_BUDGET_ELEMS", 0)
        jax.clear_caches()

    return switch


@pytest.fixture()
def interpret_kernels(monkeypatch):
    binloop = HP.BUILDERS["binloop"]
    monkeypatch.setitem(
        HP.BUILDERS, "binloop",
        binloop._replace(build=functools.partial(binloop.build, interpret=True)),
    )


@pytest.fixture()
def builds_seen(monkeypatch):
    """The ``loc`` ([K, N] slot of each row, -1: takes no part) of every
    scatter build a fit makes under ``jax.disable_jit()``, in order."""
    seen = []
    scatter = HP.BUILDERS["scatter"]

    def build(operand, loc, g, h, num_nodes, num_bins, lowp=False,
              exact_hess=False):
        seen.append(np.asarray(loc))
        return scatter.build(operand, loc, g, h, num_nodes, num_bins)

    monkeypatch.setitem(HP.BUILDERS, "scatter", scatter._replace(build=build))
    return seen


def _same_trees(got, want, exact_leaves):
    np.testing.assert_array_equal(got[0].split_feat, want[0].split_feat)
    np.testing.assert_array_equal(got[0].split_bin, want[0].split_bin)
    np.testing.assert_array_equal(got[1], want[1])  # every row's leaf
    if exact_leaves:
        np.testing.assert_array_equal(got[0].leaf_value, want[0].leaf_value)
    else:
        # leaves are segment sums over rows in both programs (not derived):
        # the room is for what the compiler reassociates between two programs
        np.testing.assert_allclose(
            got[0].leaf_value, want[0].leaf_value, rtol=1e-6, atol=1e-7
        )


@pytest.mark.parametrize("capped", [False, True])
@pytest.mark.parametrize("values", ["half", "real"])
@pytest.mark.parametrize("kind", ["balanced", "skewed"])
@pytest.mark.parametrize("impl", ["scatter", "gemm", "pallas"])
def test_subtraction_grows_the_trees_of_direct_builds(
    impl, kind, values, capped, every_node_built, interpret_kernels
):
    # the Pallas kernels take over above 4,096 rows, the GEMM serves below
    n = 4608 if impl == "pallas" else 3072
    binned, t = _table(kind, n)
    grad, hess = _values(t, values)
    masks, mcw = _lanes(n)
    kw = {}
    if capped:
        # the sweep's one program for several depths: a lane past its cap
        # emits no splits
        kw["max_depth_v"] = jnp.asarray([DEPTH, 3, 5], jnp.int32)
    got = _grow(binned, grad, hess, masks, impl, mcw, **kw)
    every_node_built()
    want = _grow(binned, grad, hess, masks, impl, mcw, **kw)

    slots, direct = got[2], want[2]
    assert direct.nodes_derived.sum() == 0 and slots.nodes_derived.sum() > 0
    # the same live nodes either way; past the root half of them built
    nodes = slots.nodes_built + slots.nodes_derived
    np.testing.assert_array_equal(nodes, direct.nodes_built)
    np.testing.assert_array_equal(slots.nodes_derived[1:] * 2, nodes[1:])
    assert (slots.built <= direct.built).all()
    assert (slots.live <= slots.built).all()
    # +-0.5 / 0.25 sums are exact in float32 in any order; the scatter and
    # the GEMM also add a node's rows in the same order in both programs
    _same_trees(got, want, values == "half" or impl != "pallas")


@pytest.mark.parametrize("values", ["half", "real"])
def test_chunk_loop_runs_over_chunks_of_pairs(
    values, monkeypatch, every_node_built
):
    """At 8-slot chunks the deep levels loop: a chunk of 8 PAIR slots
    serves 16 nodes, so the levels run half the chunks."""
    monkeypatch.setattr(HP, "_GEMM_CHUNK_CEIL", 8)
    n = 3072
    binned, t = _table("balanced", n)
    grad, hess = _values(t, values)
    masks, mcw = _lanes(n)
    got = _grow(binned, grad, hess, masks, "gemm", mcw)
    every_node_built()
    want = _grow(binned, grad, hess, masks, "gemm", mcw)
    s, d = got[2], want[2]
    assert (s.built == 8 * s.chunks_run).all() and s.chunks_run.max() > 2
    # cap 128 node slots: 16 chunks of nodes, 8 of pairs
    assert ((s.chunks_run + s.chunks_skipped)[s.built > 0] == 8).all()
    assert ((d.chunks_run + d.chunks_skipped)[d.built > 0] == 16).all()
    assert s.chunks_run.sum() < 0.7 * d.chunks_run.sum()
    _same_trees(got, want, True)


def test_forest_with_node_subsets_is_bit_equal(every_node_built):
    """Spark's forest: bootstrap weights and w*y are integers, the subset
    of a node is drawn from its heap index, which the pairing leaves
    alone."""
    from transmogrifai_tpu.models import gbdt as G

    n = 3000
    binned, t = _table("balanced", n, seed=21)
    y = (t > np.median(t)).astype(F32)
    masks, _ = _lanes(n)
    n_sub = G.resolve_feature_subset("sqrt", binned.shape[1], 2, True)

    def fit():
        trees, slots = TR.fit_forest_batched(
            binned, y, masks, num_trees=3, max_depth=6, num_bins=BINS,
            min_instances=2.0, seed=17, feature_subset=n_sub,
            info_gain_norm=TR.GINI, return_slots=True,
        )
        return jax.tree.map(np.asarray, (trees, slots))

    trees, slots = fit()
    every_node_built()
    ref, ref_slots = fit()
    assert slots.nodes_derived.sum() > 0 == ref_slots.nodes_derived.sum()
    for a, b in zip(trees, ref):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        slots.subset_admitted, ref_slots.subset_admitted
    )
    np.testing.assert_array_equal(slots.subset_pairs, ref_slots.subset_pairs)


def test_seven_class_forest_is_bit_equal(every_node_built):
    """Seven statistic channels (six class indicators and w) go through the
    same subtraction, channel by channel."""
    n, classes = 2600, 7
    rng = np.random.default_rng(12)
    binned, t = _table("balanced", n, seed=31)
    y = np.clip((t - t.min()) / (np.ptp(t) + 1e-6) * classes, 0, classes - 1)
    y = np.floor(y).astype(F32)
    masks = np.stack([np.ones(n), rng.random(n) < 0.75]).astype(F32)

    def fit():
        trees, outs, slots = TR.fit_forest_batched(
            binned, y, jnp.asarray(masks), num_trees=2, max_depth=6,
            num_bins=BINS, min_instances=3.0, min_info_gain=0.001, seed=5,
            lowp=True, num_classes=classes, info_gain_norm=TR.GINI,
            return_outputs=True, return_slots=True,
        )
        return jax.tree.map(np.asarray, (trees, outs, slots))

    trees, outs, slots = fit()
    every_node_built()
    ref, ref_outs, ref_slots = fit()
    assert trees.leaf_value.shape[-1] == classes
    assert slots.nodes_derived.sum() > 0 == ref_slots.nodes_derived.sum()
    for a, b in zip(trees, ref):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(outs, ref_outs)


def test_two_boosting_rounds_under_spark_logloss(every_node_built):
    """Spark's GBT: round 0 fits the +-1 labels (exact sums), round 1 a
    real-valued pseudo-residual: the same splits, and margins that differ
    by what the leaves' division may."""
    n = 3072
    binned, t = _table("balanced", n, seed=41)
    y = (t > np.median(t)).astype(F32)
    masks, _ = _lanes(n)

    def fit():
        trees, margin, slots = TR.fit_boosted_batched(
            binned, y, masks, num_rounds=2, max_depth=6, num_bins=BINS,
            eta=0.1, reg_lambda=0.0, min_child_weight=5.0,
            min_info_gain=0.001, objective="spark:logloss",
            info_gain_norm=TR.VARIANCE, return_slots=True,
        )
        return jax.tree.map(np.asarray, (trees, margin, slots))

    trees, margin, slots = fit()
    every_node_built()
    ref, ref_margin, ref_slots = fit()
    assert slots.nodes_derived.sum() > 0 == ref_slots.nodes_derived.sum()
    np.testing.assert_array_equal(slots.rounds_residual, [0, 3])
    np.testing.assert_array_equal(trees.split_feat, ref.split_feat)
    np.testing.assert_array_equal(trees.split_bin, ref.split_bin)
    np.testing.assert_array_equal(trees.leaf_value[:, 0], ref.leaf_value[:, 0])
    np.testing.assert_allclose(margin, ref_margin, rtol=1e-6, atol=1e-7)


def test_the_built_child_is_the_one_with_the_smaller_hessian_sum(builds_seen):
    """One column cuts 64 rows of 4,096 off to the LEFT (hessian 64 against
    4,032), another 48 of those 64 to the RIGHT: level 1 builds the 64
    rows, level 2 the 16 that stayed left of the second split (16 < 48). The
    rows a build is handed say so (run eagerly: the builder sees values)."""
    n = 4096
    a = (np.arange(n) >= 64).astype(np.int32)          # 0 on the 64 rows
    b = ((np.arange(n) % 4) > 0).astype(np.int32)      # 1 on three of four
    binned = jnp.asarray(np.stack([a, b], axis=1))
    # the root gains most on a; under its left child only b tells rows apart
    target = np.where(a == 1, 0.0, 10.0 + 5.0 * b).astype(F32)
    with jax.disable_jit():
        tree, _node, slots = TR._grow_tree_impl(
            binned, jnp.asarray(-target)[None], jnp.ones((1, n), F32),
            jnp.ones((1, n), F32), jnp.ones((1, 2), F32), max_depth=3,
            num_bins=2, reg_lambda=0.0, min_child_weight=1.0,
            min_info_gain=1e-6, hist_impl="scatter",
        )
    feats = np.asarray(tree.split_feat)[0]
    assert feats[0, 0] == 0 and feats[1, 0] == 1 and feats[1, 1] == -1
    root, level1, level2 = builds_seen[:3]
    assert (root == 0).all()
    # the 64 rows left of the root's split, at pair slot 0
    np.testing.assert_array_equal(np.flatnonzero(level1[0] >= 0),
                                  np.arange(64))
    # under node (1, 0): b == 0 goes left (16 rows), b == 1 right (48)
    np.testing.assert_array_equal(
        np.flatnonzero(level2[0] >= 0), np.arange(0, 64, 4)
    )
    np.testing.assert_array_equal(np.asarray(slots.nodes_built), [1, 1, 1])
    np.testing.assert_array_equal(np.asarray(slots.nodes_derived), [0, 1, 1])


def test_ties_build_the_left_child(builds_seen):
    """Two children of equal hessian sums: the left one is built."""
    n = 1024
    a = (np.arange(n) % 2).astype(np.int32)
    binned = jnp.asarray(a[:, None])
    with jax.disable_jit():
        TR._grow_tree_impl(
            binned, jnp.asarray(-a.astype(F32))[None], jnp.ones((1, n), F32),
            jnp.ones((1, n), F32), jnp.ones((1, 1), F32), max_depth=2,
            num_bins=2, reg_lambda=0.0, min_child_weight=1.0,
            hist_impl="scatter",
        )
    np.testing.assert_array_equal(np.flatnonzero(builds_seen[1][0] >= 0),
                                  np.arange(0, n, 2))


def test_a_child_without_rows_sends_the_level_to_direct_builds(
    every_node_built
):
    """Pair j's children are the compact slots 2j and 2j + 1 only if both
    hold a row. Under a negative ``gamma`` and no child weight a split with
    an EMPTY left child is taken (column 0 has no code 0; the target is
    flat, so every candidate gains -gamma and the first wins): the level
    below has one live node for its one pair, says so, and builds every
    node (a masked row is routed too, so lane 1 does the same). The trees
    are those of a fit that never subtracts."""
    n = 2048
    rng = np.random.default_rng(2)
    binned = jnp.asarray(np.stack(
        [rng.integers(1, 3, size=n), rng.integers(0, 4, size=n)], axis=1
    ).astype(np.int32))
    masks = np.stack([np.ones(n), rng.random(n) < 0.5]).astype(F32)

    def grow():
        fn = jax.jit(functools.partial(
            TR._grow_tree_impl, max_depth=4, num_bins=4, reg_lambda=1.0,
            gamma=-1.0, min_child_weight=0.0, hist_impl="scatter",
        ))
        out = fn(
            binned, jnp.ones((2, n), F32), jnp.ones((2, n), F32),
            jnp.asarray(masks), jnp.ones((2, 2), F32),
        )
        return jax.tree.map(np.asarray, out)

    got = grow()
    slots = got[2]
    # every row goes right at every level: node 2^d - 1 of level d
    chain = got[0].split_feat[:, np.arange(4), 2 ** np.arange(4) - 1]
    assert (chain == 0).all(), "the empty-left split"
    assert (got[0].split_feat >= 0).sum() == 2 * 4
    # the root is built; every level below holds ONE node a lane (the right
    # child), not the pair's two, and is built whole
    np.testing.assert_array_equal(slots.nodes_built, [2, 2, 2, 2])
    np.testing.assert_array_equal(slots.nodes_derived, [0, 0, 0, 0])
    every_node_built()
    _same_trees(got, grow(), True)


def test_counts_of_a_hand_checkable_tree():
    """Two lanes over four equal groups of rows told apart by two 2-bin
    columns; lane 1 sees only the rows of the first column's 0. Lane 0:
    root, 2 nodes, 4 nodes; lane 1: root, 2 nodes (split on the second
    column), then nothing to gain. ``nodes_built + nodes_derived`` is the
    live nodes of both lanes, and every node under a root is one of a
    pair."""
    n, depth = 4096, 5
    rng = np.random.default_rng(5)
    a = rng.integers(0, 2, size=n)
    b = rng.integers(0, 2, size=n)
    binned = jnp.asarray(np.stack([a, b], axis=1).astype(np.int32))
    target = (2.0 * a + b).astype(F32)
    masks = np.stack([np.ones(n), a == 0]).astype(F32)
    fn = jax.jit(functools.partial(
        TR._grow_tree_impl, max_depth=depth, num_bins=2, reg_lambda=0.0,
        gamma=0.0, min_child_weight=1.0, min_info_gain=1e-6,
        hist_impl="scatter",
    ))
    _tree, _node, slots = fn(
        binned, jnp.asarray(np.stack([-target] * 2)), jnp.ones((2, n), F32),
        jnp.asarray(masks), jnp.ones((2, 2), F32),
    )
    slots = jax.tree.map(np.asarray, slots)
    np.testing.assert_array_equal(slots.nodes_built, [2, 2, 2, 0, 0])
    np.testing.assert_array_equal(slots.nodes_derived, [0, 2, 2, 0, 0])
    # the kernel's node axis: the widest lane's pairs, at the floor's width
    np.testing.assert_array_equal(slots.live, [1, 1, 2, 0, 0])
    np.testing.assert_array_equal(slots.built, [32, 32, 32, 0, 0])


def test_derived_histograms_are_within_rounding_of_direct_ones():
    """Real-valued targets: the heavier child as parent - lighter differs
    from its own direct build by rounding of the parent's scale, under 1e-5
    of the child's own largest cell when it IS the heavier one."""
    n, f = 20000, 5
    rng = np.random.default_rng(8)
    codes = jnp.asarray(rng.integers(0, BINS, size=(n, f)).astype(np.int32))
    g = jnp.asarray(rng.normal(size=(1, n)).astype(F32))
    h = jnp.ones((1, n), jnp.float32)
    right = rng.random(n) < 0.9  # the right child is the heavier
    build = functools.partial(
        HP.build_histogram_scatter_batched, codes, grad=g, hess=h,
        num_nodes=1, num_bins=BINS,
    )
    parent = build(node=jnp.zeros((1, n), jnp.int32))
    left = build(node=jnp.asarray(np.where(right, -1, 0)[None]))
    direct = np.asarray(build(node=jnp.asarray(np.where(right, 0, -1)[None])))
    rows = TR.sibling_rows(
        left.reshape(1, -1), parent.reshape(1, -1),
        jnp.zeros((1, 1), bool), jnp.ones((1, 2), bool),
    )
    np.testing.assert_array_equal(np.asarray(rows[0]), np.asarray(left).ravel())
    err = np.abs(np.asarray(rows[1]) - direct.ravel()).max()
    assert 0 < err <= 1e-5 * np.abs(direct).max()
    # a slot no node lives in comes out empty, whatever its parent row held
    dead = TR.sibling_rows(
        left.reshape(1, -1), parent.reshape(1, -1),
        jnp.ones((1, 1), bool), jnp.asarray([[True, False]]),
    )
    assert not np.asarray(dead[1]).any()
    np.testing.assert_array_equal(
        np.asarray(dead[0]), np.asarray(parent - left).ravel()
    )


# (lanes, max_slots, channels, max_parents) at the cells' 9,774 cells a node
CELL_GROUPS = [(55, 2), (302, 32)]
KEPT = {
    "depth_10_two_channels": ((4, 1 << 10, 2, 256), 256),     # 79 MB
    "depth_12_two_channels": ((4, 1 << 12, 2, 1024), 1024),   # 317 MB
    "depth_12_seven_classes": ((4, 1 << 12, 7, 1024), 1024),  # 1.11 GB
    "eight_such_lanes": ((8, 1 << 12, 7, 1024), 0),           # 2.2 GB: no
    "depth_1": ((4, 2, 2, 0), 0),
}


@pytest.mark.parametrize("case", sorted(KEPT))
def test_plan_keeps_the_parents_that_fit_its_budget(case):
    (lanes, max_slots, channels, max_parents), want = KEPT[case]
    plan = HP.histogram_plan(
        "pallas", 1_002_701, lanes, CELL_GROUPS, max_slots,
        stat_channels=channels, lowp=channels > 2, max_parents=max_parents,
    )
    assert plan.parent_slots == want


@pytest.mark.parametrize(
    "depth,rows,sharded,want",
    [(10, 1 << 20, False, 256), (12, 1 << 20, False, 1024),
     (3, 1 << 20, False, 2), (1, 1 << 20, False, 0),
     # few rows: a level holds no more nodes than rows
     (12, 100, False, 128),
     # the sharded path builds every node
     (10, 1 << 20, True, 0)],
)
def test_layout_asks_for_the_widest_level_that_has_a_next(
    depth, rows, sharded, want
):
    _cap, plan, _ladder = TR._slot_layout(
        "scatter", rows, 2, [(5, BINS)], depth, sharded=sharded
    )
    assert plan.parent_slots == want


def test_a_fit_over_the_budget_builds_every_node_and_says_so(monkeypatch):
    from transmogrifai_tpu.models import gbdt
    from transmogrifai_tpu.telemetry import spans as tspans

    n = 2048
    binned, t = _table("balanced", n)
    y = (t > np.median(t)).astype(F32)

    monkeypatch.setenv("TPTPU_AOT", "0")

    def fit():
        jax.clear_caches()
        tspans.reset_for_tests()
        _trees, margin, slots = TR.fit_boosted_batched(
            binned, y, np.ones((2, n), F32), num_rounds=1, max_depth=6,
            num_bins=BINS, eta=0.3, return_slots=True,
        )
        gbdt.await_stack_outputs({"outputs": margin, "hist_slots": slots})
        (rec,) = [
            r["args"] for r in tspans.snapshot_events()
            if r["name"] == "tree/await_outputs"
        ]
        return rec

    kept = fit()
    assert kept["nodes_derived"] > 0
    assert kept["nodes_built"] - kept["nodes_derived"] == 2  # the two roots
    # room for 2 lanes x 15 parents x 6 x 8 cells x 2 channels, not for 16
    cells = 2 * 6 * BINS * 2
    monkeypatch.setattr(HP, "_PARENT_HIST_BUDGET_ELEMS", 16 * cells - 1)
    direct = fit()
    assert direct["nodes_derived"] == 0
    assert direct["nodes_built"] == kept["nodes_built"] + kept["nodes_derived"]
    assert direct["slots_built"] >= kept["slots_built"]
