"""The histogram width ladder (models/trees.py): a tree level's histograms
are built at the smallest rung that holds its live node slots — its sibling
PAIRS where the fit keeps the level above's histograms and builds one child
of each pair (tests/test_hist_sibling_subtraction.py), its nodes where it
builds every node. Trees grown with the ladder must equal trees grown at
the full chunk width, on every histogram implementation, and the counts the
fit hands back must say what was built."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.models import hist_pallas as HP
from transmogrifai_tpu.models import trees as TR

BINS = 8
DEPTH = 9  # cap 512 slots: a real-valued target fills three rungs and more


def _table(kind: str, n: int, seed: int = 0):
    """(binned [N, F], target [N]) whose tree is balanced (every level
    fills), skewed (one deep thin branch: deep levels hold few live
    nodes), or short (the signal is one split deep)."""
    rng = np.random.default_rng(seed)
    f = 5
    x = rng.normal(size=(n, f)).astype(np.float32)
    if kind == "balanced":
        t = x[:, 0] + x[:, 1] * x[:, 2] + 0.5 * np.sin(3 * x[:, 3])
        t = t + 0.3 * rng.normal(size=n)
    elif kind == "skewed":
        # 95% of the rows are identical: only the rest can keep splitting
        x[: int(n * 0.95)] = 0.0
        t = x[:, 0] * x[:, 1] + x[:, 2]
    else:
        t = (x[:, 0] > 0.2).astype(np.float32) * 2.0
    thr = TR.quantile_thresholds(x, BINS)
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
    return binned, t.astype(np.float32)


def _grow(binned, grad, hess, row_mask, impl):
    k = grad.shape[0]
    fn = jax.jit(functools.partial(
        TR._grow_tree_impl, max_depth=DEPTH, num_bins=BINS,
        reg_lambda=1.0, gamma=0.0,
        min_child_weight=np.asarray([1.0, 4.0], np.float32)[:k],
        hist_impl=impl,
    ))
    feat_mask = jnp.ones((k, binned.shape[1]), jnp.float32)
    tree, _node, slots = fn(
        binned, jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(row_mask),
        feat_mask,
    )
    return jax.tree.map(np.asarray, (tree, slots))


@pytest.fixture(params=["pairs", "nodes"])
def slots_hold(request, monkeypatch):
    """What a build's slots hold: sibling pairs (the fit keeps its parents'
    histograms), or nodes (no room for them: every node is built)."""
    if request.param == "nodes":
        monkeypatch.setattr(HP, "_PARENT_HIST_BUDGET_ELEMS", 0)
    return request.param


@pytest.fixture()
def interpret_kernels(monkeypatch):
    """The bin-loop builder in interpret mode, where the tree grower looks
    it up (CPU has no Mosaic)."""
    binloop = HP.BUILDERS["binloop"]
    monkeypatch.setitem(
        HP.BUILDERS, "binloop",
        binloop._replace(build=functools.partial(binloop.build, interpret=True)),
    )


@pytest.mark.parametrize("values", ["half", "real"])
@pytest.mark.parametrize("kind", ["balanced", "skewed", "short"])
@pytest.mark.parametrize("impl", ["scatter", "gemm", "pallas"])
def test_ladder_grows_the_full_width_trees(
    impl, kind, values, monkeypatch, interpret_kernels
):
    # the Pallas kernels take over above 4,096 rows, the GEMM serves below
    n = 4608 if impl == "pallas" else 3072
    binned, t = _table(kind, n)
    if values == "half":
        # one boosting round of binary:logistic: g = +-0.5, h = 0.25
        grad = np.where(t > np.median(t), -0.5, 0.5).astype(np.float32)
        hess = np.full(n, 0.25, np.float32)
    else:
        grad, hess = -t, np.ones(n, np.float32)
    # lane 1 stops early: a quarter of the rows and a larger child weight
    row_mask = np.ones((2, n), np.float32)
    row_mask[1, n // 4:] = 0.0
    grad2, hess2 = np.stack([grad, grad]), np.stack([hess, hess])

    tree, slots = _grow(binned, grad2, hess2, row_mask, impl)
    monkeypatch.setattr(TR, "_width_ladder", lambda chunk: (chunk,))
    full, full_slots = _grow(binned, grad2, hess2, row_mask, impl)

    assert (full_slots.built[full_slots.built > 0] >= 128).all()
    if kind == "balanced" and values == "real":
        # (the scatter builder's chunk is 512 slots and a level of this
        # depth has 256 pairs at most: two of its rungs)
        rungs = 2 if impl == "scatter" else 3
        assert len(set(slots.built.tolist())) >= rungs, "the rungs engage"
    np.testing.assert_array_equal(slots.live, full_slots.live)
    assert (slots.built <= full_slots.built).all()
    assert (slots.live <= slots.built).all()
    np.testing.assert_array_equal(tree.split_feat, full.split_feat)
    np.testing.assert_array_equal(tree.split_bin, full.split_bin)
    if values == "half" or impl != "pallas":
        # +-0.5 / 0.25 sums are exact in float32 whatever the order; the
        # scatter and the GEMM add a node's rows in the same order at
        # every width
        np.testing.assert_array_equal(tree.leaf_value, full.leaf_value)
    else:
        # the leaf sums are made outside the histogram builds, from the
        # same routing: equal trees give equal leaves. The tolerance is
        # for what the compiler may reassociate between two programs.
        np.testing.assert_allclose(
            tree.leaf_value, full.leaf_value, rtol=1e-6, atol=1e-7
        )


def test_slot_counts_of_a_hand_checkable_tree(slots_hold):
    """Four equal groups of rows told apart by two 2-bin columns: the root
    splits, both children split, then nothing is left to gain. Level 0
    (1 live slot) and level 1 (2 nodes: one pair) are built at the floor,
    level 2 (4 nodes in 2 pairs, no split) too, and the early exit skips
    the rest."""
    n, depth = 4096, 7
    rng = np.random.default_rng(5)
    a = rng.integers(0, 2, size=n)
    b = rng.integers(0, 2, size=n)
    binned = jnp.asarray(np.stack([a, b], axis=1).astype(np.int32))
    target = (2.0 * a + b).astype(np.float32)
    fn = jax.jit(functools.partial(
        TR._grow_tree_impl, max_depth=depth, num_bins=2, reg_lambda=0.0,
        gamma=0.0, min_child_weight=1.0, min_info_gain=1e-6,
        hist_impl="scatter",
    ))
    tree, _node, slots = fn(
        binned, jnp.asarray(-target)[None], jnp.ones((1, n), jnp.float32),
        jnp.ones((1, n), jnp.float32), jnp.ones((1, 2), jnp.float32),
    )
    # cap = 2**7 = 128 slots in one chunk: rungs 32, 64, 128
    assert TR._width_ladder(128) == (32, 64, 128)
    floor = TR._HIST_WIDTH_FLOOR
    live = {"pairs": [1, 1, 2], "nodes": [1, 2, 4]}[slots_hold]
    derived = {"pairs": [0, 1, 2], "nodes": [0, 0, 0]}[slots_hold]
    np.testing.assert_array_equal(
        np.asarray(slots.live), live + [0, 0, 0, 0]
    )
    np.testing.assert_array_equal(
        np.asarray(slots.built), [floor, floor, floor, 0, 0, 0, 0]
    )
    np.testing.assert_array_equal(
        np.asarray(slots.nodes_derived), derived + [0, 0, 0, 0]
    )
    np.testing.assert_array_equal(
        np.asarray(slots.nodes_built + slots.nodes_derived),
        [1, 2, 4, 0, 0, 0, 0],
    )
    assert (np.asarray(tree.split_feat)[0, 2] == -1).all()


def test_a_full_level_is_built_at_its_own_width(slots_hold):
    """Every row its own leaf candidate: 256 distinct codes on one column
    keep every level full, so level d has 2**d live nodes and is built at
    the smallest rung that holds them (or their 2**(d-1) pairs); built by
    node, the last level needs both chunks, by pair one."""
    n, depth, bins = 4096, 9, 512
    code = (np.arange(n) % 512).astype(np.int32)
    binned = jnp.asarray(code[:, None])
    target = code.astype(np.float32)
    fn = jax.jit(functools.partial(
        TR._grow_tree_impl, max_depth=depth, num_bins=bins, reg_lambda=0.0,
        gamma=0.0, min_child_weight=1.0, min_info_gain=0.0,
        hist_impl="gemm",
    ))
    _tree, _node, slots = fn(
        binned, jnp.asarray(-target)[None], jnp.ones((1, n), jnp.float32),
        jnp.ones((1, n), jnp.float32), jnp.ones((1, 1), jnp.float32),
    )
    # the GEMM caps a chunk at 128 slots: rungs 32, 64, 128; cap 512
    nodes = [1, 2, 4, 8, 16, 32, 64, 128, 256]
    if slots_hold == "pairs":
        live = [1, 1, 2, 4, 8, 16, 32, 64, 128]
        built = [32, 32, 32, 32, 32, 32, 32, 64, 128]
        # (the widest rung IS the chunk: a level of 65-128 slots runs one
        # chunk of the loop's and leaves the others out)
        runs, skipped = [1] * 9, [0] * 8 + [1]
    else:
        live = nodes
        built = [32, 32, 32, 32, 32, 32, 64, 128, 256]
        runs, skipped = [1] * 8 + [2], [0] * 7 + [3, 2]
    np.testing.assert_array_equal(np.asarray(slots.live), live)
    np.testing.assert_array_equal(np.asarray(slots.built), built)
    np.testing.assert_array_equal(np.asarray(slots.chunks_run), runs)
    np.testing.assert_array_equal(np.asarray(slots.chunks_skipped), skipped)
    np.testing.assert_array_equal(
        np.asarray(slots.nodes_built + slots.nodes_derived), nodes
    )


@pytest.mark.parametrize(
    "chunk,ladder",
    [
        (1, (1,)), (8, (8,)), (32, (32,)), (64, (32, 64)),
        (128, (32, 64, 128)), (256, (32, 64, 128, 256)),
        (4096, (512, 1024, 2048, 4096)),
    ],
)
def test_ladder_is_a_function_of_the_chunk_width(chunk, ladder):
    assert TR._width_ladder(chunk) == ladder
    assert len(ladder) <= 4 and ladder[-1] == chunk


def test_small_programs_lower_to_one_level_body(slots_hold):
    """A chunk at or under the floor has one rung: no branch over widths in
    the program (the level scan holds one histogram build per group; where
    a level's slots hold pairs, one more for the level that must build
    every node, in chunks, because a child of some split holds no row)."""
    n = 512
    binned, t = _table("balanced", n)
    args = (
        binned, jnp.asarray(-t)[None], jnp.ones((1, n), jnp.float32),
        jnp.ones((1, n), jnp.float32), jnp.ones((1, 5), jnp.float32),
    )

    def scatters(depth):
        text = jax.jit(functools.partial(
            TR._grow_tree_impl, max_depth=depth, num_bins=BINS,
            hist_impl="scatter",
        )).lower(*args).as_text()
        return text.count("stablehlo.scatter")

    # depth 5: 32 slots, the floor; depth 7: 128 slots, three rungs
    bodies = {"nodes": (1, 3), "pairs": (1 + 1, 3 + 1)}[slots_hold]
    assert scatters(7) * bodies[0] == scatters(5) * bodies[1]


def test_await_outputs_lands_the_counts_on_span_and_ledger():
    from transmogrifai_tpu.models import gbdt
    from transmogrifai_tpu.telemetry import export as texport
    from transmogrifai_tpu.telemetry import spans as tspans

    n = 3072
    binned, t = _table("balanced", n)
    y = (t > np.median(t)).astype(np.float32)
    trees, margin, slots = TR.fit_boosted_batched(
        binned, y, np.ones((2, n), np.float32), num_rounds=2,
        max_depth=DEPTH, num_bins=BINS, eta=0.3, return_slots=True,
    )
    assert np.asarray(slots.live).shape == (2, DEPTH)
    tspans.reset_for_tests()
    before = TR.hist_slot_stats().snapshot()
    stack = {"outputs": margin, "hist_slots": slots}
    out = gbdt.await_stack_outputs(stack)
    again = gbdt.await_stack_outputs(stack)  # the counts ride the first read
    assert out.shape == (2, n) and again.shape == (2, n)
    live = int(np.asarray(slots.live).sum())
    built = int(np.asarray(slots.built).sum())
    assert 0 < live <= built
    nodes_built = int(np.asarray(slots.nodes_built).sum())
    nodes_derived = int(np.asarray(slots.nodes_derived).sum())
    # both lanes' roots are built; every node under them is one of a pair
    assert nodes_built - nodes_derived == 2 * 2 and nodes_derived > 0
    recs = [
        r["args"] for r in tspans.snapshot_events()
        if r["name"] == "tree/await_outputs"
    ]
    assert [a.get("slots_live") for a in recs] == [live, None]
    assert recs[0]["slots_built"] == built
    assert recs[0]["nodes_built"] == nodes_built
    assert recs[0]["nodes_derived"] == nodes_derived
    now = TR.hist_slot_stats().snapshot()
    assert now["histSlotsLive"] - before["histSlotsLive"] == live
    assert now["histSlotsBuilt"] - before["histSlotsBuilt"] == built
    assert now["histNodesBuilt"] - before["histNodesBuilt"] == nodes_built
    assert (
        now["histNodesDerived"] - before["histNodesDerived"] == nodes_derived
    )
    text = texport.render_prometheus()
    assert f"tptpu_tree_hist_slots_built {now['histSlotsBuilt']}" in text


# (columns, bins, lowp) -> {slots: (row_tile, feat_tile)}: the cells' two
# feature groups, four value variants (boosting) and two (the forest)
FLAGSHIP_TILES = {
    (302, 32, False): {
        256: (1024, 104), 128: (2048, 104), 64: (2048, 104),
        32: (2048, 104), 8: (2048, 104),
    },
    (302, 32, True): {
        256: (2048, 104), 128: (2048, 104), 64: (2048, 104),
        32: (2048, 104), 8: (2048, 104),
    },
    (55, 2, False): {
        256: (1024, 56), 128: (2048, 56), 64: (2048, 56), 32: (2048, 56),
        8: (2048, 56),
    },
    (55, 2, True): {
        256: (2048, 56), 128: (2048, 56), 64: (2048, 56), 32: (2048, 56),
        8: (2048, 56),
    },
}


@pytest.mark.parametrize("slots", [256, 128, 64, 32, 8])
@pytest.mark.parametrize(
    "group", FLAGSHIP_TILES, ids=lambda g: "{}x{}{}".format(
        g[0], g[1], "_lowp" if g[2] else ""
    ),
)
def test_kernel_tiles_by_width_at_the_flagship_shape(group, slots):
    """The measured table (``binloop_tiles``' docstring): the feature tile
    fills towards the MXU's 128 rows at EVERY width, evenly (three tiles of
    104 for 302 columns, not 128 + 128 + 46; one of 56 for the 55 narrow
    ones), and the row tile is the stacked operand's element cap: 1,024
    rows where it is 1,024 lanes wide (256 slots x 4 variants), else
    2,048."""
    f, bins, lowp = group
    assert HP.binloop_tiles(f, slots, bins, lowp=lowp) == (
        FLAGSHIP_TILES[group][slots]
    )


@pytest.mark.parametrize("lowp", [False, True])
@pytest.mark.parametrize("bins", [2, 32, 64])
@pytest.mark.parametrize("f", [3, 55, 302, 500])
@pytest.mark.parametrize("slots", [8, 64, 256])
def test_kernel_tiles_are_sublane_multiples_within_the_mxu(
    f, slots, bins, lowp
):
    row_tile, feat_tile = HP.binloop_tiles(f, slots, bins, lowp=lowp)
    assert row_tile % 128 == 0 and 128 <= row_tile <= 2048
    assert feat_tile % HP.FEAT_TILE == 0 and 8 <= feat_tile <= 128
    # evening the tiles never adds a tile or pads more than one sublane
    # group per tile
    f8 = -(-f // 8) * 8
    tiles = -(-f8 // feat_tile)
    assert tiles == -(-f8 // 128)
    assert tiles * feat_tile - f8 < 8 * tiles
    # what the model says the pair takes is under what Mosaic is told
    assert HP.binloop_vmem_bytes(
        row_tile, feat_tile, slots, bins, lowp
    ) <= HP._BINLOOP_VMEM_BUDGET < HP._BINLOOP_VMEM_LIMIT


# (slots, lowp, bins, row_tile, feat_tile) -> MiB of scoped VMEM Mosaic
# asked for when compiled for a v5e (read off its refusal under a limit
# set just over the pipelined blocks: PR 30)
MOSAIC_ASKED_MIB = {
    (256, False, 32, 1024, 104): 17.99,
    (256, False, 32, 2048, 128): 26.65,
    (256, False, 32, 128, 128): 16.65,
    (256, False, 64, 512, 128): 34.64,
    (256, True, 32, 2048, 128): 23.54,
    (128, False, 32, 2048, 128): 15.81,
    (64, False, 32, 2048, 128): 13.99,
    (32, False, 32, 2048, 128): 12.49,
    (32, True, 32, 2048, 128): 12.06,
    (256, False, 2, 2048, 56): 8.55,
}


@pytest.mark.parametrize(
    "point", MOSAIC_ASKED_MIB, ids=lambda p: "-".join(map(str, p))
)
def test_vmem_model_is_not_under_what_mosaic_asked_for(point):
    slots, lowp, bins, row_tile, feat_tile = point
    model = HP.binloop_vmem_bytes(row_tile, feat_tile, slots, bins, lowp)
    asked = MOSAIC_ASKED_MIB[point] * 2**20
    assert asked <= model <= 1.5 * asked


# (slots, the pair binloop_tiles gave up to PR 29): the full-width pairs
# the joint choice replaced
OLD_TILES = {256: (1024, 8), 128: (2048, 16)}


@pytest.mark.parametrize("values", ["half", "real"])
@pytest.mark.parametrize("slots", [256, 128])
def test_old_and_new_tiles_build_the_same_histograms(slots, values):
    """The kernel at the flagship shape's old pair and at its new pair, in
    interpret mode: another order of adding row tiles and another split of
    the columns, the same sums. +-0.5 / 0.25 values (one round of
    binary:logistic) are exact in float32 in any order; real values agree
    with the scatter histograms within the kernel tests' tolerance."""
    n, f, k = 4608, 70, 2
    new = HP.binloop_tiles(302, slots, 32)
    assert new != OLD_TILES[slots]
    rng = np.random.default_rng(slots)
    binned = jnp.asarray(rng.integers(0, BINS, size=(n, f)), jnp.int32)
    node = jnp.asarray(rng.integers(-1, slots, size=(k, n)), jnp.int32)
    if values == "half":
        g = rng.choice([-0.5, 0.5], size=(k, n)).astype(np.float32)
        h = np.full((k, n), 0.25, np.float32)
    else:
        g = rng.normal(size=(k, n)).astype(np.float32)
        h = rng.uniform(0.1, 1.0, size=(k, n)).astype(np.float32)
    g, h = jnp.asarray(g), jnp.asarray(h)

    def build(tiles):
        return np.asarray(HP.build_histogram_pallas_binloop(
            binned, node, g, h, slots, BINS, row_tile=tiles[0],
            feat_tile=tiles[1], interpret=True,
        ))

    old_hist, new_hist = build(OLD_TILES[slots]), build(new)
    ref = np.asarray(HP.build_histogram_scatter_batched(
        binned, node, g, h, slots, BINS
    ))
    if values == "half":
        np.testing.assert_array_equal(old_hist, new_hist)
        np.testing.assert_array_equal(new_hist, ref)
    else:
        np.testing.assert_allclose(old_hist, ref, atol=2e-4)
        np.testing.assert_allclose(new_hist, ref, atol=2e-4)


@pytest.mark.parametrize(
    "kw,text",
    [
        # the boosted cell: four rungs, the widest group is the 302 wide;
        # the 4 lanes share a pass up to 64 slots (the one-lane tiles)
        (dict(max_depth=10, lowp=False),
         "32x4:2048/104 64x4:2048/104 128x1:2048/104 256x1:1024/104"),
        # the forest cell's three depth programs (lowp)
        (dict(max_depth=12, lowp=True),
         "32x4:2048/104 64x4:2048/104 128x4:2048/104 256x1:2048/104"),
        (dict(max_depth=6, lowp=True), "32x4:2048/104 64x4:2048/104"),
        (dict(max_depth=3, lowp=True), "8x4:2048/104"),
        # four shards: the local rows' plan, the full width only
        (dict(max_depth=10, lowp=False, shards=4), "256x1:1024/104"),
        (dict(max_depth=10, lowp=False, shards=1), "256x1:1024/104"),
        # builders without tiles
        (dict(max_depth=10, lowp=False, n=4096), "none"),
        (dict(max_depth=10, lowp=False, impl="scatter"), "none"),
    ],
)
def test_hist_tiles_attribute_names_the_ladder_of_the_widest_group(kw, text):
    kw = {"n": 1_002_701, "impl": "pallas", **kw}
    n = kw.pop("n")
    assert TR.hist_tiles(n, 4, [(55, 2), (302, 32)], **kw) == text
