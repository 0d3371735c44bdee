"""On-device (real TPU) parity for the Pallas kernels.

Interpret mode cannot catch a Mosaic regression, so these tests run ONLY on
a TPU backend (skipped on the CPU-mesh CI run — the conftest forces
JAX_PLATFORMS=cpu there; run with TPTPU_TPU_TESTS=1 and no platform
override to exercise them on hardware, e.g. through the chip tool).

Covered: the two histogram kernels the tree fits take above 4,096 rows
(bin-loop at <=64 bins, lane-packed above) against the scatter reference,
and the serve-side traversal kernel against the gather traversal (bit
parity, per tree and through both ensemble wrappers). Which builder a fit
takes is ``hist_pallas.histogram_plan``'s to say (tests/test_histogram_plan.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="on-device Mosaic parity tests need a real TPU backend",
)


def _case(n, f, b, k, seed=0):
    rng = np.random.default_rng(seed)
    binned = rng.integers(0, b, size=(n, f)).astype(np.int32)
    g = rng.normal(size=(k, n)).astype(np.float32)
    h = np.abs(rng.normal(size=(k, n))).astype(np.float32) + 0.1
    node = rng.integers(0, 4, size=(k, n)).astype(np.int32)
    fmask = np.ones((k, f), np.float32)
    return binned, node, g, h, fmask


def test_grow_tree_pallas_vs_scatter_on_device(monkeypatch):
    from transmogrifai_tpu.models import trees as TR

    rng = np.random.default_rng(1)
    n, f = 1500, 16
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x @ rng.normal(size=f) > 0).astype(np.float32)
    thr = TR.quantile_thresholds(x, max_bins=32)
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
    masks = jnp.ones((2, n), jnp.float32)
    kw = dict(num_rounds=4, max_depth=5, num_bins=32, eta=0.3,
              objective="binary:logistic")
    tp, mp = TR.fit_boosted_batched(binned, jnp.asarray(y), masks, **kw)
    monkeypatch.setattr(TR, "_resolved_impl", lambda: "scatter")
    ts, ms = TR.fit_boosted_batched(binned, jnp.asarray(y), masks, **kw)
    np.testing.assert_array_equal(
        np.asarray(tp.split_feat), np.asarray(ts.split_feat)
    )
    np.testing.assert_allclose(np.asarray(mp), np.asarray(ms), rtol=1e-4)


@pytest.mark.parametrize("lowp", [False, True])
def test_two_phase_histogram_matches_scatter_on_device(lowp):
    """The packed hi/lo-bf16 histogram kernel must match the f64-exactness
    scatter reference on real Mosaic (not just interpret mode)."""
    from transmogrifai_tpu.models.hist_pallas import (
        build_histogram_pallas_batched,
        build_histogram_scatter_batched,
    )

    n, f, b, k, m = 4096, 12, 32, 2, 8
    binned, node, g, h, _ = _case(n, f, b, k)
    if lowp:
        g = np.sign(g).astype(np.float32)  # bf16-exact indicator values
        h = np.ones_like(h)
    a = np.asarray(build_histogram_pallas_batched(
        jnp.asarray(binned), jnp.asarray(node), jnp.asarray(g),
        jnp.asarray(h), m, b, lowp=lowp,
    ))
    ref = np.asarray(build_histogram_scatter_batched(
        jnp.asarray(binned), jnp.asarray(node), jnp.asarray(g),
        jnp.asarray(h), m, b,
    ))
    if lowp:
        np.testing.assert_array_equal(a, ref)  # integer sums stay exact
    else:
        np.testing.assert_allclose(a, ref, rtol=2e-4, atol=2e-3)


def test_two_phase_histogram_256_bins_on_device():
    from transmogrifai_tpu.models.hist_pallas import (
        build_histogram_pallas_batched,
        build_histogram_scatter_batched,
    )

    n, f, b, k, m = 2048, 4, 256, 1, 4
    binned, node, g, h, _ = _case(n, f, b, k)
    a = np.asarray(build_histogram_pallas_batched(
        jnp.asarray(binned), jnp.asarray(node), jnp.asarray(g),
        jnp.asarray(h), m, b,
    ))
    ref = np.asarray(build_histogram_scatter_batched(
        jnp.asarray(binned), jnp.asarray(node), jnp.asarray(g),
        jnp.asarray(h), m, b,
    ))
    np.testing.assert_allclose(a, ref, rtol=2e-4, atol=2e-3)


@pytest.mark.parametrize("bins", [32, 256])
@pytest.mark.parametrize("lowp", [False, True])
def test_main_path_histograms_match_scatter_above_gemm_cutoff(bins, lowp):
    """Above 4,096 rows ``_grow_tree_impl`` takes the bin-loop kernel at
    <=64 bins and the lane-packed kernel beyond; both must match the
    scatter reference on real Mosaic at a main-path row count."""
    from transmogrifai_tpu.models.hist_pallas import (
        build_histogram_pallas_batched,
        build_histogram_pallas_binloop,
        build_histogram_scatter_batched,
    )

    n, f, k, m = 16384, 24, 2, 16
    binned, node, g, h, _ = _case(n, f, bins, k, seed=3)
    node = np.random.default_rng(4).integers(0, m, size=(k, n)).astype(
        np.int32
    )
    if lowp:
        g = np.sign(g).astype(np.float32)  # bf16-exact indicator values
        h = np.ones_like(h)
    build = (
        build_histogram_pallas_binloop if bins <= 64
        else build_histogram_pallas_batched
    )
    args = (jnp.asarray(binned), jnp.asarray(node), jnp.asarray(g),
            jnp.asarray(h), m, bins)
    got = np.asarray(build(*args, lowp=lowp))
    ref = np.asarray(build_histogram_scatter_batched(*args))
    if lowp:
        np.testing.assert_array_equal(got, ref)  # integer sums stay exact
    else:
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-3)


def _random_stack(rng, t, depth, f, bins):
    from transmogrifai_tpu.models import trees as TR

    w = 1 << depth
    return TR.Tree(
        split_feat=jnp.asarray(
            rng.integers(-1, f, size=(t, depth, w)).astype(np.int32)
        ),
        split_bin=jnp.asarray(
            rng.integers(0, bins, size=(t, depth, w)).astype(np.int32)
        ),
        leaf_value=jnp.asarray(rng.normal(size=(t, w)).astype(np.float32)),
    )


@pytest.mark.parametrize(
    "t,depth,bins",
    [(8, 3, 32), (50, 12, 32), (200, 10, 32), (1000, 6, 256), (20, 8, 1000)],
)
def test_serve_kernel_bit_parity_with_gather_on_device(t, depth, bins):
    """The traversal kernel Mosaic compiled must give the gather
    traversal's bits: per tree, and through the forest and boosted
    wrappers against ``predict_*_raw`` (the staged device programs)."""
    from transmogrifai_tpu.models import serve_pallas as SP
    from transmogrifai_tpu.models import trees as TR

    rng = np.random.default_rng(t * 100 + depth)
    n, f = 20000, 64
    trees = _random_stack(rng, t, depth, f, bins)
    x = rng.normal(size=(n, f)).astype(np.float32)
    thr = jnp.asarray(TR.quantile_thresholds(x, max_bins=bins))
    xj = jnp.asarray(x)
    binned = TR.bin_data(xj, thr)

    per_tree = SP.serve_trees_pallas(
        binned, trees.split_feat, trees.split_bin, trees.leaf_value,
        num_bins=bins,
    )
    ref = jax.jit(
        lambda b, tr: jax.vmap(lambda one: TR.predict_tree(b, one))(tr)
    )(binned, trees)
    np.testing.assert_array_equal(np.asarray(per_tree), np.asarray(ref))

    eta, base = jnp.float32(0.02), jnp.float32(0.3)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(
            SP.predict_boosted_pallas, static_argnames=("num_bins",)
        )(binned, trees, eta, base, num_bins=bins)),
        np.asarray(TR.predict_boosted_raw(xj, thr, trees, eta, base)),
    )
    np.testing.assert_array_equal(
        np.asarray(jax.jit(
            SP.predict_forest_pallas, static_argnames=("num_bins",)
        )(binned, trees, num_bins=bins)),
        np.asarray(TR.predict_forest_raw(xj, thr, trees)),
    )


def test_width_ladder_grows_the_scatter_trees_on_device():
    """A depth-10 fit at 65,536 rows x 32 bins takes every rung of the
    width ladder (32, 64, 128 and the 256-slot chunks) through the
    bin-loop kernel Mosaic compiled; it must grow the trees the scatter
    histograms grow. One round of binary:logistic: g = +-0.5, h = 0.25, so
    every float32 sum is exact at any tile size and the trees are equal
    to the last bit."""
    import functools

    from transmogrifai_tpu.models import trees as TR

    n, f, bins, depth, k = 65536, 24, 32, 10, 2
    rng = np.random.default_rng(11)
    x = rng.normal(size=(n, f)).astype(np.float32)
    t = x[:, 0] + x[:, 1] * x[:, 2] + np.sin(3 * x[:, 3]) + rng.normal(size=n)
    binned = TR.bin_data(
        jnp.asarray(x), jnp.asarray(TR.quantile_thresholds(x, bins))
    )
    grad = np.where(t > np.median(t), -0.5, 0.5).astype(np.float32)
    args = (
        binned, jnp.asarray(np.stack([grad] * k)),
        jnp.full((k, n), 0.25, jnp.float32), jnp.ones((k, n), jnp.float32),
        jnp.ones((k, f), jnp.float32),
    )

    def grow(impl):
        tree, _node, slots = jax.jit(functools.partial(
            TR._grow_tree_impl, max_depth=depth, num_bins=bins,
            reg_lambda=1.0, gamma=0.8,
            min_child_weight=np.asarray([1.0, 10.0], np.float32),
            hist_impl=impl,
        ))(*args)
        return jax.tree.map(np.asarray, (tree, slots))

    tree, slots = grow("pallas")
    ref, ref_slots = grow("scatter")
    assert set(slots.built.tolist()) >= {32, 64, 128, 256}
    assert slots.live.sum() > 0.6 * slots.built.sum()
    np.testing.assert_array_equal(slots.live, ref_slots.live)
    np.testing.assert_array_equal(tree.split_feat, ref.split_feat)
    np.testing.assert_array_equal(tree.split_bin, ref.split_bin)
    np.testing.assert_array_equal(tree.leaf_value, ref.leaf_value)


@pytest.mark.parametrize("values", ["half", "real"])
@pytest.mark.parametrize("depth", [10, 12])
def test_sibling_subtraction_grows_the_direct_trees_on_device(
    depth, values, monkeypatch
):
    """The twin of tests/test_hist_sibling_subtraction.py on the chip: at
    65,536 rows the bin-loop kernel builds one child of every sibling pair
    (256 pair slots a chunk) and the other is its parent less it. With
    g = +-0.5, h = 0.25 every sum is exact and the trees are bit for bit
    those the scatter histograms grow with EVERY node built; with real
    targets (against the kernel's own direct builds) a derived histogram
    differs in its last bits, so a near tie may turn: nearly every node
    agrees, and the counts say how many."""
    import functools

    from transmogrifai_tpu.models import hist_pallas as HP
    from transmogrifai_tpu.models import trees as TR

    n, f, bins, k = 65536, 24, 32, 2
    rng = np.random.default_rng(depth)
    x = rng.normal(size=(n, f)).astype(np.float32)
    t = x[:, 0] + x[:, 1] * x[:, 2] + np.sin(3 * x[:, 3]) + rng.normal(size=n)
    binned = TR.bin_data(
        jnp.asarray(x), jnp.asarray(TR.quantile_thresholds(x, bins))
    )
    if values == "half":
        grad = np.where(t > np.median(t), -0.5, 0.5).astype(np.float32)
        hess = np.full(n, 0.25, np.float32)
    else:
        grad, hess = (-t).astype(np.float32), np.ones(n, np.float32)
    args = (
        binned, jnp.asarray(np.stack([grad] * k)),
        jnp.asarray(np.stack([hess] * k)), jnp.ones((k, n), jnp.float32),
        jnp.ones((k, f), jnp.float32),
    )

    def grow(impl):
        tree, _node, slots = jax.jit(functools.partial(
            TR._grow_tree_impl, max_depth=depth, num_bins=bins,
            reg_lambda=1.0, gamma=0.0,
            min_child_weight=np.asarray([1.0, 10.0], np.float32),
            hist_impl=impl,
        ))(*args)
        return jax.tree.map(np.asarray, (tree, slots))

    tree, slots = grow("pallas")
    assert slots.nodes_derived.sum() > 0
    nodes = slots.nodes_built + slots.nodes_derived
    np.testing.assert_array_equal(slots.nodes_derived[1:] * 2, nodes[1:])
    # the deep levels run 256-slot chunks of PAIRS, half as many as nodes
    assert slots.chunks_run.max() > 1 or depth == 10
    assert (slots.built[slots.chunks_run > 1] % 256 == 0).all()
    assert slots.built.max() >= 256 and (slots.live <= slots.built).all()
    monkeypatch.setattr(HP, "_PARENT_HIST_BUDGET_ELEMS", 0)
    # real values: the kernel rounds its inputs to a (hi, lo) bfloat16 pair,
    # so only the kernel's own direct builds tell what the subtraction did
    ref, ref_slots = grow("scatter" if values == "half" else "pallas")
    assert ref_slots.nodes_derived.sum() == 0
    if values == "half":
        np.testing.assert_array_equal(ref_slots.nodes_built, nodes)
        np.testing.assert_array_equal(tree.split_feat, ref.split_feat)
        np.testing.assert_array_equal(tree.split_bin, ref.split_bin)
        np.testing.assert_array_equal(tree.leaf_value, ref.leaf_value)
    else:
        same = (tree.split_feat == ref.split_feat) & (
            tree.split_bin == ref.split_bin
        )
        split = ref.split_feat >= 0
        print(
            f"depth {depth}: {int((~same & split).sum())} of "
            f"{int(split.sum())} split nodes differ; nodes a level "
            f"{nodes.tolist()}; built {slots.built.tolist()}"
        )
        assert same[split].mean() > 0.99
        # the top of the tree, where a node holds thousands of rows
        np.testing.assert_array_equal(
            tree.split_feat[:, :6], ref.split_feat[:, :6]
        )


@pytest.mark.parametrize("cols", [55, 302, 500])
@pytest.mark.parametrize("lowp", [False, True])
@pytest.mark.parametrize("slots", [8, 32, 64, 128, 256])
@pytest.mark.parametrize("bins", [2, 32, 64])
def test_binloop_compiles_and_matches_scatter_at_the_chosen_tiles(
    bins, slots, lowp, cols
):
    """Every shape a TPU tree fit can hand ``binloop_tiles`` (both cells'
    groups, the 64-bin sketch, a forest deep enough for the full width)
    compiles under the limit the kernel states to Mosaic and builds the
    scatter histograms. 5,000 rows: two or more row tiles at every chosen
    ``row_tile``, the last one padded."""
    from transmogrifai_tpu.models.hist_pallas import (
        build_histogram_pallas_binloop,
        build_histogram_scatter_batched,
    )

    n, k = 5000, 2
    binned, _, g, h, _ = _case(n, cols, bins, k, seed=bins + slots)
    node = np.random.default_rng(slots).integers(
        -1, slots, size=(k, n)
    ).astype(np.int32)
    if lowp:
        g = np.sign(g).astype(np.float32)  # bf16-exact indicator values
        h = np.ones_like(h)
    args = (jnp.asarray(binned), jnp.asarray(node), jnp.asarray(g),
            jnp.asarray(h), slots, bins)
    got = np.asarray(build_histogram_pallas_binloop(*args, lowp=lowp))
    ref = np.asarray(build_histogram_scatter_batched(*args))
    if lowp:
        np.testing.assert_array_equal(got, ref)  # integer sums stay exact
    else:
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-3)


@pytest.mark.parametrize("cols,bins", [(55, 2), (302, 32)])
@pytest.mark.parametrize("slots", [8, 32, 64, 128])
@pytest.mark.parametrize("channels,lowp", [(7, True), (3, True), (3, False)])
def test_binloop_builds_every_statistic_channel_on_device(
    channels, lowp, slots, cols, bins
):
    """The statistic axis on real Mosaic: a K-class forest's K channels
    (the K - 1 class indicators times w, and w; bfloat16-exact) and a
    three-channel real-valued fit, at every width a fit of that many
    channels can build at (``histogram_plan``: 128 slots at most for
    seven one-variant channels)."""
    from transmogrifai_tpu.models.hist_pallas import (
        build_histogram_pallas_binloop,
        build_histogram_scatter_batched,
    )

    n, k = 5000, 2
    rng = np.random.default_rng(channels * 1000 + slots + cols)
    binned = rng.integers(0, bins, size=(n, cols)).astype(np.int32)
    node = rng.integers(-1, slots, size=(k, n)).astype(np.int32)
    w = rng.poisson(1.0, size=(k, n)).astype(np.float32)
    if lowp:
        cls = rng.integers(0, channels, size=n)
        grad = np.stack(
            [-((cls == c) * w) for c in range(1, channels)], axis=1
        ).astype(np.float32)
    else:
        grad = rng.normal(size=(k, channels - 1, n)).astype(np.float32)
    args = (jnp.asarray(binned), jnp.asarray(node), jnp.asarray(grad),
            jnp.asarray(w), slots, bins)
    got = np.asarray(build_histogram_pallas_binloop(*args, lowp=lowp))
    ref = np.asarray(build_histogram_scatter_batched(*args))
    assert got.shape == ref.shape == (k, slots, cols, bins, channels)
    if lowp:
        np.testing.assert_array_equal(got, ref)  # integer sums stay exact
    else:
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-3)
