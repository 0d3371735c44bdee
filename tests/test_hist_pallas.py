"""Pallas histogram kernel tests (interpret mode on CPU)."""
import numpy as np
import jax.numpy as jnp
import pytest

from transmogrifai_tpu.models.hist_pallas import (
    build_histogram_pallas,
    build_histogram_pallas_binloop,
    build_histogram_scatter,
    build_histogram_scatter_batched,
)


class TestHistogramKernel:
    def _data(self, n=500, f=5, b=8, m=6, seed=0):
        rng = np.random.default_rng(seed)
        return (
            jnp.asarray(rng.integers(0, b, (n, f)), dtype=jnp.int32),
            jnp.asarray(rng.integers(-1, m, n), dtype=jnp.int32),
            jnp.asarray(rng.normal(size=n), dtype=jnp.float32),
            jnp.asarray(rng.uniform(0.1, 1, n), dtype=jnp.float32),
            b, m,
        )

    def test_parity_with_scatter(self):
        binned, node, g, h, b, m = self._data()
        a = build_histogram_pallas(binned, node, g, h, m, b, row_tile=256,
                                   interpret=True)
        ref = build_histogram_scatter(binned, node, g, h, m, b)
        np.testing.assert_allclose(np.asarray(a), np.asarray(ref), atol=2e-4)

    def test_binloop_parity_with_scatter(self):
        """The bin-loop kernel (default two-phase path at <=64 bins) must
        match the scatter reference, including dead rows and K batching."""
        binned, node, g, h, b, m = self._data()
        a = build_histogram_pallas_binloop(
            binned, node[None, :], g[None, :], h[None, :], m, b,
            row_tile=256, interpret=True,
        )[0]
        ref = build_histogram_scatter(binned, node, g, h, m, b)
        np.testing.assert_allclose(np.asarray(a), np.asarray(ref), atol=2e-4)

    def test_binloop_parity_unaligned_batched(self):
        binned, node, g, h, b, m = self._data(n=301, f=3, b=5, m=3, seed=2)
        node2 = jnp.stack([node, jnp.maximum(node - 1, -1)])
        g2 = jnp.stack([g, g * 0.5])
        h2 = jnp.stack([h, h])
        a = build_histogram_pallas_binloop(
            binned, node2, g2, h2, m, b, row_tile=256, interpret=True
        )
        ref = build_histogram_scatter_batched(binned, node2, g2, h2, m, b)
        assert a.shape == (2, 3, 3, 5, 2)
        np.testing.assert_allclose(np.asarray(a), np.asarray(ref), atol=2e-4)

    def test_parity_with_scatter_256_bins(self):
        """>128 bins: the bin axis spans multiple 128-lane groups — the
        kernel must keep parity (the round-2 fallback cliff shape)."""
        binned, node, g, h, _, m = self._data(n=300, f=3, b=256)
        a = build_histogram_pallas(binned, node, g, h, m, 256, row_tile=256,
                                   interpret=True)
        ref = build_histogram_scatter(binned, node, g, h, m, 256)
        np.testing.assert_allclose(np.asarray(a), np.asarray(ref), atol=2e-4)

    def test_dead_rows_do_not_contribute(self):
        binned, node, g, h, b, m = self._data()
        dead = jnp.full_like(node, -1)
        out = build_histogram_pallas(binned, dead, g, h, m, b, row_tile=256,
                                     interpret=True)
        assert float(jnp.abs(out).sum()) == 0.0

    def test_unaligned_sizes(self):
        # n not a multiple of the row tile; f not a multiple of FEAT_TILE
        binned, node, g, h, b, m = self._data(n=301, f=3, b=5, m=3)
        a = build_histogram_pallas(binned, node, g, h, m, b, row_tile=256,
                                   interpret=True)
        ref = build_histogram_scatter(binned, node, g, h, m, b)
        assert a.shape == (3, 3, 5, 2)
        np.testing.assert_allclose(np.asarray(a), np.asarray(ref), atol=2e-4)

    def test_grow_tree_impl_selection(self):
        """grow_tree with explicit scatter impl (CPU path) learns a split."""
        from transmogrifai_tpu.models import trees as TR

        rng = np.random.default_rng(1)
        n = 2000
        x = rng.normal(size=(n, 4)).astype(np.float32)
        y = (x[:, 2] > 0.3).astype(np.float32)
        thr = TR.quantile_thresholds(x, 16)
        binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
        tree = TR.grow_tree(
            binned, jnp.asarray(-(y - 0.5)), jnp.ones(n, jnp.float32),
            jnp.ones(n, jnp.float32), jnp.ones(4, jnp.float32),
            max_depth=2, num_bins=16, hist_impl="scatter",
        )
        assert int(tree.split_feat[0][0]) == 2  # found the true feature


def _lanes_data(lanes, slots, channels, lowp, n=300, f=5, b=4, seed=0):
    """Codes, nodes and statistics of ``lanes`` fits: a quarter of the rows
    dead (node -1), and the last lane of several weightless (every row's
    statistics 0). ``lowp``: bfloat16-exact values, as a forest's."""
    rng = np.random.default_rng(seed + 97 * lanes + slots + channels)
    binned = rng.integers(0, b, (n, f))
    node = rng.integers(0, slots, (lanes, n))
    node[rng.random((lanes, n)) < 0.25] = -1
    shape = (lanes, n) if channels == 2 else (lanes, channels - 1, n)
    grad = rng.normal(size=shape)
    hess = rng.uniform(0.1, 1.0, (lanes, n))
    if lowp:
        grad, hess = np.sign(grad), np.ones_like(hess)
    if lanes > 1:
        grad[-1], hess[-1] = 0.0, 0.0
    return (
        jnp.asarray(binned, jnp.int32), jnp.asarray(node, jnp.int32),
        jnp.asarray(grad, jnp.float32), jnp.asarray(hess, jnp.float32), b,
    )


@pytest.mark.parametrize("lowp", [False, True], ids=["hilo", "lowp"])
@pytest.mark.parametrize("channels", [2, 7])
@pytest.mark.parametrize("slots", [8, 32, 64])
@pytest.mark.parametrize("lanes", [1, 3, 4, 5])
def test_binloop_lanes_sharing_a_pass_build_the_one_lane_histograms(
    lanes, slots, channels, lowp
):
    """The lanes one grid step builds together (``binloop_lane_group``; a
    last partial group padded with dead lanes) give every lane the
    histograms of the kernel that builds one lane a pass, bit for bit, and
    the scatter reference's within the kernel tests' tolerance."""
    from transmogrifai_tpu.models.hist_pallas import binloop_lane_group

    binned, node, grad, hess, b = _lanes_data(lanes, slots, channels, lowp)
    group = binloop_lane_group(lanes, channels, lowp, slots)
    assert 1 <= group <= lanes

    def build(lane_group):
        return np.asarray(build_histogram_pallas_binloop(
            binned, node, grad, hess, slots, b, lowp=lowp, row_tile=128,
            interpret=True, lane_group=lane_group,
        ))

    folded, one = build(None), build(1)
    assert folded.shape == (lanes, slots, binned.shape[1], b, channels)
    np.testing.assert_array_equal(folded, one)
    ref = np.asarray(
        build_histogram_scatter_batched(binned, node, grad, hess, slots, b)
    )
    np.testing.assert_allclose(folded, ref, atol=2e-4)
    if lanes > 1:
        assert not folded[-1].any()  # the weightless lane
