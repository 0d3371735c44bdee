"""An exact hessian as ONE variant of the bin-loop kernel's stacked operand.

Under an objective whose hessian is 1 (``trees.UNIT_HESSIAN_OBJECTIVES``)
and row weights that are 0 or 1, ``h * row_mask`` is bfloat16-exact, so the
(hi, lo) pair the kernel used to carry it in had a lo of zeros. The boosted
fit states it (``_boost_chunk_body``) and the kernel stacks three variants
(g hi, g lo, h) where it stacked four, with the same histograms bit for
bit; ``HistSlots.stack_variants`` / ``stack_variants_full`` count it."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.models import hist_pallas as HP
from transmogrifai_tpu.models import trees as TR

SLOTS = (8, 32, 64, 128, 256)


def _kernel_data(lanes, slots, n=300, f=5, b=4, seed=0):
    """Codes, nodes (a quarter of the rows dead), real g and h in {0, 1}:
    a first-order boosted fit's statistics under a 0/1 row mask."""
    rng = np.random.default_rng(seed + 31 * lanes + slots)
    binned = rng.integers(0, b, (n, f))
    node = rng.integers(0, slots, (lanes, n))
    node[rng.random((lanes, n)) < 0.25] = -1
    grad = rng.normal(size=(lanes, n))
    hess = (rng.random((lanes, n)) < 0.7).astype(np.float32)
    return (
        jnp.asarray(binned, jnp.int32), jnp.asarray(node, jnp.int32),
        jnp.asarray(grad, jnp.float32), jnp.asarray(hess, jnp.float32), b,
    )


@pytest.mark.parametrize("lanes", [1, 4])
@pytest.mark.parametrize("slots", SLOTS)
def test_three_variants_build_the_four_variant_histograms_bit_for_bit(
    lanes, slots
):
    binned, node, grad, hess, b = _kernel_data(lanes, slots)

    def build(**kw):
        return np.asarray(HP.build_histogram_pallas_binloop(
            binned, node, grad, hess, slots, b, row_tile=128,
            interpret=True, **kw,
        ))

    three, four = build(exact_hess=True), build()
    assert three.shape == (lanes, slots, binned.shape[1], b, 2)
    np.testing.assert_array_equal(three, four)
    # one lane a pass, too: the fold does not change a lane's sums
    np.testing.assert_array_equal(build(exact_hess=True, lane_group=1), four)
    ref = np.asarray(
        HP.build_histogram_scatter_batched(binned, node, grad, hess, slots, b)
    )
    np.testing.assert_array_equal(three[..., 1], ref[..., 1])  # counts
    np.testing.assert_allclose(three, ref, atol=2e-4)


# (channels, lowp, exact_hess) -> the operand's variants: one an exact
# statistic, a (hi, lo) pair any other
VARIANTS = {
    (2, False, False): 4,   # the boosted cell (binary:logistic)
    (2, True, False): 2,    # the forest cell
    (7, True, False): 7,    # the seven-class forest cell
    (7, False, False): 14,
    (2, False, True): 3,    # the GBT cell: g hi, g lo, h
    (7, False, True): 13,
    (2, True, True): 2,     # lowp already states the hessian
}


@pytest.mark.parametrize("case", sorted(VARIANTS))
def test_variants_by_statement(case):
    channels, lowp, exact_hess = case
    assert HP.stack_variants(channels, lowp, exact_hess) == VARIANTS[case]
    assert HP.carried_variants(
        "binloop", channels, lowp, exact_hess) == VARIANTS[case]
    for other in ("scatter", "gemm", "lanepacked"):
        assert HP.carried_variants(
            other, channels, lowp, exact_hess) == 2 * channels


# (slots, lanes) -> (g, code passes, channels built) for two channels at
# (hi, lo), at lowp and with an exact hessian: all-exact and none-exact as
# tests/test_histogram_plan.py has them; three variants fold 4 lanes up to
# 64 slots and, under _BINLOOP_FOLD_UNDER (384 lanes), 2 at 128
FOLDS = {
    # (slots, lanes): (none exact, all exact, the hessian exact)
    (8, 4): ((4, 1, 2), (4, 1, 4), (4, 1, 2)),
    (32, 4): ((4, 1, 2), (4, 1, 2), (4, 1, 2)),
    (64, 4): ((4, 1, 2), (4, 1, 2), (4, 1, 2)),
    (128, 4): ((1, 4, 2), (4, 1, 2), (2, 2, 2)),
    (256, 4): ((1, 4, 2), (1, 4, 2), (1, 4, 2)),
    (32, 1): ((1, 1, 2), (1, 1, 4), (1, 1, 2)),
    (64, 5): ((3, 2, 2), (5, 1, 2), (5, 1, 2)),
}


@pytest.mark.parametrize("case", sorted(FOLDS))
def test_lane_groups_passes_and_built_channels_by_statement(case):
    slots, lanes = case
    for (lowp, exact_hess), want in zip(
        ((False, False), (True, False), (False, True)), FOLDS[case]
    ):
        g = HP.binloop_lane_group(lanes, 2, lowp, slots, exact_hess)
        got = (
            g,
            HP.code_passes("binloop", lanes, 2, lowp, slots, exact_hess),
            HP.stat_channels_built(2, lowp, slots, g, exact_hess),
        )
        assert got == want, (lowp, exact_hess)


@pytest.mark.parametrize("slots", SLOTS)
def test_an_exact_hessian_keeps_the_four_variant_tiles_and_chunk(slots):
    """The three-variant fit takes the (hi, lo) fit's row and feature tiles
    at every rung (the row tile sets the order its sums are added in) and
    its 256-slot chunk; the VMEM model, reckoned at four variants, holds
    the folded operand."""
    f, bins = 302, 32
    g = HP.binloop_lane_group(4, 2, False, slots, True)
    tiles = HP.binloop_tiles(f, slots, bins, lanes=g)
    assert tiles == HP.binloop_tiles(f, slots, bins)
    assert HP.binloop_vmem_bytes(*tiles, slots, bins, lanes=g) <= (
        HP._BINLOOP_VMEM_BUDGET
    )
    groups = [(55, 2), (f, bins)]
    assert HP.histogram_plan(
        "pallas", 1_002_701, 4, groups, 1 << 12, exact_hess=True
    ) == HP.histogram_plan("pallas", 1_002_701, 4, groups, 1 << 12)


def test_the_dispatch_span_names_the_three_variant_ladder():
    groups = [(55, 2), (302, 32)]
    assert TR.hist_tiles(
        1_002_701, 4, groups, 12, False, impl="pallas", exact_hess=True
    ) == "32x4:2048/104 64x4:2048/104 128x2:2048/104 256x1:1024/104"
    assert TR.stat_channels_built(
        1_002_701, 4, groups, 12, False, 2, impl="pallas", exact_hess=True
    ) == 2


def _boost_args(k=2, n=64, f=3, seed=1):
    rng = np.random.default_rng(seed)
    binned = jnp.asarray(rng.integers(0, 4, (n, f)), jnp.int32)
    y = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
    row_mask = jnp.asarray((rng.random((k, n)) < 0.8).astype(np.float32))
    margin = jnp.zeros((k, n), jnp.float32)
    eta = jnp.full((k,), 0.1, jnp.float32)
    return (binned, y, row_mask, margin, eta, 1.0, 0.0, 1.0, 0.0)


@pytest.fixture()
def stated(monkeypatch):
    """The ``exact_hess`` each ``_grow_tree_impl`` call was handed (the
    executable bank off and what was traced before dropped, so that every
    fit traces here)."""
    seen = []
    grow = TR._grow_tree_impl

    def record(*args, **kw):
        seen.append(kw.get("exact_hess", False))
        return grow(*args, **kw)

    monkeypatch.setenv("TPTPU_AOT", "0")
    monkeypatch.setattr(TR, "_grow_tree_impl", record)
    jax.clear_caches()
    yield seen
    jax.clear_caches()


@pytest.mark.parametrize(
    "objective,exact",
    [
        ("spark:logloss", True),
        ("spark:squarederror", True),
        ("reg:squarederror", True),
        ("binary:logistic", False),
    ],
)
@pytest.mark.parametrize("binary_weights", [True, False])
def test_the_boosting_body_states_an_exact_hessian_where_h_is_one(
    stated, objective, exact, binary_weights
):
    TR._boost_chunk_body(
        *_boost_args(), num_rounds=2, max_depth=2, num_bins=4,
        objective=objective, hist_impl="scatter",
        binary_weights=binary_weights,
    )
    assert stated and set(stated) == {exact and binary_weights}
    assert TR.exact_hessian(objective, binary_weights) == (
        exact and binary_weights
    )


def _gbt_table(n=512, f=4, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float32)
    return x, y


@pytest.mark.parametrize(
    "weights,exact",
    [("binary", True), ("fractional", False), ("two", False)],
)
def test_a_fractional_row_weight_never_reaches_three_variants(
    stated, weights, exact
):
    """The GBT family states 0/1 weights from its masks' values: a mask
    that is a row subset engages the exact hessian, one weight of 0.5 or
    2 keeps the (hi, lo) pair, in the batched sweep and a single fit."""
    from transmogrifai_tpu.models.gbdt import GBTClassifier

    x, y = _gbt_table()
    mask = np.ones(len(y), np.float32)
    mask[::7] = 0.0
    if weights == "fractional":
        mask[3] = 0.5
    elif weights == "two":
        mask[3] = 2.0
    est = GBTClassifier(max_iter=2, max_depth=2)
    assert TR.binary_row_weights(mask) == exact
    est.fit_arrays_batched_masks(x, y, [mask], [{}])
    est.fit_arrays(x, y, mask)
    assert stated and set(stated) == {exact}


def test_a_host_mask_stated_binary_is_checked():
    binned, y, row_mask, *_ = _boost_args()
    weights = np.asarray(row_mask) * 0.5
    with pytest.raises(ValueError, match="binary_weights"):
        TR.fit_boosted_batched(
            binned, y, weights, num_rounds=1, max_depth=2, num_bins=4,
            objective="spark:logloss", binary_weights=True,
        )


@pytest.fixture()
def binloop_on_cpu(monkeypatch):
    """The TPU's plan (the bin-loop kernel above 4,096 rows) with the
    scatter builder doing the kernel's work, so the count runs here. The
    executable bank is off and what was traced before is dropped."""
    monkeypatch.setenv("TPTPU_AOT", "0")
    monkeypatch.setattr(TR, "_resolved_impl", lambda: "pallas")
    monkeypatch.setitem(HP.BUILDERS, "binloop", HP.BUILDERS["scatter"])
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize(
    "objective,share",
    [("spark:logloss", 0.75), ("reg:squarederror", 0.75),
     ("binary:logistic", 1.0)],
)
def test_hist_slots_count_the_variants_carried(
    binloop_on_cpu, objective, share
):
    from transmogrifai_tpu.models import gbdt
    from transmogrifai_tpu.telemetry import spans as tspans

    rng = np.random.default_rng(11)
    n = 4608
    x = rng.normal(size=(n, 3)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    thr = TR.quantile_thresholds(x, 8)
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
    masks = (rng.random((4, n)) < 0.8).astype(np.float32)
    _trees, margin, slots = TR.fit_boosted_batched(
        binned, jnp.asarray(y), jnp.asarray(masks), num_rounds=2,
        max_depth=3, num_bins=8, objective=objective, return_slots=True,
        binary_weights=TR.binary_row_weights(masks),
    )
    slots = jax.tree.map(np.asarray, slots)
    builds = slots.chunks_run.sum()
    assert builds > 0
    # one feature group of two statistics: 2 x 2 a build at most
    assert slots.stack_variants_full.sum() == 4 * builds
    assert slots.stack_variants.sum() == share * 4 * builds
    tspans.reset_for_tests()
    before = TR.hist_slot_stats().snapshot()
    gbdt.await_stack_outputs({"outputs": margin, "hist_slots": slots})
    (rec,) = [
        r["args"] for r in tspans.snapshot_events()
        if r["name"] == "tree/await_outputs"
    ]
    assert (rec["stack_variants"], rec["stack_variants_full"]) == (
        int(share * 4 * builds), int(4 * builds)
    )
    now = TR.hist_slot_stats().snapshot()
    assert now["histStackVariants"] - before["histStackVariants"] == (
        share * 4 * builds
    )
    assert now["histStackVariantsFull"] - before["histStackVariantsFull"] == (
        4 * builds
    )


def test_a_forest_at_lowp_carries_half_the_variants(binloop_on_cpu):
    rng = np.random.default_rng(12)
    n = 4608
    x = rng.normal(size=(n, 3)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    thr = TR.quantile_thresholds(x, 8)
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
    masks = np.ones((4, n), np.float32)
    _trees, slots = TR.fit_forest_batched(
        binned, jnp.asarray(y), masks, num_trees=2, max_depth=3,
        num_bins=8, min_info_gain=0.0, seed=3, lowp=True,
        info_gain_norm=TR.GINI, return_slots=True,
    )
    slots = jax.tree.map(np.asarray, slots)
    assert slots.stack_variants_full.sum() > 0
    assert 2 * slots.stack_variants.sum() == slots.stack_variants_full.sum()
