"""``hist_pallas.histogram_plan``: the one place that says which histogram
builder a tree fit takes and how many node slots one build may hold. The
table is the rules at the sizes that matter (the benchmark cells' own shape
first); ``fit_boosted`` is pinned as lane 0 of ``fit_boosted_batched``."""
import numpy as np
import pytest

import jax.numpy as jnp

from transmogrifai_tpu.models import hist_pallas as HP
from transmogrifai_tpu.models import trees as TR

# the cells' plane after column grouping: 55 0/1 columns, 302 at 32 bins
CELL_ROWS = 1_002_701
CELL_GROUPS = [(55, 2), (302, 32)]

# (impl, rows, lanes, groups, max_slots) -> (builders, chunk_cap)
PLAN_TABLE = {
    # histogram width 9,774; 2^25 / 4 lanes = 2^23 elements -> 512 slots;
    # the kernels' block rule: 2^19 / (8 * 128) = 512 under the ceiling 256
    "cell_depth_10": (
        ("pallas", CELL_ROWS, 4, CELL_GROUPS, 1 << 10),
        (("binloop", "binloop"), 256),
    ),
    "cell_depth_12": (
        ("pallas", CELL_ROWS, 4, CELL_GROUPS, 1 << 12),
        (("binloop", "binloop"), 256),
    ),
    "cell_depth_3": (
        ("pallas", CELL_ROWS, 4, CELL_GROUPS, 1 << 3),
        (("binloop", "binloop"), 8),
    ),
    # 2^24 / (4 lanes * 4,096 rows) = 1,024 under the GEMM ceiling 128
    "gemm_at_4096_rows": (
        ("pallas", 4096, 4, CELL_GROUPS, 1 << 10),
        (("gemm", "gemm"), 128),
    ),
    "binloop_at_4097_rows": (
        ("pallas", 4097, 4, CELL_GROUPS, 1 << 10),
        (("binloop", "binloop"), 256),
    ),
    # 2^24 / (64 lanes * 4,096 rows) = 64: the one-hot rule under the ceiling
    "gemm_many_lanes": (
        ("pallas", 4096, 64, [(12, 16)], 1 << 10),
        (("gemm",), 64),
    ),
    "gemm_never_under_8": (
        ("gemm", 1 << 20, 4, [(12, 16)], 1 << 10),
        (("gemm",), 8),
    ),
    # 256 bins: b_pad 256 -> 2^19 / (8 * 256) = 256
    "lanepacked_at_256_bins": (
        ("pallas", CELL_ROWS, 4, [(20, 256)], 1 << 10),
        (("lanepacked",), 256),
    ),
    "binloop_at_64_bins": (
        ("pallas", CELL_ROWS, 4, [(20, 64)], 1 << 10),
        (("binloop",), 256),
    ),
    "lanepacked_at_65_bins": (
        ("pallas", CELL_ROWS, 4, [(20, 65)], 1 << 10),
        (("lanepacked",), 256),
    ),
    "mixed_groups": (
        ("pallas", CELL_ROWS, 2, [(55, 2), (10, 256)], 1 << 6),
        (("binloop", "lanepacked"), 64),
    ),
    # forced builders, at sizes where "pallas" picks otherwise; scatter is
    # held by the HBM budget alone: 2^23 / 9,774 -> 512
    "scatter_forced_large": (
        ("scatter", CELL_ROWS, 4, CELL_GROUPS, 1 << 10),
        (("scatter", "scatter"), 512),
    ),
    "scatter_forced_small": (
        ("scatter", 891, 4, CELL_GROUPS, 1 << 10),
        (("scatter", "scatter"), 512),
    ),
    "gemm_forced_large": (
        ("gemm", 8192, 4, CELL_GROUPS, 1 << 10),
        (("gemm", "gemm"), 128),
    ),
    # the budget never falls under 2^20 elements: 2^20 / 9,774 -> 64
    "budget_floor_at_many_lanes": (
        ("scatter", 891, 256, CELL_GROUPS, 1 << 10),
        (("scatter", "scatter"), 64),
    ),
    # ---- the statistic axis (args: ..., stat_channels, lowp). Two channels
    # read the same whatever ``lowp``; more are held by the stacked
    # operand's 1,024 lanes: 7 one-variant channels -> 146 -> 128 slots,
    # 14 variants -> 73 -> 64; the HBM budget scales too (2^23 / (9,774 *
    # 7 / 2) -> 245 -> 128)
    "cell_depth_12_lowp": (
        ("pallas", CELL_ROWS, 4, CELL_GROUPS, 1 << 12, 2, True),
        (("binloop", "binloop"), 256),
    ),
    "seven_classes_depth_12": (
        ("pallas", CELL_ROWS, 4, CELL_GROUPS, 1 << 12, 7, True),
        (("binloop", "binloop"), 128),
    ),
    "seven_channels_two_variants": (
        ("pallas", CELL_ROWS, 4, CELL_GROUPS, 1 << 12, 7, False),
        (("binloop", "binloop"), 64),
    ),
    "seven_classes_depth_3": (
        ("pallas", CELL_ROWS, 4, CELL_GROUPS, 1 << 3, 7, True),
        (("binloop", "binloop"), 8),
    ),
    # 3 one-variant channels: 1,024 / 3 -> 341 -> 256, the ceiling's
    "three_classes_depth_12": (
        ("pallas", CELL_ROWS, 4, CELL_GROUPS, 1 << 12, 3, True),
        (("binloop", "binloop"), 256),
    ),
    # the lane-packed kernel has two accumulators: more channels are
    # planned onto the bin-loop kernel whatever the bins
    "seven_classes_at_256_bins": (
        ("pallas", CELL_ROWS, 4, [(20, 256)], 1 << 10, 7, True),
        (("binloop",), 128),
    ),
    "seven_classes_gemm": (
        ("pallas", 4096, 4, CELL_GROUPS, 1 << 10, 7, True),
        (("gemm", "gemm"), 128),
    ),
    # scatter: the HBM budget alone, 2^23 / (9,774 * 7 / 2) -> 245 -> 128
    "seven_classes_scatter": (
        ("scatter", CELL_ROWS, 4, CELL_GROUPS, 1 << 10, 7, True),
        (("scatter", "scatter"), 128),
    ),
}

# (slots, lowp, channels) -> (row_tile, feat_tile) over the cell's 302 wide
# columns at 32 bins, and the channels its operand has lanes for. Seven
# channels: the 128-slot chunk's 896 lanes halve the row tile, as four
# variants at 256 slots do; derived from the VMEM model, compiled for a
# v5e (tools/aot_v5e.py), timed in ``binloop_tiles``' docstring
CHANNEL_TILES = {
    (8, True, 7): ((2048, 104), 16),
    (32, True, 7): ((2048, 104), 8),
    (64, True, 7): ((2048, 104), 8),
    (128, True, 7): ((1024, 104), 7),
    (32, True, 2): ((2048, 104), 4),
    (32, False, 2): ((2048, 104), 2),
    (256, False, 2): ((1024, 104), 2),
}


@pytest.mark.parametrize("case", sorted(CHANNEL_TILES))
def test_tiles_and_built_channels_by_channel_count(case):
    slots, lowp, channels = case
    tiles, built = CHANNEL_TILES[case]
    assert HP.binloop_tiles(
        302, slots, 32, lowp=lowp, stat_channels=channels) == tiles
    assert HP.stat_channels_built(channels, lowp, slots) == built
    assert HP.binloop_vmem_bytes(
        *tiles, slots, 32, lowp, channels) <= HP._BINLOOP_VMEM_BUDGET
    if channels == 2:  # the default is the two-channel fit's
        assert HP.binloop_tiles(302, slots, 32, lowp=lowp) == tiles


# (channels, lowp, slots, lanes) -> g, the lanes one grid step of the
# bin-loop kernel builds: as many as its [T, g·variants·slots] operand
# holds in 1,024 lanes, spread evenly over the passes; none where one
# lane's operand is 512 lanes or more (measured slower folded)
LANE_GROUPS = {
    # two channels, four (hi, lo) variants: the boosted and GBT cells
    (2, False, 8, 4): 4,
    (2, False, 32, 4): 4,
    (2, False, 64, 4): 4,
    (2, False, 128, 4): 1,
    (2, False, 256, 4): 1,
    # two channels at lowp, two variants: the forest cell
    (2, True, 8, 4): 4,
    (2, True, 32, 4): 4,
    (2, True, 128, 4): 4,
    (2, True, 256, 4): 1,
    # seven channels at lowp: the seven-class forest cell
    (7, True, 8, 4): 4,
    (7, True, 32, 4): 4,
    (7, True, 64, 4): 2,
    (7, True, 128, 4): 1,
    # seven channels, (hi, lo): 14 variants
    (7, False, 8, 4): 4,
    (7, False, 32, 4): 2,
    (7, False, 64, 4): 1,
    # lane counts that do not divide: 5 in two passes of 3 + 2, 3 in two
    # of 2 + 1; one lane folds nothing; many lanes fold where they fit
    (2, False, 64, 5): 3,
    (7, True, 64, 3): 2,
    (2, False, 32, 1): 1,
    (2, False, 32, 12): 6,
    (2, False, 8, 24): 24,
}


@pytest.mark.parametrize(
    "case", sorted(LANE_GROUPS), ids=lambda c: "{}ch{}-{}slots-{}lanes".format(
        c[0], "-lowp" if c[1] else "", c[2], c[3]
    ),
)
def test_lane_group_by_channels_precision_slots_and_lanes(case):
    channels, lowp, slots, lanes = case
    g = HP.binloop_lane_group(lanes, channels, lowp, slots)
    assert g == LANE_GROUPS[case]
    # the folded operand fits the lanes measured
    one = HP.stack_variants(channels, lowp) * max(slots, 8)
    assert g * one <= HP._BINLOOP_STACK_LANES or g == 1
    assert g == 1 or one < HP._BINLOOP_FOLD_UNDER
    passes = -(-lanes // g)
    if one < HP._BINLOOP_FOLD_UNDER:  # no fewer passes exist
        assert passes == -(-lanes // (HP._BINLOOP_STACK_LANES // one))
    assert HP.code_passes("binloop", lanes, channels, lowp, slots) == passes
    assert HP.code_passes("scatter", lanes, channels, lowp, slots) == lanes


# (channels, lowp) -> the fits' widths: every rung a plan of the cells'
# shape can build at (the ladder under the chunk cap the plan gives)
FOLD_RUNGS = {
    (2, False): (8, 32, 64, 128, 256),
    (2, True): (8, 32, 64, 128, 256),
    (7, True): (8, 32, 64, 128),
    (7, False): (8, 32, 64),
}


@pytest.mark.parametrize("group", CELL_GROUPS)
@pytest.mark.parametrize("case", sorted(FOLD_RUNGS))
def test_folded_tiles_stay_in_the_vmem_budget_at_every_planned_rung(
    case, group
):
    """Four lanes at each rung of the cells' shape: the tiles the folded
    operand is given keep the VMEM model under the budget, and they are
    the one-lane build's (the measured table): each lane adds the same row
    tiles in the same order, so its histograms are the one-lane build's
    bit for bit."""
    channels, lowp = case
    f, bins = group
    plan = HP.histogram_plan(
        "pallas", CELL_ROWS, 4, CELL_GROUPS, 1 << 12, channels, lowp
    )
    for slots in FOLD_RUNGS[case]:
        assert slots <= plan.chunk_cap
        g = HP.binloop_lane_group(4, channels, lowp, slots)
        row_tile, feat_tile = HP.binloop_tiles(
            f, slots, bins, lowp=lowp, stat_channels=channels, lanes=g
        )
        assert HP.binloop_vmem_bytes(
            row_tile, feat_tile, slots, bins, lowp, channels, g
        ) <= HP._BINLOOP_VMEM_BUDGET
        assert (row_tile, feat_tile) == HP.binloop_tiles(
            f, slots, bins, lowp=lowp, stat_channels=channels
        )


# (channels, lowp, slots, lanes) -> the channels the folded operand has
# lanes for: 7 at 32 slots four lanes a pass fill 896 lanes, at 8 slots
# 224 lanes pad to 256
FOLDED_CHANNELS_BUILT = {
    (7, True, 32, 4): 7,
    (7, True, 8, 4): 8,
    (7, True, 64, 2): 7,
    (2, True, 32, 4): 2,
    (2, False, 32, 4): 2,
    (2, False, 8, 4): 2,
}


@pytest.mark.parametrize("case", sorted(FOLDED_CHANNELS_BUILT))
def test_built_channels_of_a_folded_operand(case):
    channels, lowp, slots, lanes = case
    assert HP.stat_channels_built(
        channels, lowp, slots, lanes) == FOLDED_CHANNELS_BUILT[case]


@pytest.mark.parametrize("case", sorted(PLAN_TABLE))
def test_plan_table(case):
    args, (builders, chunk_cap) = PLAN_TABLE[case]
    plan = HP.histogram_plan(*args)
    assert plan == HP.HistogramPlan(builders, chunk_cap)
    assert plan.chunk_cap & (plan.chunk_cap - 1) == 0, "a power of two"


def test_every_builder_is_reached_by_some_plan():
    """A builder no input selects is code no fit can run: the table has to
    reach every key of ``BUILDERS``, and name nothing else."""
    reached = set()
    for args, _want in PLAN_TABLE.values():
        reached.update(HP.histogram_plan(*args).builders)
    assert reached == set(HP.BUILDERS)


def _boost_table(n=700, f=6, bins=16, seed=4):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    x[:, f - 2:] = x[:, f - 2:] > 0.2
    score = x[:, 0] - x[:, 1] * x[:, 2] + x[:, f - 1]
    thr = TR.quantile_thresholds(x, bins)
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
    mask = (rng.uniform(size=n) < 0.8).astype(np.float32)
    return binned, score + 0.3 * rng.normal(size=n), mask


@pytest.mark.parametrize("objective", ["binary:logistic", "reg:squarederror"])
def test_fit_boosted_is_lane_0_of_the_batched_fit(objective):
    binned, score, mask = _boost_table()
    y = (score > 0).astype(np.float32) if "logistic" in objective else (
        score.astype(np.float32))
    kw = dict(num_rounds=3, max_depth=4, num_bins=16, eta=0.3,
              reg_lambda=1.0, gamma=0.1, min_child_weight=2.0,
              base_score=0.25, objective=objective)
    trees, margin = TR.fit_boosted(binned, y, mask, **kw)
    masks = np.stack([mask, 1.0 - mask])
    trees_k, margin_k = TR.fit_boosted_batched(binned, y, masks, **kw)
    assert np.asarray(trees.split_feat).shape == (3, 4, 16)
    assert (np.asarray(trees.split_feat) >= 0).sum() > 6, "it grew trees"
    for got, want in zip(trees, trees_k):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want)[0])
    np.testing.assert_array_equal(np.asarray(margin), np.asarray(margin_k)[0])
