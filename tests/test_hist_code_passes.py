"""``HistSlots.code_passes`` / ``lane_builds``: the fit program's count of
the passes its histogram builds made over the codes, and of the lanes they
built. The bin-loop kernel builds ``hist_pallas.binloop_lane_group`` lanes a
pass, every other builder one lane a pass; ``await_outputs`` lands the sums
on the ``tree/await_outputs`` span and the ``tree`` ledger."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.models import hist_pallas as HP
from transmogrifai_tpu.models import trees as TR

BINS = 8
LANES = 4


def _table(n: int, seed: int = 5):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] * x[:, 2] > 0).astype(np.float32)
    thr = TR.quantile_thresholds(x, BINS)
    masks = (rng.random((LANES, n)) < 0.8).astype(np.float32)
    return TR.bin_data(jnp.asarray(x), jnp.asarray(thr)), y, masks


def _forest(binned, y, masks):
    _trees, outs, slots = TR.fit_forest_batched(
        binned, jnp.asarray(y), masks, num_trees=2, max_depth=3,
        num_bins=BINS, min_info_gain=0.0, seed=3, info_gain_norm=TR.GINI,
        return_outputs=True, return_slots=True,
    )
    return outs, jax.tree.map(np.asarray, slots)


def test_a_builder_without_lane_groups_makes_a_pass_a_lane():
    binned, y, masks = _table(1024)
    _outs, slots = _forest(binned, y, masks)
    assert slots.code_passes.shape == (2, 3)
    # every level of both trees builds (4 lanes, one feature group)
    builds = slots.chunks_run.sum()
    assert builds > 0
    assert slots.lane_builds.sum() == LANES * builds
    np.testing.assert_array_equal(slots.code_passes, slots.lane_builds)


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


def test_the_bin_loop_kernel_builds_four_lanes_a_pass(binloop_on_cpu):
    from transmogrifai_tpu.models import gbdt
    from transmogrifai_tpu.telemetry import spans as tspans

    binned, y, masks = _table(4608)
    outs, slots = _forest(binned, y, masks)
    builds = slots.chunks_run.sum()
    assert builds > 0
    # 4 lanes at 8 slots fold into one pass: a quarter of the lanes
    assert HP.binloop_lane_group(LANES, 2, False, 8) == LANES
    assert slots.lane_builds.sum() == LANES * builds
    assert slots.code_passes.sum() == builds
    tspans.reset_for_tests()
    before = TR.hist_slot_stats().snapshot()
    gbdt.await_stack_outputs({"outputs": outs, "hist_slots": slots})
    (rec,) = [
        r["args"] for r in tspans.snapshot_events()
        if r["name"] == "tree/await_outputs"
    ]
    assert (rec["code_passes"], rec["lane_builds"]) == (
        int(builds), int(LANES * builds)
    )
    now = TR.hist_slot_stats().snapshot()
    assert now["histCodePasses"] - before["histCodePasses"] == builds
    assert now["histLaneBuilds"] - before["histLaneBuilds"] == LANES * builds
