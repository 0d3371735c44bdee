"""The gradient-boosted cell's comparison fails what it has to fail.

* The controls: ``checks/gbt_winner.py``'s plain reference put in the
  program's place (``reference.stand_in``) and read by the same
  ``reference.compare`` that reads the program: under every limit at the
  float32 the configuration states, over at least one with the kernel's
  INPUTS (the targets) rounded to bfloat16 and every sum in float32, or
  with a bfloat16 plane.
* The faults of this family: Newton trees on 0/1 labels under GBT's name
  (the learner the program was before), a child's weight compared as a sum
  of hessians (a quarter of a row each at the first round), and a first
  tree scaled by ``step_size``; the rest of a run (``run.py
  --rehearsal`` in a child whose program is patched first) prints
  ``correct`` false.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import datagen, reference  # noqa: E402

CELL = "flagship_gbt.fit"


def _config():
    """The configuration as committed, its trees cut to depths a test run
    can hold."""
    with open(os.path.join(ROOT, "benchmarks", "configs", "flagship_gbt.json")) as f:
        cfg = json.load(f)
    cfg["grid"] = {**cfg["grid"], "max_depth": [3, 5]}
    return cfg


@pytest.fixture(scope="module")
def small_ref():
    """What ``reference.build`` gives, for a plane the reference makes
    alone: every raw column through its default vectorizer, a quarter of
    the hash buckets."""
    n, seed = 6144, 2147483783
    table = datagen.flagship_table(n, seed)
    null = reference.NULL
    columns = []
    for kind, count in (("real", 10), ("int", 5), ("bin", 3)):
        for j in range(count):
            columns += [(f"{kind}_{j}", None, None), (f"{kind}_{j}", null, None)]
    for j, levels in enumerate(datagen.PICK_LEVELS):
        columns += [(f"pick_{j}", f"P{j}{c}", None) for c in range(levels)]
        columns.append((f"pick_{j}", null, None))
    columns += [("text_0", None, f"hash_{b}") for b in range(0, 512, 4)]
    columns.append(("text_0", null, None))
    return reference.build(_config(), table, columns, seed)


@pytest.mark.parametrize("plane,fit,over", [
    ("f32", "f32", set()),
    ("f32", "bf16", {"residual_gap"}),
    ("bf16", "f32", {"plane_gap", "thresholds_gap"}),
])
def test_stand_in_reads_under_the_limits_only_as_stated(small_ref, plane, fit, over):
    cfg = _config()
    product = reference.stand_in(cfg, small_ref, {"plane": plane, "fit": fit})
    compared = reference.compare(cfg, small_ref, product)
    assert {c["name"] for c in compared} == set(cfg["limits"])
    failed = {c["name"] for c in compared if not c["ok"]}
    assert over <= failed and bool(failed) == bool(over), compared


def test_the_reference_grows_by_the_stated_rule(small_ref):
    """Trees the reference grew, checked against themselves, read nothing
    wrong; the first tree's leaves are means of +-1 and the second round's
    targets are the stated gradient; a child under the row count, a leaf
    moved and a wrong weight are each counted."""
    from benchmarks.checks import gbt_winner, xgb_winner

    cfg = _config()
    params = {**cfg["estimator_defaults"], "max_depth": 5, "max_iter": 2,
              "min_instances_per_node": 10, "min_info_gain": 0.001}
    _thr, codes = xgb_winner._binned(small_ref, small_ref["x"], 32)
    y = small_ref["y"]
    mask = np.ones(len(y), np.float32)
    trees, _, margin = gbt_winner.boosted(codes, y, mask, params)
    assert np.abs(trees["leaf_value"][0]).max() <= 1.0
    assert (trees["split_feat"] >= 0).sum() >= 20
    assert gbt_winner.tree_weights(params).tolist() == [
        1.0, np.float32(0.1)]
    # F = T_1 + 0.1 T_2 on every row: recompute by routing the rows
    n = len(y)
    total = np.zeros(n, np.float32)
    for r, w in enumerate(gbt_winner.tree_weights(params)):
        node = np.zeros(n, np.int64)
        for lv in range(5):
            sf, sb = trees["split_feat"][r, lv], trees["split_bin"][r, lv]
            code = np.asarray(codes)[np.arange(n), np.maximum(sf[node], 0)]
            node = node * 2 + ((sf[node] >= 0) & (code > sb[node]))
        if r == 1:
            # the second tree's root mean is the mean stated gradient
            ys = 2 * y - 1
            t = 4 * ys / (1 + np.exp(2 * ys * total))
            assert trees["split_feat"][1, 0, 0] >= 0
            np.testing.assert_allclose(
                np.bincount(node, t, 32).sum() / n, t.mean(), rtol=1e-5)
        total = total + w * trees["leaf_value"][r][node]
    np.testing.assert_allclose(margin, total, rtol=1e-5, atol=1e-6)
    _, found, _ = gbt_winner.boosted(codes, y, mask, params, trees=trees)
    assert found == {"split_gain_gap": 0.0, "leaf_value_gap": 0.0,
                     "stop_rule_violations": 0, "residual_gap": 0.0}
    # trees grown at 10 rows a child, judged at 400: children too small
    _, found, _ = gbt_winner.boosted(
        codes, y, mask, {**params, "min_instances_per_node": 400},
        trees=trees)
    assert found["stop_rule_violations"] >= 1
    assert found["split_gain_gap"] == 1.0
    # judged at a min_info_gain the trees were not grown under: early splits
    _, found, _ = gbt_winner.boosted(
        codes, y, mask, {**params, "min_info_gain": 0.05}, trees=trees)
    assert found["stop_rule_violations"] >= 1
    moved = {k: v.copy() for k, v in trees.items()}
    moved["leaf_value"][1] *= 1.01
    _, found, _ = gbt_winner.boosted(codes, y, mask, params, trees=moved)
    assert 0.001 < found["leaf_value_gap"]
    # the second round's leaves are off by a hundredth of themselves, in
    # the target's units; the first round's are not a residual's
    assert found["residual_gap"] == pytest.approx(
        0.01 * np.abs(trees["leaf_value"][1]).max(), rel=1e-3)
    moved = {k: v.copy() for k, v in trees.items()}
    moved["leaf_value"][0] *= 1.01
    _, found, _ = gbt_winner.boosted(codes, y, mask, params, trees=moved)
    assert found["residual_gap"] < 1e-6 < found["leaf_value_gap"]


@pytest.mark.parametrize("stated,gap", [
    ([1.0, 0.1], 0.0), ([0.1, 0.1], 0.9), ([1.0, 1.0], 0.9),
    ([0.5, 0.2], 0.6), ([1.0], np.inf), (None, np.inf),
])
def test_first_tree_weight_gap_reads_what_the_product_states(
        small_ref, stated, gap):
    cfg = _config()
    product = reference.stand_in(cfg, small_ref, {"plane": "f32", "fit": "f32"})
    arrays = product["winner"]["arrays"]
    if stated is None:
        del arrays["tree_weights"]
    else:
        arrays["tree_weights"] = np.asarray(stated, np.float32)
    read = {c["name"]: c for c in reference.compare(cfg, small_ref, product)}
    assert read["first_tree_weight_gap"]["value"] == pytest.approx(gap, abs=1e-6)
    assert read["first_tree_weight_gap"]["ok"] == (gap == 0.0)


def test_a_large_leaf_is_summed_in_row_blocks():
    """A shallow tree's leaf holds a third of the table, and from round 2
    its targets are a few hundred distinct inexact values: one float32
    running sum over them strays from the leaf's mean by more than the
    distance between two score levels (PR 31, seed 631800289). The
    reference's leaves are the float64 means, rounded once."""
    from benchmarks.checks import gbt_winner

    rng = np.random.default_rng(31)
    n, slots = 400_000, 4
    node = (rng.random(n) < 0.25).astype(np.int32) * rng.integers(1, slots, n).astype(np.int32)
    values = rng.normal(-0.3, 1.0, 128).astype(np.float32)
    t = values[rng.integers(0, 128, n)]
    w = np.ones(n, np.float32)
    mean = np.bincount(node, t.astype(np.float64), slots) / np.bincount(node, minlength=slots)
    leaf, dist, gap = gbt_winner._leaves(t, w, node, mean.astype(np.float32), slots)
    assert leaf.dtype == np.float32 and dist == gap == 0.0
    assert np.abs(leaf - mean).max() < 5e-8
    # what it replaced: the running sum the check had before
    running = np.zeros(slots, np.float32)
    np.add.at(running, node, t)
    assert np.abs(running / np.bincount(node, minlength=slots) - mean).max() > 5e-6


# ------------------------------------------------------------------ faults
def _newton_leaves():
    """XGBoost's learner under GBT's name, as up to PR 31: Newton trees
    (g = p - y, h = p (1 - p)) on 0/1 labels."""
    from transmogrifai_tpu.models.gbdt import GBTClassifier

    GBTClassifier._OBJECTIVE = "binary:logistic"


def _hessian_child_stop():
    """``min_instances_per_node`` compared with a sum of first-round
    hessians, a quarter of a row each: a child needs four times the rows."""
    from transmogrifai_tpu.models.gbdt import GBTClassifier

    normalize = GBTClassifier._normalize_boost

    def weighted(self, merged):
        out = normalize(self, merged)
        out["min_child_weight"] = 4.0 * out["min_child_weight"]
        return out

    GBTClassifier._normalize_boost = weighted


def _first_tree_at_step_size():
    """The first tree scaled by ``step_size`` like the rest, in what the
    model states and serves (the weights ``get_arrays`` hands the check)."""
    from transmogrifai_tpu.models import trees

    trees.boost_tree_weights = lambda objective, rounds, eta: np.full(
        int(rounds), eta, np.float32)


FAULTS = {"newton_leaves": _newton_leaves,
          "hessian_child_stop": _hessian_child_stop,
          "first_tree_at_step_size": _first_tree_at_step_size}
#: the number each fault has to read over its limit (others may too)
CAUGHT_BY = {"newton_leaves": "leaf_value_gap",
             "hessian_child_stop": "stop_rule_violations",
             "first_tree_at_step_size": "first_tree_weight_gap"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_reads_not_correct(fault, tmp_path):
    flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    # the patched program must be traced and compiled here: the executable
    # bank would hand back the sound program's executable
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags,
           "JAX_ENABLE_COMPILATION_CACHE": "false",
           "TPTPU_COMPILE_CACHE": str(tmp_path)}
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__), fault, "--workload", CELL,
         "--seed", "2147483791", "--seconds", "1", "--trace", "0",
         "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, line["compared"]
    read = line["compared"][CAUGHT_BY[fault]]
    value = float(read["value"])  # an unreadable number is "inf"
    assert not value <= read["limit"], line["compared"]


if __name__ == "__main__":
    # child of test_fault_reads_not_correct: break the program, then drive
    # the rest of a run
    os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[sys.argv[1]]()
    from benchmarks import run

    sys.exit(run.main(sys.argv[2:]))
