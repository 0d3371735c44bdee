"""The multiclass forest cell's comparison fails what it has to fail.

* The controls: ``checks/forest_multiclass_winner.py``'s plain reference put
  in the program's place (``reference.stand_in``) and read by the same
  ``reference.compare`` that reads the program: under every limit at the
  float32 the configuration states, over at least one with the sums and
  leaves held in bfloat16, or with a bfloat16 plane.
* The faults of this family, each planted in the program before the rest of
  a run (``run.py --rehearsal`` in a child): K one-vs-rest indicator forests
  where the configuration guarantees one forest (the learner the program
  was before), a gain that is the binary Gini of class 1 alone, and a
  winner whose leaves are no distributions; each prints ``correct`` false.
* The gate: a program that states no forest over K classes, or another,
  is not run at all.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import datagen, datagen_multiclass, reference  # noqa: E402

CELL = "flagship_rf_multiclass.fit"


def _config():
    """The configuration as committed, its forests cut to depths and a
    count of trees a test run can hold."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "flagship_rf_multiclass.json")) as f:
        cfg = json.load(f)
    cfg["grid"] = {**cfg["grid"], "max_depth": [3, 5], "num_trees": [2]}
    return cfg


@pytest.fixture(scope="module")
def small_ref():
    """What ``reference.build`` gives, for a plane the reference makes
    alone: every raw column through its default vectorizer, a quarter of
    the hash buckets, the seven-class label."""
    n, seed = 6144, 2147483783
    table = datagen_multiclass.multiclass_table(n, seed)
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
    ("f32", "bf16", {"leaf_value_gap", "class_prob_gap"}),
    ("bf16", "f32", {"plane_gap", "thresholds_gap"}),
])
def test_stand_in_reads_under_the_limits_only_as_stated(small_ref, plane, fit, over):
    cfg = _config()
    product = reference.stand_in(cfg, small_ref, {"plane": plane, "fit": fit})
    compared = reference.compare(cfg, small_ref, product)
    assert {c["name"] for c in compared} == set(cfg["limits"])
    assert set(cfg["limits_why"]) == set(cfg["limits"])
    failed = {c["name"] for c in compared if not c["ok"]}
    assert over <= failed and bool(failed) == bool(over), compared


def test_the_table_has_seven_classes_at_the_stated_shares():
    """The label's shares lie within a point of the stated ones at a size a
    test can make (the constants are the file's, not the seed's), the
    smallest class is in every fold, and nothing else of the table moved."""
    n = 60000
    table = datagen_multiclass.multiclass_table(n, 2147483801)
    plain = datagen.flagship_table(n, 2147483801)
    assert set(table) == set(plain)
    for name in plain:
        if name == "label":
            continue
        a, b = table[name], plain[name]
        if isinstance(a, tuple):
            assert all(np.array_equal(u, v) for u, v in zip(a, b))
        else:
            assert np.array_equal(a, b)
    shares = 100.0 * np.bincount(table["label"].astype(int), minlength=7) / n
    assert len(shares) == datagen_multiclass.CLASSES == 7
    assert np.abs(shares - np.asarray(datagen_multiclass.SHARES)).max() < 1.0
    assert shares.min() >= 0.4
    again = datagen_multiclass.multiclass_table(n, 2147483801)
    assert np.array_equal(again["label"], table["label"])


@pytest.mark.parametrize("seed", [0, 2147483791, 3400000999])
def test_a_runs_seed_names_the_classes_and_moves_nothing_else(seed):
    """The cell's table is one table (``table_seed``); a run's seed permutes
    the class ids: the same rows carry the same class under another name,
    every other column is where it was, and the same seed gives the same
    names."""
    n, table_seed = 4096, _config()["table_seed"]
    plain = datagen_multiclass.multiclass_table(n, table_seed)
    order = datagen_multiclass.class_order(seed)
    assert sorted(order.tolist()) == list(range(datagen_multiclass.CLASSES))
    assert np.array_equal(order, datagen_multiclass.class_order(seed))
    named = datagen_multiclass.multiclass_table(n, table_seed, order)
    assert np.array_equal(named["label"], order[plain["label"].astype(int)])
    for name in plain:
        if name == "label":
            continue
        a, b = named[name], plain[name]
        if isinstance(a, tuple):
            assert all(np.array_equal(u, v) for u, v in zip(a, b))
        else:
            assert np.array_equal(a, b)


def test_two_seeds_name_the_classes_differently():
    orders = {tuple(datagen_multiclass.class_order(s).tolist())
              for s in range(3400000000, 3400000016)}
    assert len(orders) > 12


def test_the_reference_grows_and_judges_by_the_k_class_gini(small_ref):
    """A forest the reference grew, checked against itself, reads nothing
    wrong; a leaf that is no distribution, a split outside its node's
    subset and a split whose Gini gain lies under ``min_info_gain`` are
    each counted."""
    import jax

    from benchmarks.checks import forest_multiclass_winner as fw
    from benchmarks.checks import xgb_winner

    cfg = _config()
    params = {**cfg["estimator_defaults"], "max_depth": 5, "num_trees": 2,
              "min_instances_per_node": 10, "min_info_gain": 0.001}
    _thr, codes = xgb_winner._binned(small_ref, small_ref["x"], 32)
    y = small_ref["y"]
    mask = np.ones(len(y), np.float32)
    trees, _, prob, _ = fw.forest(codes, y, mask, params, 7)
    assert trees["leaf_value"].shape == (2, 32, 7)
    assert (trees["split_feat"] >= 0).sum() >= 12
    assert np.abs(prob.sum(axis=1) - 1).max() < 1e-5
    # two depth-5 trees still beat the majority class's share
    assert (prob.argmax(1) == y).mean() > (y == 0).mean() + 0.005
    _, found, mine, theirs = fw.forest(codes, y, mask, params, 7, trees=trees)
    assert found == {"split_gain_gap": 0.0, "leaf_value_gap": 0.0,
                     "class_prob_gap": 0.0, "node_subset_violations": 0,
                     "stop_rule_violations": 0}
    assert np.array_equal(mine, theirs)
    f = codes.shape[1]
    n_sub = fw.n_subset("auto", f, 2)
    root = np.asarray(jax.random.choice(
        jax.random.fold_in(fw.tree_keys(42, 2)[0][1], 1), f, (n_sub,),
        replace=False))
    assert trees["split_feat"][0, 0, 0] in root
    moved = {k: v.copy() for k, v in trees.items()}
    moved["split_feat"][0, 0, 0] = next(c for c in range(f) if c not in root)
    _, found, _, _ = fw.forest(codes, y, mask, params, 7, trees=moved)
    assert found["node_subset_violations"] >= 1
    assert found["split_gain_gap"] == 1.0
    scaled = {**trees, "leaf_value": trees["leaf_value"] * 1.05}
    _, found, _, _ = fw.forest(codes, y, mask, params, 7, trees=scaled)
    assert found["leaf_value_gap"] > 0.01 and found["split_gain_gap"] == 0.0
    _, found, _, _ = fw.forest(
        codes, y, mask, {**params, "min_info_gain": 0.2}, 7, trees=trees)
    assert found["stop_rule_violations"] >= 1
    assert fw.weighted_f1(np.array([0, 0, 1, 2]), np.array([0, 1, 1, 1])) == (
        pytest.approx(0.5 * (2 / 3) + 0.25 * 0.5 + 0.0))


# ------------------------------------------------------------------ faults
def _one_vs_rest():
    """K indicator forests, one a class, their shares normalised: the
    learner the program was before (it still STATES one forest)."""
    from transmogrifai_tpu.models import gbdt

    forest = gbdt.RandomForestClassifier
    fit_one = forest.fit_arrays

    class OneVsRest(gbdt.ForestClassifierModel):
        def __init__(self, models):
            super().__init__(models[0].thresholds, models[0].trees)
            self.models = models

        def predict_arrays(self, x):
            p = np.stack(
                [m.predict_arrays(x)[1][:, 1] for m in self.models], axis=1)
            prob = p / np.maximum(p.sum(axis=1, keepdims=True), 1e-12)
            return prob.argmax(axis=1).astype(np.float64), prob, p

        def get_arrays(self):
            out = {"thresholds": self.thresholds}
            for c, m in enumerate(self.models):
                for key, value in m.get_arrays().items():
                    if key != "thresholds":
                        out[key.replace("c0__", f"c{c}__")] = value
            return out

    def fit_arrays(self, x, y, row_mask):
        classes = self._num_classes(y, row_mask)
        return OneVsRest([
            fit_one(self, x, (y == c).astype(np.float64), row_mask)
            for c in range(classes)])

    forest.fit_arrays = fit_arrays
    forest._fit_group_masks = lambda self, *a: None


def _binary_gini_on_class_1():
    """The gain sees class 1's indicator alone: every other class's
    channel is empty, so the K-class Gini is class 1's binary one."""
    from transmogrifai_tpu.models import trees

    sound = trees.forest_gradients

    def class_1_alone(target, k_fits, num_classes):
        grad = sound(target, k_fits, num_classes)
        if grad.ndim == 2:
            return grad
        return grad.at[:, 1:, :].set(0.0)

    trees.forest_gradients = class_1_alone


def _leaves_not_normalised():
    """The winner's leaves hold each class's share 5% high: no
    distribution."""
    from transmogrifai_tpu.models.gbdt import ForestClassifierModel

    get_arrays = ForestClassifierModel.get_arrays

    def altered(self):
        out = dict(get_arrays(self))
        out["c0__leaf_value"] = np.asarray(out["c0__leaf_value"]) * 1.05
        return out

    ForestClassifierModel.get_arrays = altered


def _states_one_vs_rest():
    from transmogrifai_tpu.models import gbdt

    gbdt.FOREST_MULTICLASS = "one-vs-rest: K indicator forests"


def _states_nothing():
    from transmogrifai_tpu.models import gbdt

    del gbdt.FOREST_MULTICLASS


FAULTS = {"none": lambda: None,
          "one_vs_rest": _one_vs_rest,
          "binary_gini_on_class_1": _binary_gini_on_class_1,
          "leaves_not_normalised": _leaves_not_normalised,
          "states_one_vs_rest": _states_one_vs_rest,
          "states_nothing": _states_nothing}
#: the number each fault has to read over its limit (others may too)
CAUGHT_BY = {"one_vs_rest": "leaf_value_gap",
             "binary_gini_on_class_1": "split_gain_gap",
             "leaves_not_normalised": "leaf_value_gap"}


def _child(fault, tmp_path, seed="2147483790", trace="0"):
    """``run.py --rehearsal`` after ``fault``. The default seed names the
    two large classes 0 and 1 (``class_order``: [0 1 5 4 3 2 6]), so a gain
    that sees class 1 alone still finds splits, and they are other ones."""
    flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    # the patched program must be traced and compiled here: the executable
    # bank would hand back the sound program's executable
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags,
           "JAX_ENABLE_COMPILATION_CACHE": "false",
           "TPTPU_COMPILE_CACHE": str(tmp_path)}
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), fault, "--workload", CELL,
         "--seed", seed, "--seconds", "1", "--trace", trace, "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )


@pytest.mark.parametrize("fault", sorted(CAUGHT_BY))
def test_fault_reads_not_correct(fault, tmp_path):
    done = _child(fault, tmp_path)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, line["compared"]
    read = line["compared"][CAUGHT_BY[fault]]
    value = float(read["value"])  # "inf" where a shape differs
    assert not value <= read["limit"], line["compared"]


def test_every_seed_does_the_same_work_on_another_label(tmp_path):
    """Two runs of the cell under two seeds: both ``correct``, the forests'
    node slots filled alike (the same nodes on the same rows), the classes
    named differently."""
    lines = []
    for seed in ("7", "3400000999"):
        done = _child("none", tmp_path, seed=seed, trace="1")
        assert done.returncode == 0, done.stderr[-2000:]
        lines.append(json.loads(done.stdout.strip().splitlines()[-1]))
    assert all(line["correct"] is True for line in lines), lines
    slots = [line["metrics"]["mc_forest_slot_occupancy_pct"]["value"]
             for line in lines]
    assert slots[0] == slots[1] and slots[0] > 0
    assert (datagen_multiclass.class_order(7).tolist()
            != datagen_multiclass.class_order(3400000999).tolist())


@pytest.mark.parametrize("fault", ["states_one_vs_rest", "states_nothing"])
def test_a_program_that_states_another_forest_is_not_run(fault, tmp_path):
    """The driver reads the statement before it makes the table: a program
    without the K-class learner ends in seconds, with no result line."""
    done = _child(fault, tmp_path)
    assert done.returncode != 0
    assert not done.stdout.strip()
    assert "not run" in done.stderr


if __name__ == "__main__":
    # child of the tests above: break the program, then drive the rest of
    # a run
    os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[sys.argv[1]]()
    from benchmarks import run

    sys.exit(run.main(sys.argv[2:]))
