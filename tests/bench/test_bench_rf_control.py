"""The forest cell's comparison fails what it has to fail.

* The controls: ``checks/forest_winner.py``'s plain reference put in the
  program's place (``reference.stand_in``) and read by the same
  ``reference.compare`` that reads the program: under every limit at the
  float32 the configuration states, over at least one with the sums and
  leaves held in bfloat16, or with a bfloat16 plane.
* The faults of this family: a forest that draws one column subset a TREE
  (the learner the program was before), one that compares ``min_info_gain``
  with the un-normalised gain, and a winner whose leaves are altered; the
  rest of a run (``run.py --rehearsal`` in a child whose program is patched
  first) prints ``correct`` false.
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

CELL = "flagship_rf.fit"


def _config():
    """The configuration as committed, its forests cut to depths and a
    count of trees a test run can hold."""
    with open(os.path.join(ROOT, "benchmarks", "configs", "flagship_rf.json")) as f:
        cfg = json.load(f)
    cfg["grid"] = {**cfg["grid"], "max_depth": [3, 5], "num_trees": [2]}
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
    ("f32", "bf16", {"leaf_value_gap"}),
    ("bf16", "f32", {"plane_gap", "thresholds_gap"}),
])
def test_stand_in_reads_under_the_limits_only_as_stated(small_ref, plane, fit, over):
    cfg = _config()
    product = reference.stand_in(cfg, small_ref, {"plane": plane, "fit": fit})
    compared = reference.compare(cfg, small_ref, product)
    assert {c["name"] for c in compared} == set(cfg["limits"])
    failed = {c["name"] for c in compared if not c["ok"]}
    assert over <= failed and bool(failed) == bool(over), compared


def test_the_reference_draws_the_stated_subsets(small_ref):
    """A forest the reference grew: every split lies in the subset that
    ``jax.random.choice(jax.random.fold_in(k_cols, j), F, (n_sub,))`` gives
    for its tree and heap index, and a tree checked against itself reads
    nothing wrong."""
    import jax

    from benchmarks.checks import forest_winner, xgb_winner

    cfg = _config()
    params = {**cfg["estimator_defaults"], "max_depth": 5, "num_trees": 2,
              "min_instances_per_node": 10, "min_info_gain": 0.001}
    _thr, codes = xgb_winner._binned(small_ref, small_ref["x"], 32)
    mask = np.ones(len(small_ref["y"]), np.float32)
    trees, _, score = forest_winner.forest(codes, small_ref["y"], mask, params)
    f = codes.shape[1]
    n_sub = forest_winner.n_subset("auto", f, 2)
    assert n_sub == int(np.ceil(np.sqrt(f))) < f
    seen = 0
    for t, (_k_boot, k_cols) in enumerate(forest_winner.tree_keys(42, 2)):
        for level in range(5):
            for node in np.nonzero(trees["split_feat"][t, level] >= 0)[0]:
                subset = np.asarray(jax.random.choice(
                    jax.random.fold_in(k_cols, (1 << level) + int(node)),
                    f, (n_sub,), replace=False))
                assert trees["split_feat"][t, level, node] in subset
                seen += 1
    assert seen >= 8 and 0.0 <= score.min() <= score.max() <= 1.0
    _, found, _ = forest_winner.forest(codes, small_ref["y"], mask, params,
                                   trees=trees)
    assert found == {"split_gain_gap": 0.0, "leaf_value_gap": 0.0,
                     "node_subset_violations": 0, "stop_rule_violations": 0}
    # a split moved to a column outside its node's subset is counted
    moved = {k: v.copy() for k, v in trees.items()}
    root = np.asarray(jax.random.choice(
        jax.random.fold_in(forest_winner.tree_keys(42, 2)[0][1], 1), f, (n_sub,),
        replace=False))
    moved["split_feat"][0, 0, 0] = next(
        c for c in range(f) if c not in root)
    _, found, _ = forest_winner.forest(codes, small_ref["y"], mask, params,
                                   trees=moved)
    assert found["node_subset_violations"] >= 1
    assert found["split_gain_gap"] == 1.0


# ------------------------------------------------------------------ faults
class _JaxWithTreeWideFoldIn:
    """``jax`` as ``models/trees.py`` sees it, with ``random.fold_in``
    deaf to the node: every node of a tree draws the tree's one subset."""

    def __init__(self):
        import jax

        self._jax = jax

        class _Random:
            def __getattr__(_self, name):
                return getattr(jax.random, name)

            @staticmethod
            def fold_in(key, data):
                return jax.random.fold_in(key, data * 0)

        self.random = _Random()

    def __getattr__(self, name):
        return getattr(self._jax, name)


def _per_tree_subsets():
    """One column subset a tree instead of one a node."""
    from transmogrifai_tpu.models import trees

    trees.jax = _JaxWithTreeWideFoldIn()


def _unnormalised_stop():
    """``min_info_gain`` compared with the gain summed over the node's
    rows, as before."""
    from transmogrifai_tpu.models.gbdt import RandomForestClassifier

    RandomForestClassifier._INFO_GAIN_NORM = 0.0


def _answer_altered():
    """The winner's parameters altered where they are produced."""
    from transmogrifai_tpu.models.gbdt import ForestClassifierModel

    get_arrays = ForestClassifierModel.get_arrays

    def altered(self):
        out = dict(get_arrays(self))
        out["c0__leaf_value"] = np.asarray(out["c0__leaf_value"]) * 1.05
        return out

    ForestClassifierModel.get_arrays = altered


FAULTS = {"per_tree_subsets": _per_tree_subsets,
          "unnormalised_stop": _unnormalised_stop,
          "answer_altered": _answer_altered}
#: the number each fault has to read over its limit (others may too)
CAUGHT_BY = {"per_tree_subsets": "node_subset_violations",
             "unnormalised_stop": "stop_rule_violations",
             "answer_altered": "leaf_value_gap"}


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
    assert not read["value"] <= read["limit"], line["compared"]


if __name__ == "__main__":
    # child of test_fault_reads_not_correct: break the program, then drive
    # the rest of a run
    os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[sys.argv[1]]()
    from benchmarks import run

    sys.exit(run.main(sys.argv[2:]))
