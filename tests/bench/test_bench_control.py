"""The comparison fails what it has to fail.

* The controls: the plain reference put in the program's place
  (``reference.stand_in``) and read by the same ``reference.compare`` that
  reads the program. At the precision the configuration states (float32)
  every number is under its limit; one step below (bfloat16), in the plane
  or in the fit, at least one number is over (the same on the chip at the
  cell's own size: PERF.md section 2).
* The faults: the rest of a run driven with the timed path broken
  underneath (``run.py --rehearsal`` in a child whose program is patched
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

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _cells_checked_by(check):
    """The cells whose configuration names ``check``: the faults below are
    faults of that check's family (a cell with another check brings its
    own)."""
    files = {c["name"]: c["file"] for c in BENCH["configs"]}
    return [
        w["name"] for w in BENCH["workloads"]
        if json.load(open(os.path.join(ROOT, files[w["config"]])))["check"] == check
    ]


def _config(name):
    """The configuration as committed, its trees cut to a depth a test run
    can hold."""
    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg["grid"] = {**cfg["grid"], "max_depth": [5]}
    return cfg


@pytest.fixture(scope="module")
def small_ref():
    """What ``reference.build`` gives, for a plane the reference makes
    alone: every raw column through its default vectorizer, half of the
    hash buckets."""
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
    columns += [("text_0", None, f"hash_{b}") for b in range(0, 512, 2)]
    columns.append(("text_0", null, None))
    return reference.build(_config("flagship_xgb"), table, columns, seed)


@pytest.mark.parametrize("plane,fit,over", [
    ("f32", "f32", set()),
    ("f32", "bf16", {"leaf_value_gap"}),
    ("bf16", "f32", {"plane_gap", "thresholds_gap"}),
])
def test_stand_in_reads_under_the_limits_only_as_stated(small_ref, plane, fit, over):
    cfg = _config("flagship_xgb")
    product = reference.stand_in(cfg, small_ref, {"plane": plane, "fit": fit})
    compared = reference.compare(cfg, small_ref, product)
    assert {c["name"] for c in compared} == set(cfg["limits"])
    failed = {c["name"] for c in compared if not c["ok"]}
    assert over <= failed and bool(failed) == bool(over), compared


# ------------------------------------------------------------------ faults
def _half_batch():
    """Half of the rows left out of every fit, the mean taken over the
    rest."""
    from transmogrifai_tpu.selector.validators import Validator

    validate = Validator.validate

    def halved(self, candidates, x, y, evaluator, extra_masks=(), **kw):
        keep = (np.arange(len(y)) % 2 == 0)
        extra = [np.asarray(m) * keep for m in extra_masks]
        orig_split = self.split_masks
        self.split_masks = lambda yy: [
            (tm & keep, vm) for tm, vm in orig_split(yy)]
        try:
            return validate(self, candidates, x, y, evaluator,
                            extra_masks=extra, **kw)
        finally:
            self.split_masks = orig_split

    Validator.validate = halved


def _answer_altered():
    """The winner's parameters altered where they are produced."""
    from transmogrifai_tpu.models.gbdt import BoostedBinaryModel

    get_arrays = BoostedBinaryModel.get_arrays

    def altered(self):
        out = dict(get_arrays(self))
        out["leaf_value"] = np.asarray(out["leaf_value"]) * 1.05
        return out

    BoostedBinaryModel.get_arrays = altered


def _metric_altered():
    """A fold's validation metric altered where it is produced."""
    from transmogrifai_tpu.evaluators import binary

    aupr = binary.aupr
    binary.aupr = lambda y, score: aupr(y, score) + 0.01


def _wrong_winner():
    """The grid point with the worst validation metric declared the
    winner."""
    from transmogrifai_tpu.selector.validators import Validator

    Validator.best = staticmethod(
        lambda results, evaluator: min(results, key=lambda r: r.metric_mean))


FAULTS = {"half_batch": _half_batch, "answer_altered": _answer_altered,
          "metric_altered": _metric_altered, "wrong_winner": _wrong_winner}
#: the number each fault has to read over its limit (others may too)
CAUGHT_BY = {"half_batch": "fold_metric_gap", "answer_altered": "leaf_value_gap",
             "metric_altered": "fold_metric_gap", "wrong_winner": "winner_not_best"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", _cells_checked_by("xgb_winner"))
def test_fault_reads_not_correct(cell, fault):
    flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags,
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__), fault, "--workload", cell,
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
