"""Serve-side Pallas traversal suite (models/serve_pallas.py): the
level-synchronous one-hot kernel's interpret-mode CPU twin must be
BIT-IDENTICAL to the gather traversal (``vmap(predict_tree)``) across
depths, ragged shapes, and leaf-only trees; the forest/boosted wrappers
must match their ``trees.py`` contracts; the impl gate must honor
``TPTPU_SERVE_TREES``; the program-bank gate must admit ``serve_trees``
with bucket-stable fingerprints; and the fused serving closure must
produce identical scores under either implementation while their plans
carry DIFFERENT fingerprints (the ``:pl`` descriptor salt).
Markers: ``residency`` (+ ``fused`` on the closure test).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import transmogrifai_tpu.types as T
from transmogrifai_tpu.dataset import Dataset
from transmogrifai_tpu.features import from_dataset
from transmogrifai_tpu.local.scoring import score_function
from transmogrifai_tpu.models import serve_pallas as SP
from transmogrifai_tpu.models import trees as TR
from transmogrifai_tpu.models.gbdt import XGBoostClassifier
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.selector import BinaryClassificationModelSelector
from transmogrifai_tpu.types.columns import column_from_values
from transmogrifai_tpu.utils import uid as uid_util
from transmogrifai_tpu.workflow.workflow import Workflow

pytestmark = [pytest.mark.residency]


def _random_stack(rng, t, depth, f, bins):
    w = 1 << depth
    return TR.Tree(
        split_feat=jnp.asarray(
            rng.integers(-1, f, size=(t, depth, w)).astype(np.int32)
        ),
        split_bin=jnp.asarray(
            rng.integers(0, bins, size=(t, depth, w)).astype(np.int32)
        ),
        leaf_value=jnp.asarray(
            rng.normal(size=(t, w)).astype(np.float32)
        ),
    )


def _gather_ref(binned, trees):
    per_tree = jax.vmap(
        lambda sf, sb, lv: TR.predict_tree(binned, TR.Tree(sf, sb, lv))
    )(trees.split_feat, trees.split_bin, trees.leaf_value)
    return np.asarray(per_tree)  # [T, N]


class TestKernelParity:
    @pytest.mark.parametrize("depth", [1, 2, 4, 6])
    def test_bit_identical_across_depths(self, depth):
        rng = np.random.default_rng(depth)
        t, f, n, bins = 5, 7, 133, 16
        trees = _random_stack(rng, t, depth, f, bins)
        binned = jnp.asarray(
            rng.integers(0, bins, size=(n, f)).astype(np.int32)
        )
        got = np.asarray(
            SP.serve_trees_pallas(
                binned, trees.split_feat, trees.split_bin,
                trees.leaf_value, interpret=True,
            )
        )
        np.testing.assert_array_equal(got, _gather_ref(binned, trees))

    def test_deep_trees_take_the_chunked_levels(self):
        # depth 9 at an 8-tree tile: levels 7 and 8 span 1024 and 2048
        # lanes, so the per-level chunk loop runs more than one step and
        # the leaf level is picked without being materialized
        rng = np.random.default_rng(11)
        trees = _random_stack(rng, t=11, depth=9, f=9, bins=32)
        binned = jnp.asarray(
            rng.integers(0, 32, size=(70, 9)).astype(np.int32)
        )
        got = np.asarray(
            SP.serve_trees_pallas(
                binned, trees.split_feat, trees.split_bin,
                trees.leaf_value, tree_tile=8, interpret=True, num_bins=32,
            )
        )
        np.testing.assert_array_equal(got, _gather_ref(binned, trees))

    @pytest.mark.parametrize("num_bins", [None, 1000])
    def test_codes_past_one_byte_stay_exact(self, num_bins):
        # bin codes above 256 are not bf16-exact; the kernel splits them
        # into bytes unless the caller's bin count says one operand holds
        rng = np.random.default_rng(13)
        trees = _random_stack(rng, t=4, depth=5, f=6, bins=1000)
        binned = jnp.asarray(
            rng.integers(0, 1000, size=(50, 6)).astype(np.int32)
        )
        got = np.asarray(
            SP.serve_trees_pallas(
                binned, trees.split_feat, trees.split_bin,
                trees.leaf_value, interpret=True, num_bins=num_bins,
            )
        )
        np.testing.assert_array_equal(got, _gather_ref(binned, trees))

    def test_ragged_shapes_pad_and_slice(self):
        # N and T far from tile multiples: padded rows/trees must be
        # invisible in the sliced result
        rng = np.random.default_rng(9)
        trees = _random_stack(rng, t=3, depth=3, f=5, bins=8)
        binned = jnp.asarray(
            rng.integers(0, 8, size=(17, 5)).astype(np.int32)
        )
        got = np.asarray(
            SP.serve_trees_pallas(
                binned, trees.split_feat, trees.split_bin,
                trees.leaf_value, row_tile=128, tree_tile=8, interpret=True,
            )
        )
        assert got.shape == (3, 17)
        np.testing.assert_array_equal(got, _gather_ref(binned, trees))

    def test_leaf_only_trees(self):
        # split_feat = -1 everywhere: every row lands on node 0's subtree
        # leftmost leaf, matching the gather traversal exactly
        rng = np.random.default_rng(2)
        trees = _random_stack(rng, t=4, depth=2, f=3, bins=4)
        trees = TR.Tree(
            split_feat=jnp.full_like(trees.split_feat, -1),
            split_bin=trees.split_bin,
            leaf_value=trees.leaf_value,
        )
        binned = jnp.asarray(
            rng.integers(0, 4, size=(9, 3)).astype(np.int32)
        )
        got = np.asarray(
            SP.serve_trees_pallas(
                binned, trees.split_feat, trees.split_bin,
                trees.leaf_value, interpret=True,
            )
        )
        np.testing.assert_array_equal(got, _gather_ref(binned, trees))

    def test_forest_and_boosted_wrappers(self):
        rng = np.random.default_rng(5)
        trees = _random_stack(rng, t=6, depth=3, f=4, bins=8)
        binned = jnp.asarray(
            rng.integers(0, 8, size=(40, 4)).astype(np.int32)
        )
        fmean = np.asarray(
            SP.predict_forest_pallas(binned, trees, interpret=True)
        )
        np.testing.assert_array_equal(
            fmean, np.asarray(TR.predict_forest(binned, trees))
        )
        boosted = np.asarray(
            SP.predict_boosted_pallas(
                binned, trees, jnp.float32(0.3), jnp.float32(0.5),
                interpret=True,
            )
        )
        ref = 0.5 + 0.3 * _gather_ref(binned, trees).sum(axis=0)
        np.testing.assert_allclose(boosted, ref, rtol=1e-6, atol=1e-6)


class TestImplGate:
    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("TPTPU_SERVE_TREES", "pallas")
        assert SP.serve_impl() == "pallas"
        monkeypatch.setenv("TPTPU_SERVE_TREES", "gather")
        assert SP.serve_impl() == "gather"

    def test_default_off_tpu_is_gather(self, monkeypatch):
        monkeypatch.delenv("TPTPU_SERVE_TREES", raising=False)
        if jax.default_backend() != "tpu":
            assert SP.serve_impl() == "gather"
            assert SP.serve_interpret() is True


@pytest.mark.analysis
class TestProgramBank:
    def test_serve_trees_admitted_bucket_stable(self):
        from transmogrifai_tpu.analysis import program as P

        errors = []
        specs = P.collect_specs(errors=errors)
        assert not errors
        sv = [s for s in specs if s.name == "serve_trees"]
        assert len(sv) == 1
        spec = sv[0]
        assert spec.scoring is True
        report = P.audit_spec(spec).to_json()
        assert report["errors"] == 0
        prog = report["programs"]["serve_trees"]
        # TPJ005: one fingerprint across every batch bucket
        assert len(prog["fingerprints"]) == 1
        assert prog["bucketAxis"] == "batch"


@pytest.mark.fused
@pytest.mark.serving
class TestFusedClosureParity:
    def _train(self):
        uid_util.reset()
        rng = np.random.default_rng(17)
        n = 192
        x1 = rng.normal(size=n)
        x2 = rng.normal(size=n)
        city = [["a", "b", "c", "d"][i % 4] for i in range(n)]
        label = (
            x1 + 0.5 * x2 + 0.2 * rng.normal(size=n) > 0
        ).astype(float)
        ds = Dataset.of({
            "label": column_from_values(T.RealNN, label),
            "x1": column_from_values(T.Real, x1),
            "x2": column_from_values(T.Real, x2),
            "city": column_from_values(T.PickList, city),
        })
        resp, preds = from_dataset(ds, response="label")
        vec = transmogrify(list(preds))
        sel = BinaryClassificationModelSelector(
            seed=7, num_folds=2,
            models=[
                (XGBoostClassifier(num_round=3, max_depth=3),
                 {"eta": [0.3]}),
            ],
        )
        pred = sel.set_input(resp, vec).get_output()
        model = (
            Workflow().set_result_features(pred).set_input_dataset(ds)
            .train()
        )
        rows = [
            {"x1": float(a), "x2": float(b), "city": c}
            for a, b, c in zip(x1[:48], x2[:48], city[:48])
        ]
        return model, rows

    def test_pallas_vs_gather_identical_distinct_fingerprints(
        self, monkeypatch,
    ):
        monkeypatch.setenv("TPTPU_HOST_PREDICT_MAX", "0")
        model, rows = self._train()
        results = {}
        for impl in ("gather", "pallas"):
            monkeypatch.setenv("TPTPU_SERVE_TREES", impl)
            fn = score_function(model)
            fn.prime_fused()
            md = fn.metadata()["fused"]
            assert md["active"], md
            out = fn.batch(rows)
            probs = np.array(
                [next(iter(r.values()))["probability_1"] for r in out]
            )
            md = fn.metadata()["fused"]
            assert md["fallbacks"] == 0 and md["dispatches"] >= 1
            results[impl] = (probs, md["fingerprint"])
        np.testing.assert_array_equal(
            results["gather"][0], results["pallas"][0]
        )
        # the ":pl" descriptor salt keeps the executables apart in the bank
        assert results["gather"][1] != results["pallas"][1]
