"""Compile-plane suite (transmogrifai_tpu/compiler/ + utils/aot.py):
persistent executable cache (fresh-process hits, corruption fallback,
version invalidation), cross-candidate program dedup + lane buckets,
async warmup, donated dispatch twins, and the compileStats ledger
surfaced in selector summaries and scoring metadata.
"""
import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.compiler import bucketing, dispatch
from transmogrifai_tpu.compiler import stats as cstats
from transmogrifai_tpu.compiler import warmup
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.utils import aot


# ------------------------------------------------------------------- ledger
class TestCompileStatsLedger:
    def test_record_and_delta(self):
        s = cstats.CompileStats()
        s.record_compile("prog_a")
        s.record_compile("prog_a")
        s.bump("cacheHitsDisk")
        s.record_sweep(lanes=6, padded=2)
        snap = s.snapshot()
        assert snap["programsCompiled"] == 2
        assert snap["programsCompiledByName"] == {"prog_a": 2}
        assert snap["dedupHits"] == 5
        assert snap["laneBucketPads"] == 2
        assert snap["bucketedSweeps"] == 1
        assert snap["compileCacheHitRate"] == pytest.approx(1 / 3, abs=1e-3)

    def test_global_delta_isolates_a_phase(self):
        before = cstats.snapshot()
        cstats.stats().record_compile("prog_delta_test")
        d = cstats.delta(before)
        assert d["programsCompiled"] == 1
        assert d["programsCompiledByName"] == {"prog_delta_test": 1}

    def test_warmup_overlap_accumulates(self):
        s = cstats.CompileStats()
        s.record_warmup(3, 0.5)
        s.record_warmup(1, 0.25)
        snap = s.snapshot()
        assert snap["warmupPrograms"] == 4
        assert snap["warmupOverlapSeconds"] == pytest.approx(0.75)


# ------------------------------------------------------------- lane buckets
class TestLaneBuckets:
    def test_bucket_values(self):
        assert bucketing.lane_bucket(1) == 1
        assert bucketing.lane_bucket(2) == 2
        assert bucketing.lane_bucket(3) == 4
        assert bucketing.lane_bucket(24) == 32
        assert bucketing.lane_bucket(64) == 64
        assert bucketing.lane_bucket(65) == 96  # multiples of 32 past 64
        assert bucketing.lane_bucket(97) == 128

    def test_env_disable(self, monkeypatch):
        monkeypatch.setenv("TPTPU_LANE_BUCKETS", "0")
        assert bucketing.lane_bucket(24) == 24

    def test_pad_replicates_lane_zero(self):
        a = np.arange(6, dtype=np.float32).reshape(3, 2)
        b = np.asarray([1.0, 2.0, 3.0], np.float32)
        pa, pb = bucketing.pad_lane_arrays(4, a, b)
        assert pa.shape == (4, 2) and pb.shape == (4,)
        np.testing.assert_array_equal(pa[3], a[0])
        assert pb[3] == b[0]
        # no-op when already at the bucket
        (same,) = bucketing.pad_lane_arrays(3, a)
        assert same is a


# ------------------------------------------------- dedup / padding parity
def _sweep_data(seed=0, n=97, d=11):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)
    return x, y


class TestCandidateDedup:
    def test_value_only_candidates_share_one_program(self):
        """Acceptance: >=4 value-only hyperparameter variants compile at
        most ONE program for the family, and the ledger records the shared
        lanes as dedup hits."""
        x, y = _sweep_data()
        est = LogisticRegression(max_iter=20)
        masks = [np.ones(len(y), np.float32)] * 2
        points = [{"reg_param": r} for r in (0.0, 0.01, 0.1, 0.3)]
        before = cstats.snapshot()
        models = est.fit_arrays_batched_masks(x, y, masks, points)
        d = cstats.delta(before)
        assert d["programsCompiledByName"].get(
            "logistic_binary_batched", 0
        ) <= 1
        assert d["dedupHits"] >= len(masks) * len(points) - 1
        assert models[0][0].weights.shape == (x.shape[1],)

    def test_dedup_is_bit_identical_across_lane_order(self):
        """Two value-only candidates share one executable; swapping their
        lane order reuses it (no new compile) and produces bit-identical
        fits — lanes are independent GEMM columns."""
        x, y = _sweep_data(seed=1)
        est = LogisticRegression(max_iter=20)
        mask = np.ones(len(y), np.float32)
        p1, p2 = {"reg_param": 0.01}, {"reg_param": 0.2}
        a = est.fit_arrays_batched_masks(x, y, [mask], [p1, p2])
        before = cstats.snapshot()
        b = est.fit_arrays_batched_masks(x, y, [mask], [p2, p1])
        d = cstats.delta(before)
        assert d["programsCompiled"] == 0  # shared executable
        assert d["cacheHitsMemory"] >= 1
        np.testing.assert_array_equal(a[0][0].weights, b[0][1].weights)
        np.testing.assert_array_equal(a[0][1].weights, b[0][0].weights)

    def test_padded_bucket_matches_unpadded(self, monkeypatch):
        """3 candidates pad onto the 4-lane bucket; the padded program's
        real lanes match the unpadded (TPTPU_LANE_BUCKETS=0) fits."""
        x, y = _sweep_data(seed=2)
        est = LogisticRegression(max_iter=20)
        mask = np.ones(len(y), np.float32)
        points = [{"reg_param": r} for r in (0.0, 0.05, 0.5)]
        before = cstats.snapshot()
        padded = est.fit_arrays_batched_masks(x, y, [mask], points)
        assert cstats.delta(before)["laneBucketPads"] == 1
        monkeypatch.setenv("TPTPU_LANE_BUCKETS", "0")
        plain = est.fit_arrays_batched_masks(x, y, [mask], points)
        for i in range(len(points)):
            np.testing.assert_allclose(
                padded[0][i].weights, plain[0][i].weights,
                rtol=1e-6, atol=1e-7,
            )
            np.testing.assert_allclose(
                padded[0][i].intercept, plain[0][i].intercept,
                rtol=1e-6, atol=1e-7,
            )

    def test_deduped_matches_sequential_fit(self):
        """The shared-program fit agrees with the undeduped sequential
        fit_arrays path (same solver, K=1 lane) to solver tolerance."""
        x, y = _sweep_data(seed=3)
        est = LogisticRegression(max_iter=40)
        mask = (np.random.default_rng(4).random(len(y)) > 0.2).astype(
            np.float32
        )
        points = [{"reg_param": 0.01}, {"reg_param": 0.1}]
        batched = est.fit_arrays_batched_masks(x, y, [mask], points)
        for i, p in enumerate(points):
            seq = est.with_params(**p).fit_arrays(x, y, mask)
            pb = x @ batched[0][i].weights + batched[0][i].intercept
            ps = x @ seq.weights + seq.intercept
            np.testing.assert_allclose(pb, ps, atol=1e-3)


# ------------------------------------------------------- persistent cache
@pytest.fixture()
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("TPTPU_COMPILE_CACHE", str(tmp_path))
    return tmp_path


def _drain_saves():
    for th in list(aot._THREADS):
        th.join(timeout=30)


class TestPersistentCache:
    def test_blob_roundtrip_and_disk_hit(self, cache_dir):
        fn = jax.jit(lambda a: a * 3.0)
        args = (np.arange(5, dtype=np.float32),)
        before = cstats.snapshot()
        out = aot.aot_call("plane_rt_test", fn, args, {})
        np.testing.assert_allclose(np.asarray(out), args[0] * 3.0)
        _drain_saves()
        key = aot._key("plane_rt_test", args, {})
        path = aot._blob_path("plane_rt_test", key)
        assert os.path.exists(path)
        # evict the in-memory entry: the next call must load from disk
        with aot._LOCK:
            aot._MEM.pop(key, None)
        out2 = aot.aot_call("plane_rt_test", fn, args, {})
        np.testing.assert_allclose(np.asarray(out2), args[0] * 3.0)
        d = cstats.delta(before)
        assert d["programsCompiled"] >= 1
        assert d["cacheHitsDisk"] >= 1

    def test_garbage_blob_recompiles_and_counts(self, cache_dir):
        fn = jax.jit(lambda a: a + 1.0)
        args = (np.arange(4, dtype=np.float32),)
        key = aot._key("plane_corrupt_test", args, {})
        path = aot._blob_path("plane_corrupt_test", key)
        with open(path, "wb") as fh:
            fh.write(b"\x00garbage-not-a-pickle")
        before = cstats.snapshot()
        out = aot.aot_call("plane_corrupt_test", fn, args, {})
        np.testing.assert_allclose(np.asarray(out), args[0] + 1.0)
        d = cstats.delta(before)
        assert d["corruptBlobsDropped"] == 1
        assert d["programsCompiled"] == 1  # recompiled transparently

    def test_valid_pickle_wrong_payload_recompiles(self, cache_dir):
        fn = jax.jit(lambda a: a - 2.0)
        args = (np.arange(4, dtype=np.float32),)
        key = aot._key("plane_payload_test", args, {})
        path = aot._blob_path("plane_payload_test", key)
        with open(path, "wb") as fh:
            fh.write(pickle.dumps({"not": "an executable"}))
        before = cstats.snapshot()
        out = aot.aot_call("plane_payload_test", fn, args, {})
        np.testing.assert_allclose(np.asarray(out), args[0] - 2.0)
        assert cstats.delta(before)["corruptBlobsDropped"] == 1
        assert not os.path.exists(path) or os.path.getsize(path) > 100

    def test_version_mismatch_invalidation(self, cache_dir):
        """Blobs from another source version (different salt) are deleted
        on sight by prewarm and counted as invalidations."""
        d = aot._exec_dir()
        stale = os.path.join(d, f"{'0' * 16}-somename-{'1' * 24}.jaxexec")
        with open(stale, "wb") as fh:
            fh.write(b"stale-version-blob")
        legacy = os.path.join(d, "not-a-blob.jaxexec")  # unknown layout
        with open(legacy, "wb") as fh:
            fh.write(b"legacy")
        before = cstats.snapshot()
        aot.prewarm()
        assert not os.path.exists(stale)
        assert not os.path.exists(legacy)
        assert cstats.delta(before)["versionInvalidations"] == 2

    def test_prewarm_name_filter(self, cache_dir):
        """prewarm(names=...) loads only the named programs and leaves the
        rest banked on disk."""
        fn = jax.jit(lambda a: a * 5.0)
        args = (np.arange(3, dtype=np.float32),)
        aot.aot_call("plane_filter_keep", fn, args, {})
        fn2 = jax.jit(lambda a: a * 7.0)
        aot.aot_call("plane_filter_other", fn2, args, {})
        _drain_saves()
        k1 = aot._key("plane_filter_keep", args, {})
        k2 = aot._key("plane_filter_other", args, {})
        assert os.path.exists(aot._blob_path("plane_filter_keep", k1))
        assert os.path.exists(aot._blob_path("plane_filter_other", k2))
        with aot._LOCK:
            aot._MEM.pop(k1, None)
            aot._MEM.pop(k2, None)
        loaded = aot.prewarm(names={"plane_filter_keep"})
        assert loaded == 1
        with aot._LOCK:
            assert k1 in aot._MEM and k2 not in aot._MEM
        assert os.path.exists(aot._blob_path("plane_filter_other", k2))


# ------------------------------------------------------------------ warmup
class TestWarmup:
    def test_train_programs_maps_selector_families(self):
        from transmogrifai_tpu.selector import (
            BinaryClassificationModelSelector,
        )

        sel = BinaryClassificationModelSelector(seed=0)
        names = warmup.train_programs([sel])
        assert "logistic_binary_batched" in names
        assert "boost_chunk" in names       # XGB default candidate
        assert "forest_scan" in names       # RF default candidate
        assert "predict_boosted" in names   # winner's scoring program

    def test_unknown_family_warms_everything(self):
        class Weird:
            pass

        from transmogrifai_tpu.selector.model_selector import ModelSelector

        sel = ModelSelector.__new__(ModelSelector)
        sel.models = [(Weird(), {})]
        assert warmup.train_programs([sel]) is None

    def test_start_warmup_runs_once_per_scope(self, cache_dir):
        warmup.reset_for_tests()
        th = warmup.start_warmup(names=set(), scope="plane-test")
        assert th is not None
        th.join(timeout=30)
        assert warmup.start_warmup(names=set(), scope="plane-test") is None
        warmup.reset_for_tests()


# ---------------------------------------------------------------- dispatch
class TestDispatch:
    def test_prefetch_roundtrip(self):
        arr = np.arange(12, dtype=np.float32).reshape(3, 4)
        dispatch.prefetch_f32(arr)
        buf = dispatch.device_f32(arr)
        buf2 = dispatch.device_f32(arr)
        assert buf is buf2  # the prefetched buffer, not a fresh upload
        np.testing.assert_array_equal(np.asarray(buf), arr)

    def test_device_f32_fallback_without_prefetch(self):
        arr = np.arange(4, dtype=np.float64)
        out = dispatch.device_f32(arr)
        assert out.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(out), arr)

    def test_donating_twin_matches_plain(self):
        def f(a, b, n):
            return a * n + b

        plain = jax.jit(f, static_argnames=("n",))
        twin = dispatch.donating(
            "plane_donate_test", plain, donate_argnums=(0,),
            static_argnames=("n",),
        )
        a = jnp.arange(4, dtype=jnp.float32)
        b = jnp.ones(4, dtype=jnp.float32)
        expect = np.asarray(plain(jnp.array(a), b, n=2))
        got = np.asarray(twin(a, b, n=2))
        np.testing.assert_array_equal(got, expect)

    def test_donation_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("TPTPU_DONATE", "0")
        plain = jax.jit(lambda a: a)
        assert dispatch.donating("plane_kill_test", plain, (0,)) is plain

    def test_boost_donation_changes_no_results(self, monkeypatch):
        """The donated boost-chunk twin fits bit-identical trees to the
        undonated path (donation is an aliasing property, not math)."""
        from transmogrifai_tpu.models import trees as TR

        rng = np.random.default_rng(0)
        x = rng.normal(size=(80, 5)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.float32)
        thr = TR.quantile_thresholds(x, max_bins=8)
        binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
        mask = jnp.ones((1, len(y)), dtype=jnp.float32)

        def run():
            trees, margin = TR.fit_boosted_batched(
                binned, jnp.asarray(y), mask, num_rounds=3, max_depth=3,
                num_bins=8, eta=0.3, objective="binary:logistic",
            )
            return np.asarray(margin)

        donated = run()
        monkeypatch.setenv("TPTPU_DONATE", "0")
        # TPTPU_AOT=0 too: without it the second run would hit the first
        # run's in-memory program and never execute the undonated twin
        monkeypatch.setenv("TPTPU_AOT", "0")
        monkeypatch.setattr(dispatch, "_DONATED", {})
        plain = run()
        np.testing.assert_array_equal(donated, plain)


# ----------------------------------------------- fresh-process cache reuse
_CHILD_TRAIN = """
import json
import numpy as np
import transmogrifai_tpu.types as T
from transmogrifai_tpu.dataset import Dataset
from transmogrifai_tpu.types.columns import column_from_values
from transmogrifai_tpu.features import from_dataset
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.selector import BinaryClassificationModelSelector
from transmogrifai_tpu.workflow.workflow import Workflow

rng = np.random.default_rng(0)
n = 80
x1 = rng.normal(size=n)
x2 = rng.normal(size=n)
label = (x1 + 0.5 * x2 > 0).astype(float)
ds = Dataset.of({
    "label": column_from_values(T.RealNN, label),
    "x1": column_from_values(T.Real, x1),
    "x2": column_from_values(T.Real, x2),
})
resp, preds = from_dataset(ds, response="label")
vec = transmogrify(list(preds))
sel = BinaryClassificationModelSelector(
    seed=3, num_folds=2,
    models=[(LogisticRegression(), {"reg_param": [0.0, 0.01, 0.1, 0.3]})],
)
pred = sel.set_input(resp, vec).get_output()
model = Workflow().set_result_features(pred).set_input_dataset(ds).train()
print(json.dumps(model.summary_json()["modelSelectorSummary"]["compileStats"]))
"""


class TestFreshProcessCache:
    def test_second_fresh_process_compiles_strictly_fewer(self, tmp_path):
        """Acceptance: two fresh processes train against one shared
        persistent cache dir; the second deserializes banked executables
        (cache hits > 0) and compiles strictly fewer programs."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["TPTPU_COMPILE_CACHE"] = str(tmp_path)
        env.pop("XLA_FLAGS", None)  # single device: keep the sweep batched

        def run():
            p = subprocess.run(
                [sys.executable, "-c", _CHILD_TRAIN],
                capture_output=True, text=True, timeout=420, env=env,
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            )
            assert p.returncode == 0, p.stderr[-2000:]
            return json.loads(p.stdout.strip().splitlines()[-1])

        first = run()
        second = run()
        assert first["programsCompiled"] >= 1
        assert second["programsCompiled"] < first["programsCompiled"]
        hits = (
            second["cacheHitsDisk"] + second["cacheHitsMemory"]
            + second["warmupPrograms"]
        )
        assert hits > 0
        assert second["compileCacheHitRate"] == pytest.approx(1.0)


_CHILD_SCOPED = r"""
import os, sys
import jax, jax.numpy as jnp
from transmogrifai_tpu.compiler import cache as ccache

placed = ccache.enable_persistent_cache()
assert placed == os.environ["JAX_COMPILATION_CACHE_DIR"], placed
assert jax.config.jax_compilation_cache_include_metadata_in_key

def make(scope):
    def step(x):
        if scope:
            with jax.named_scope(scope):
                return jnp.sin(x) * 2.0
        return jnp.sin(x) * 2.0
    return step

x = jnp.arange(8.0)
for scope in (None, "tree/histogram", None):
    jax.jit(make(scope))(x).block_until_ready()
print(len([f for f in os.listdir(placed) if f.endswith("-cache")]))
"""


class TestJaxCacheKey:
    def test_a_metadata_only_edit_gets_its_own_cache_entry(self, tmp_path):
        """JAX's cache must not hand a program the executable compiled
        before a scope name existed: the resolver puts op metadata into
        the key, so the scoped twin of a function is a second entry (with
        JAX's default key the two collapse into one)."""
        env = dict(os.environ)
        env.update(
            JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
            JAX_ENABLE_COMPILATION_CACHE="true",
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
            JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
        )
        p = subprocess.run(
            [sys.executable, "-c", _CHILD_SCOPED],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert p.returncode == 0, p.stderr[-2000:]
        # the unscoped program, its scoped twin, and nothing new for the
        # repeat of the first (JAX's default key would keep one for all)
        entries = int(p.stdout.strip().splitlines()[-1])
        names = [f for f in os.listdir(tmp_path) if f.endswith("-cache")]
        assert sum(n.startswith("jit_step-") for n in names) == 2
        assert entries == len(names)


# --------------------------------------------------------- summary surface
class TestCompileStatsSurface:
    @pytest.fixture(scope="class")
    def trained(self):
        import transmogrifai_tpu.types as T
        from transmogrifai_tpu.dataset import Dataset
        from transmogrifai_tpu.features import from_dataset
        from transmogrifai_tpu.ops import transmogrify
        from transmogrifai_tpu.selector import (
            BinaryClassificationModelSelector,
        )
        from transmogrifai_tpu.types.columns import column_from_values
        from transmogrifai_tpu.utils import uid as uid_util
        from transmogrifai_tpu.workflow.workflow import Workflow

        uid_util.reset()
        rng = np.random.default_rng(5)
        n = 90
        x1 = rng.normal(size=n)
        label = (x1 > 0).astype(float)
        ds = Dataset.of({
            "label": column_from_values(T.RealNN, label),
            "x1": column_from_values(T.Real, x1),
            "x2": column_from_values(T.Real, rng.normal(size=n)),
        })
        resp, preds = from_dataset(ds, response="label")
        vec = transmogrify(list(preds))
        sel = BinaryClassificationModelSelector(
            seed=9, num_folds=2,
            models=[(LogisticRegression(), {"reg_param": [0.0, 0.1]})],
        )
        pred = sel.set_input(resp, vec).get_output()
        model = (
            Workflow().set_result_features(pred).set_input_dataset(ds).train()
        )
        return ds, pred, model

    def test_selector_summary_carries_compile_stats(self, trained):
        _ds, _pred, model = trained
        cs = model.summary_json()["modelSelectorSummary"]["compileStats"]
        assert "programsCompiled" in cs and "dedupHits" in cs
        assert cs["dedupHits"] >= 1  # 2 points x (2 folds + refit) lanes

    def test_summary_pretty_renders_compile_line(self, trained):
        _ds, _pred, model = trained
        assert "Compile plane:" in model.summary_pretty()

    def test_score_metadata_carries_compile_stats(self, trained):
        from transmogrifai_tpu.local.scoring import score_function

        ds, _pred, model = trained
        fn = score_function(model)
        fn(ds.rows()[0])
        md = fn.metadata()
        assert "compileStats" in md
        assert "programsCompiled" in md["compileStats"]
