"""Fused end-to-end scoring graph suite (compiler/fused.py +
local/scoring.py): golden fused-vs-staged parity (batch / columnar /
single-row, tree bit-identity, GLM 1e-6), quarantine compaction through
the fused path, in-graph explain lanes vs the staged sweep, the
``TPTPU_FUSED=0`` opt-out and dispatch-error fallback (TPX008, counted),
runtime-vs-static transfer-census reconciliation ("uploads only at
ingest, downloads only at render"), donated-buffer hygiene (TPX003 over
the fused module), and the standing service riding the fused program.
Marker: ``fused`` (also ``serving`` — it exercises the serving closure).
"""
import os

import numpy as np
import pytest

import transmogrifai_tpu.types as T
from transmogrifai_tpu.compiler import stats as cstats
from transmogrifai_tpu.compiler.fused import Unfuseable, build_fused_plan
from transmogrifai_tpu.dataset import Dataset
from transmogrifai_tpu.features import from_dataset
from transmogrifai_tpu.local.scoring import score_function
from transmogrifai_tpu.models.gbdt import XGBoostClassifier
from transmogrifai_tpu.models.linear import LinearRegression
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.selector import (
    BinaryClassificationModelSelector,
    RegressionModelSelector,
)
from transmogrifai_tpu.telemetry import runlog as rl
from transmogrifai_tpu.types.columns import column_from_values
from transmogrifai_tpu.utils import uid as uid_util
from transmogrifai_tpu.workflow.workflow import Workflow

pytestmark = [pytest.mark.fused, pytest.mark.serving]


def _mixed_ds(n=128, seed=17, binary=True):
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    city = [["bern", "kyiv", "lomé", "oslo"][i % 4] for i in range(n)]
    label = (
        (x1 + 0.5 * x2 > 0).astype(float) if binary else x1 + 0.3 * x2
    )
    ds = Dataset.of({
        "label": column_from_values(T.RealNN, label),
        "age": column_from_values(T.Real, x1),
        "income": column_from_values(T.Real, x2),
        "city": column_from_values(T.PickList, city),
    })
    rows = [
        {"age": float(a), "income": float(b), "city": c}
        for a, b, c in zip(x1, x2, city)
    ]
    # sparse rows are normal serving traffic — keep some in the corpus
    rows[3] = {"age": None, "income": 1.0, "city": None}
    rows[7] = {"income": -0.25}
    return ds, rows


def _train(models, selector_cls=BinaryClassificationModelSelector,
           binary=True, sanity=True, seed=17):
    uid_util.reset()
    ds, rows = _mixed_ds(binary=binary, seed=seed)
    resp, preds = from_dataset(ds, response="label")
    vec = transmogrify(list(preds))
    if sanity:
        vec = resp.sanity_check(vec, remove_bad_features=True)
    kw = {"seed": 7, "models": models}
    if selector_cls is BinaryClassificationModelSelector:
        kw["num_folds"] = 2
    pred = selector_cls(**kw).set_input(resp, vec).get_output()
    model = (
        Workflow().set_result_features(pred).set_input_dataset(ds).train()
    )
    return model, ds, rows


LR = [(LogisticRegression(), {"reg_param": [0.01]})]


@pytest.fixture(scope="module")
def flagship():
    """The synthetic flagship: Real + Real + PickList, SanityChecker
    feature removal, one LR candidate — the plan shape the CI fused smoke
    trains."""
    model, ds, rows = _train(LR)
    return {"model": model, "ds": ds, "rows": rows}


@pytest.fixture()
def fused_cutoff(monkeypatch):
    """Force every batch above the host-predict cutoff so the fused
    program engages at test-sized batches."""
    monkeypatch.setenv("TPTPU_HOST_PREDICT_MAX", "0")


def _staged_twin(fn, call, monkeypatch):
    """Run ``call`` with the fused path opted out (the staged loop) on
    the SAME closure — eligibility re-reads TPTPU_FUSED per batch."""
    monkeypatch.setenv("TPTPU_FUSED", "0")
    try:
        return call()
    finally:
        monkeypatch.delenv("TPTPU_FUSED")


def _prob_matrix(outs, key):
    return np.array(
        [[r[key]["probability_0"], r[key]["probability_1"]] for r in outs]
    )


# ------------------------------------------------------------------ build
class TestBuild:
    def test_flagship_plan_builds(self, flagship):
        fn = score_function(flagship["model"])
        assert fn.prime_fused() is True
        prog = fn.fused_state["program"]
        assert prog is not None
        # Real+Real numeric member (2x [value,null]) + city pivot member
        assert prog.plane_width >= prog.width > 0
        assert prog.up_bytes_per_row > 0 and prog.down_bytes_per_row > 0
        d = prog.describe()
        assert d["fingerprint"] == prog.fingerprint
        assert len(d["members"]) == 2

    def test_env_opt_out(self, flagship, monkeypatch):
        monkeypatch.setenv("TPTPU_FUSED", "0")
        fn = score_function(flagship["model"])
        assert fn.prime_fused() is False
        assert fn.metadata()["fused"]["reason"] == "TPTPU_FUSED=0"
        report = fn.audit().to_json()
        tpx008 = [f for f in report["findings"] if f["code"] == "TPX008"]
        assert tpx008 and tpx008[0]["severity"] == "info"
        # lifting the opt-out must not have erased anything: the program
        # builds and the finding clears
        monkeypatch.delenv("TPTPU_FUSED")
        assert fn.prime_fused() is True
        assert fn.metadata()["fused"]["reason"] is None
        report = fn.audit().to_json()
        assert not [
            f for f in report["findings"] if f["code"] == "TPX008"
        ]

    def test_unfuseable_family_reports_tpx008(self, monkeypatch):
        """A model family without a fused device predict (MLP) degrades
        the whole plan to the staged loop, with the reason audited — and
        a TPTPU_FUSED=0 set/unset cycle must not erase that reason."""
        from transmogrifai_tpu.models.mlp import MLPClassifier

        model, _, rows = _train(
            [(MLPClassifier(hidden_layers=(4,), max_iter=8), {})]
        )
        fn = score_function(model)
        assert fn.prime_fused() is False
        assert "fused device predict" in fn.fused_state["reason"]
        report = fn.audit().to_json()
        assert any(f["code"] == "TPX008" for f in report["findings"])
        # and scoring still works, staged
        out = fn.batch(rows[:4])
        assert len(out) == 4
        # opt-out cycle: the dynamic env reason must not overwrite the
        # build obstruction
        monkeypatch.setenv("TPTPU_FUSED", "0")
        assert fn.metadata()["fused"]["reason"] == "TPTPU_FUSED=0"
        monkeypatch.delenv("TPTPU_FUSED")
        assert "fused device predict" in fn.metadata()["fused"]["reason"]
        report = fn.audit().to_json()
        assert any(f["code"] == "TPX008" for f in report["findings"])

    def test_build_is_static(self, flagship):
        """build_fused_plan executes no stage and uploads nothing."""
        from transmogrifai_tpu.workflow.dag import compute_dag

        model = flagship["model"]
        plan = [
            model.fitted.get(s.uid, s)
            for layer in compute_dag(list(model.result_features))
            for s in layer
        ]
        before = rl.snapshot()
        prog = build_fused_plan(
            plan, list(model.raw_features),
            [f.name for f in model.result_features],
        )
        delta = rl.delta(before)
        assert delta["h2dTransfers"] == 0 and delta["d2hTransfers"] == 0
        assert prog.width > 0

    def test_set_valued_pivot_is_unfuseable(self):
        from transmogrifai_tpu.ops.categorical import OneHotModel
        from transmogrifai_tpu.features import FeatureBuilder

        feat = FeatureBuilder.MultiPickList("tags").as_predictor()
        m = OneHotModel([["a", "b"]], True, True)
        m.set_input(feat)
        with pytest.raises(Unfuseable, match="set-valued"):
            m.fused_member_spec()


# ----------------------------------------------------------------- parity
class TestParity:
    def test_batch_parity_glm(self, flagship, fused_cutoff, monkeypatch):
        fn = score_function(flagship["model"])
        rows = flagship["rows"][:48]
        fused = fn.batch(rows)
        staged = _staged_twin(fn, lambda: fn.batch(rows), monkeypatch)
        assert fn.metadata()["fused"]["dispatches"] >= 1
        key = next(iter(fused[0]))
        np.testing.assert_allclose(
            _prob_matrix(fused, key), _prob_matrix(staged, key), atol=1e-6
        )
        preds = [
            (a[key]["prediction"], b[key]["prediction"])
            for a, b in zip(fused, staged)
        ]
        assert all(a == b for a, b in preds)

    def test_columnar_parity(self, flagship, fused_cutoff, monkeypatch):
        fn = score_function(flagship["model"])
        ds = flagship["ds"]
        fused = fn.columns(ds)
        staged = _staged_twin(fn, lambda: fn.columns(ds), monkeypatch)
        key = next(iter(fused))
        np.testing.assert_allclose(
            np.asarray(fused[key].probability),
            np.asarray(staged[key].probability),
            atol=1e-6,
        )

    def test_single_row_parity(self, flagship, fused_cutoff, monkeypatch):
        """b=1 buckets to the size-1 program — the fused graph covers the
        single-row path too once the cutoff is below it."""
        fn = score_function(flagship["model"])
        row = flagship["rows"][0]
        fused = fn(row)
        staged = _staged_twin(fn, lambda: fn(row), monkeypatch)
        key = next(iter(fused))
        assert fused[key]["prediction"] == staged[key]["prediction"]
        assert abs(
            fused[key]["probability_1"] - staged[key]["probability_1"]
        ) < 1e-6

    def test_tree_predictions_bit_identical(self, fused_cutoff,
                                            monkeypatch):
        model, _, rows = _train(
            [(XGBoostClassifier(num_round=5, max_depth=3), {})]
        )
        fn = score_function(model)
        fused = fn.batch(rows[:32])
        staged = _staged_twin(fn, lambda: fn.batch(rows[:32]), monkeypatch)
        assert fn.metadata()["fused"]["dispatches"] == 1
        key = next(iter(fused[0]))
        for a, b in zip(fused, staged):
            assert a[key] == b[key]  # bit-identical, not allclose

    def test_regression_parity(self, fused_cutoff, monkeypatch):
        model, _, rows = _train(
            [(LinearRegression(), {"reg_param": [0.01]})],
            selector_cls=RegressionModelSelector, binary=False,
        )
        fn = score_function(model)
        fused = fn.batch(rows[:32])
        staged = _staged_twin(fn, lambda: fn.batch(rows[:32]), monkeypatch)
        key = next(iter(fused[0]))
        for a, b in zip(fused, staged):
            assert abs(a[key]["prediction"] - b[key]["prediction"]) < 1e-5

    def test_quarantined_rows_compact_through_fused(self, flagship,
                                                    fused_cutoff):
        """Malformed rows quarantine exactly as on the staged path: the
        fused dispatch sees only the compacted survivors."""
        fn = score_function(flagship["model"])
        rows = [dict(r) for r in flagship["rows"][:12]]
        rows[2] = {"age": "zzz", "income": 0.1, "city": "bern"}
        rows[9] = {"age": "???", "income": 0.2, "city": "kyiv"}
        out = fn.batch(rows)
        assert len(out) == 12
        assert fn.quarantine.stats()["quarantinedRows"] >= 2
        assert fn.metadata()["fused"]["dispatches"] >= 1
        key = next(iter(out[0]))
        # quarantined rows answer with the default prediction
        assert out[2][key] == out[9][key]

    def test_poisoned_rows_run_staged_under_fault_plan(self, flagship,
                                                       fused_cutoff,
                                                       fault_plan):
        """An installed FaultPlan targets per-stage hooks the fused graph
        bypasses — such batches run the staged loop (NOT counted as a
        fallback: chaos is test machinery, not a degradation)."""
        fault_plan.fail_stage_transform(
            target="modelSelector", times=None, rows=(1,)
        )
        fn = score_function(flagship["model"])
        before = cstats.snapshot()
        out = fn.batch(flagship["rows"][:8])
        delta = cstats.delta(before)
        assert delta["fusedDispatches"] == 0
        assert delta["fusedFallbacks"] == 0
        assert len(out) == 8
        assert fn.quarantine.stats()["quarantinedRows"] >= 1


# ---------------------------------------------------------------- explain
class TestExplain:
    def test_explain_rides_the_single_dispatch(self, flagship,
                                               fused_cutoff, monkeypatch):
        fn = score_function(flagship["model"])
        rows = flagship["rows"][:16]
        before = cstats.snapshot()
        fused = fn.batch(rows, explain=3)
        delta = cstats.delta(before)
        assert delta["fusedDispatches"] == 1
        assert delta["fusedExplainLanes"] > 0
        staged = _staged_twin(
            fn, lambda: fn.batch(rows, explain=3), monkeypatch
        )
        for a, b in zip(fused, staged):
            fa, sa = a["attributions"], b["attributions"]
            assert set(fa) == set(sa)
            for g in fa:
                assert abs(fa[g] - sa[g]) < 1e-5
        # quarantined rows still answer with None
        bad = fn.batch(
            [{"age": "zzz", "income": 0.1, "city": "bern"}], explain=2
        )
        assert bad[0]["attributions"] is None

    def test_explain_budget_skip_keeps_scores(self, flagship,
                                              fused_cutoff, monkeypatch):
        """A sweep too large for one dispatch degrades attributions (typed
        + counted), never scores."""
        from transmogrifai_tpu.insights import ledger as attr_ledger

        monkeypatch.setenv("TPTPU_EXPLAIN_LANE_BUDGET", "1")
        fn = score_function(flagship["model"])
        before = attr_ledger.snapshot()
        out = fn.batch(flagship["rows"][:8], explain=2)
        delta = attr_ledger.delta(before)
        assert delta["explainBudgetSkips"] == 1
        key = next(iter(out[0]))
        assert "prediction" in out[0][key]
        assert all(r["attributions"] is None for r in out)


# ----------------------------------------------------------------- census
class TestCensus:
    def test_uploads_at_ingest_downloads_at_render(self, flagship,
                                                   fused_cutoff):
        fn = score_function(flagship["model"])
        rows = flagship["rows"][:32]
        fn.batch(rows)  # bring-up: program build + one-time param upload
        before = rl.snapshot()
        for _ in range(3):
            fn.batch(rows)
        runtime = rl.delta(before)
        # steady state: exactly ONE h2d (ingest) and ONE d2h (render) per
        # batch — the fused acceptance criterion
        assert runtime["h2dTransfers"] == 3
        assert runtime["d2hTransfers"] == 3
        static = fn.audit().to_json()["transferCensus"]
        assert static["fusedProgram"] is True
        assert static["hostToDeviceTransfers"] == 1
        assert static["deviceToHostTransfers"] == 1
        rec = rl.reconcile_transfer_census(
            runtime, static, rows=96, batches=3, check_uploads=True
        )
        assert rec["consistent"], rec
        assert runtime["d2hBytes"] == round(
            static["downBytesPerRow"] * 96
        )

    def test_audit_is_tpx002_clean_and_tpx003_clean(self, flagship,
                                                    fused_cutoff):
        fn = score_function(flagship["model"])
        fn.batch(flagship["rows"][:32])
        report = fn.audit().to_json()
        codes = {f["code"] for f in report["findings"]}
        assert "TPX002" not in codes  # no device->host->device bounce
        assert "TPX003" not in codes  # no donated-buffer reuse
        assert "TPX008" not in codes  # no degradation
        assert report["fusedProgram"]["coveredStages"]

    def test_donation_misuse_scan_covers_fused_module(self):
        """The TPX003 AST guard actually runs over compiler/fused.py and
        finds nothing — the donated ingest is never read after dispatch."""
        from transmogrifai_tpu.analysis.plan_audit import (
            donation_misuse_module,
        )

        report = donation_misuse_module("transmogrifai_tpu.compiler.fused")
        assert report.to_json()["findings"] == []


# --------------------------------------------------------------- fallback
class TestFallback:
    def test_dispatch_error_degrades_to_staged(self, flagship,
                                               fused_cutoff, monkeypatch):
        fn = score_function(flagship["model"])
        assert fn.prime_fused()
        prog = fn.fused_state["program"]

        def boom(*a, **kw):
            raise RuntimeError("chip fell off")

        monkeypatch.setattr(prog, "run", boom)
        before = cstats.snapshot()
        out = fn.batch(flagship["rows"][:16])
        delta = cstats.delta(before)
        assert len(out) == 16
        key = next(iter(out[0]))
        assert "prediction" in out[0][key]
        assert delta["fusedFallbacks"] == 1
        md = fn.metadata()["fused"]
        assert md["fallbacks"] == 1
        assert md["lastFallback"] == "dispatch_error"
        report = fn.audit().to_json()
        tpx008 = [f for f in report["findings"] if f["code"] == "TPX008"]
        assert tpx008 and tpx008[0]["severity"] == "warning"
        # a program failing EVERY batch disables itself (no per-batch
        # failed-retrace tax forever), with the reason audited
        fn.batch(flagship["rows"][:16])
        fn.batch(flagship["rows"][:16])
        md = fn.metadata()["fused"]
        assert md["active"] is False
        assert "disabled after 3 consecutive" in md["reason"]
        before = cstats.snapshot()
        fn.batch(flagship["rows"][:16])  # no 4th attempt
        assert cstats.delta(before)["fusedFallbacks"] == 0

    def test_compiler_refusal_is_reported_once_and_disables(
        self, flagship, fused_cutoff, monkeypatch, caplog,
    ):
        """A program the compiler refuses fails identically on every
        batch: the first failed dispatch probes the lowering, logs ONE
        error naming the program, and disables it — no three-strike
        wait, no per-batch failed retrace."""
        import logging

        # no bank: an executable an earlier test banked under this
        # fingerprint would run instead of tracing the refusing core
        monkeypatch.setenv("TPTPU_AOT", "0")
        fn = score_function(flagship["model"])
        assert fn.prime_fused()
        prog = fn.fused_state["program"]

        def refuse(plane, p):
            raise NotImplementedError("unsupported shape cast")

        monkeypatch.setattr(prog._spec, "core", refuse)
        with caplog.at_level(logging.WARNING):
            out = fn.batch(flagship["rows"][:16])
        assert len(out) == 16 and "prediction" in out[0][next(iter(out[0]))]
        md = fn.metadata()["fused"]
        assert md["active"] is False
        assert md["fallbacks"] == 1
        assert md["reason"].startswith("does not compile on")
        errors = [
            r for r in caplog.records
            if r.levelno == logging.ERROR and "does not compile" in r.message
        ]
        assert len(errors) == 1
        assert prog.fingerprint in errors[0].message
        before = cstats.snapshot()
        fn.batch(flagship["rows"][:16])  # stays staged, nothing retried
        assert cstats.delta(before)["fusedFallbacks"] == 0

    def test_fallback_twin_parity(self, flagship, fused_cutoff,
                                  monkeypatch):
        """The staged continuation after a fused failure produces the
        same scores the fused dispatch would have."""
        fn = score_function(flagship["model"])
        rows = flagship["rows"][:16]
        good = fn.batch(rows)
        prog = fn.fused_state["program"]
        monkeypatch.setattr(
            prog, "run",
            lambda *a, **kw: (_ for _ in ()).throw(RuntimeError("x")),
        )
        degraded = fn.batch(rows)
        key = next(iter(good[0]))
        np.testing.assert_allclose(
            _prob_matrix(good, key), _prob_matrix(degraded, key),
            atol=1e-6,
        )


# ---------------------------------------------------------------- service
class TestService:
    def test_service_micro_batches_ride_fused(self, flagship,
                                              fused_cutoff):
        from transmogrifai_tpu.serving import ScoringService, ServiceConfig

        fn = score_function(flagship["model"])
        svc = ScoringService(
            fn, config=ServiceConfig(max_batch_rows=16, workers=1)
        )
        svc.start()
        try:
            assert fn.fused_state["program"] is not None  # primed at start
            before = cstats.snapshot()
            futs = [svc.submit(r) for r in flagship["rows"][:8]]
            scored = [f.result(timeout=30.0)[0] for f in futs]
            explained = svc.submit(
                flagship["rows"][0], explain=2
            ).result(timeout=30.0)[0]
        finally:
            svc.stop()
        delta = cstats.delta(before)
        assert delta["fusedDispatches"] >= 1
        assert delta["fusedFallbacks"] == 0
        key = next(iter(scored[0]))
        assert all("prediction" in r[key] for r in scored)
        assert explained["attributions"] is not None


# ----------------------------------------------------------- native twin
class TestNativeOff:
    def test_parity_survives_native_disable_env(self, flagship,
                                                fused_cutoff, monkeypatch):
        """TPTPU_DISABLE_NATIVE=1 routes the pivot interning through the
        dict fallback — the fused codes (and scores) must not change.
        (CI also re-runs this whole module under that env.)"""
        fn = score_function(flagship["model"])
        rows = flagship["rows"][:16]
        with_native = fn.batch(rows)
        monkeypatch.setenv("TPTPU_DISABLE_NATIVE", "1")
        without = fn.batch(rows)
        key = next(iter(with_native[0]))
        np.testing.assert_allclose(
            _prob_matrix(with_native, key), _prob_matrix(without, key),
            atol=0.0,
        )
