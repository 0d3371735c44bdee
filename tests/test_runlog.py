"""Training-run flight recorder suite (telemetry/runlog.py): report
round-trip through save/load and the model manifest, runtime-vs-static
transfer-census reconciliation, ETA monotone convergence on an injectable
clock, the cross-run regression sentinel (seeded slow_stage chaos positive
+ identical-twin negative), the CPU no-device-memory fallback, the
summary-degradation satellite, the ``runs`` CLI, and the <2% train-overhead
guard (the PR-6/PR-7 absolute-cost pattern). Marker: ``runlog``.
"""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

import transmogrifai_tpu.types as T
from transmogrifai_tpu.dataset import Dataset
from transmogrifai_tpu.features import from_dataset
from transmogrifai_tpu.local.scoring import score_function
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.resilience import faults
from transmogrifai_tpu.selector import BinaryClassificationModelSelector
from transmogrifai_tpu.telemetry import events as tevents
from transmogrifai_tpu.telemetry import runlog as rl
from transmogrifai_tpu.telemetry import spans as tspans
from transmogrifai_tpu.types.columns import column_from_values
from transmogrifai_tpu.utils import uid as uid_util
from transmogrifai_tpu.workflow.workflow import Workflow

pytestmark = pytest.mark.runlog

LR_MODELS = [(LogisticRegression(), {"reg_param": [0.01]})]


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _flagship_ds(n=96, seed=0):
    rng = np.random.default_rng(seed)
    return Dataset.of({
        "label": column_from_values(
            T.RealNN, rng.integers(0, 2, n).tolist()
        ),
        "age": column_from_values(T.Real, rng.normal(40.0, 9.0, n).tolist()),
        "city": column_from_values(
            T.PickList, [["a", "b", "c"][i % 3] for i in range(n)]
        ),
    })


def _flagship_workflow(seed=0):
    ds = _flagship_ds(seed=seed)
    label, predictors = from_dataset(ds, response="label")
    checked = label.sanity_check(
        transmogrify(predictors), remove_bad_features=True
    )
    pred = (
        BinaryClassificationModelSelector(seed=7, models=LR_MODELS)
        .set_input(label, checked)
        .get_output()
    )
    # single-device like the flagship bench: fits dispatch through the
    # compiler/dispatch seam (mesh runs shard uploads via GSPMD instead,
    # which the runtime census deliberately does not claim)
    wf = (
        Workflow().set_result_features(pred).set_input_dataset(ds)
        .set_parallelism(None)
    )
    return wf, ds


def _train(run_dir=None, progress=None, seed=0):
    uid_util.reset()
    wf, ds = _flagship_workflow(seed=seed)
    t0 = time.perf_counter()
    model = wf.train(run_dir=run_dir, progress=progress)
    return model, ds, time.perf_counter() - t0


@pytest.fixture(scope="module")
def flagship(tmp_path_factory):
    """One recorded synthetic-flagship train with run-dir persistence and
    a progress stream captured."""
    run_dir = str(tmp_path_factory.mktemp("runs"))
    events = []
    model, ds, wall = _train(run_dir=run_dir, progress=events.append)
    return {
        "model": model, "ds": ds, "wall": wall,
        "run_dir": run_dir, "progress": events,
    }


def _load_bench():
    path = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    spec = importlib.util.spec_from_file_location("bench_mod_runlog", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------- the report
def test_flagship_report_shape_and_validation(flagship):
    report = flagship["model"].run_report
    assert report is not None
    assert rl.validate_run_report(report) == []
    run = report["run"]
    # per-phase seconds: ingest + fit at minimum, every cell timed
    assert {"ingest", "fit"} <= set(run["phases"])
    assert all(c["seconds"] >= 0.0 for c in run["phases"].values())
    assert run["phases"]["fit"]["seconds"] > 0.0
    # per-layer timings with the DAG's layer count, rows carried
    assert len(run["layers"]) >= 3
    assert all(l["rows"] for l in run["layers"])
    # candidate sweep timed (the selector's internal validator pulses)
    assert run["candidates"] and run["candidates"][0]["model"]
    assert run["candidates"][0]["seconds"] >= 0.0
    # the runtime transfer census saw the GLM fit uploads
    census = run["transferCensus"]
    assert census["hostToDevice"]["count"] > 0
    assert census["hostToDevice"]["bytes"] > 0
    # sweep ledger delta rides the report
    assert "dedupHits" in run["sweeps"]
    # quality captured from the holdout evaluation
    assert "AuROC" in (run["quality"] or {})
    # headline metrics flattened for regression tooling
    m = report["metrics"]
    assert m["wall_s"] > 0 and m["layers"] == len(run["layers"])
    assert m["h2d_transfers"] == census["hostToDevice"]["count"]


def test_report_roundtrip_file_and_manifest(flagship, tmp_path):
    report = flagship["model"].run_report
    # RUN_*.json round-trip: the train already wrote one into run_dir
    paths = rl.list_run_reports(flagship["run_dir"])
    assert len(paths) == 1 and os.path.basename(paths[0]).startswith("RUN_")
    loaded = rl.load_run_report(paths[0])
    assert loaded["run"]["runId"] == report["run"]["runId"]
    assert loaded["run"]["file"] == os.path.basename(paths[0])
    # model-manifest round-trip
    mdir = str(tmp_path / "model")
    flagship["model"].save(mdir)
    from transmogrifai_tpu.workflow.workflow import WorkflowModel

    reloaded = WorkflowModel.load(mdir)
    assert reloaded.run_report is not None
    assert reloaded.run_report["run"]["runId"] == report["run"]["runId"]
    assert rl.validate_run_report(reloaded.run_report) == []
    # summary surfaces
    assert flagship["model"].summary_json()["run"]["run"]["runId"] == (
        report["run"]["runId"]
    )
    pretty = flagship["model"].summary_pretty()
    assert "Run report:" in pretty
    assert report["run"]["file"] in pretty


def test_run_report_rides_unified_bench_schema(flagship):
    bench = _load_bench()
    assert bench.validate_bench_report(flagship["model"].run_report) == []


def test_validate_run_report_rejects_malformed(flagship):
    good = flagship["model"].run_report
    assert rl.validate_run_report([]) != []
    assert rl.validate_run_report({"schema_version": 1}) != []
    no_run = dict(good)
    no_run.pop("run")
    assert any("run" in p for p in rl.validate_run_report(no_run))
    bad_census = json.loads(json.dumps(good))
    bad_census["run"]["transferCensus"]["hostToDevice"] = {"count": "x"}
    assert any(
        "transferCensus" in p for p in rl.validate_run_report(bad_census)
    )


def test_run_source_in_prometheus_exposition():
    from transmogrifai_tpu.telemetry import render_prometheus

    before = rl.snapshot()
    rl.record_upload(4096, 0.001)
    rl.record_download(768, 0.0005)
    d = rl.delta(before)
    assert d["h2dTransfers"] == 1 and d["h2dBytes"] == 4096
    assert d["d2hTransfers"] == 1 and d["d2hBytes"] == 768
    text = render_prometheus()
    assert "tptpu_run_h2d_transfers" in text
    assert "tptpu_run_d2h_bytes" in text
    assert "tptpu_run_summary_degraded" in text


# ------------------------------------------------------------ progress + ETA
def test_progress_stream_carries_layers_and_phases(flagship):
    events = flagship["progress"]
    kinds = {e["event"] for e in events}
    assert {"phase", "layer"} <= kinds
    layer_events = [e for e in events if e["event"] == "layer"]
    assert len(layer_events) == len(flagship["model"].run_report["run"]["layers"])
    # after the first layer the EWMA is live and the ETA counts DOWN to 0
    assert all(
        e["secondsPerLayer"] is not None and e["etaSeconds"] is not None
        for e in layer_events
    )
    assert layer_events[-1]["etaSeconds"] == 0.0


def test_broken_progress_callback_never_breaks_train():
    def bomb(event):
        raise RuntimeError("user callback bug")

    model, _, _ = _train(progress=bomb)
    assert model.run_report is not None  # train survived and recorded


def test_eta_monotone_convergence_on_injectable_clock():
    """Drive layer pulses on a fake clock: a noisy first layer, then a
    constant per-layer cost — the EWMA's error against the true cost must
    shrink monotonically and the ETA must converge to per * remaining."""
    clock = FakeClock()
    rec = rl.RunRecorder(clock=clock)
    rec.start()
    true_cost = 2.0
    total = 12
    errors = []
    for li in range(total):
        rec.on_layer_start(li, total=total)
        clock.advance(10.0 if li == 0 else true_cost)  # li 0: cold outlier
        rec.on_layer_end(li, total=total)
        if li >= 1:
            errors.append(abs(rec.eta.seconds_per_unit - true_cost))
    assert all(b <= a + 1e-12 for a, b in zip(errors, errors[1:]))
    assert errors[-1] < 0.05  # converged onto the true per-layer cost
    assert rec.eta.eta(3) == pytest.approx(
        3 * rec.eta.seconds_per_unit
    )
    assert rec.eta.eta(0) == 0.0


def test_eta_estimator_validates_alpha():
    with pytest.raises(ValueError):
        rl.EtaEstimator(alpha=0.0)
    e = rl.EtaEstimator()
    assert e.eta(5) is None  # no updates yet


# --------------------------------------------------- transfer reconciliation
def test_runtime_vs_static_census_reconciles(flagship, monkeypatch):
    """Score a device-dispatched batch (host-predict cutoff forced down)
    and square the runtime census delta against the static TPX census
    from the plan auditor: same d2h crossing count, same bytes/row."""
    monkeypatch.setenv("TPTPU_HOST_PREDICT_MAX", "4")
    fn = score_function(flagship["model"])
    names = [f.name for f in flagship["model"].raw_features]
    rows = [
        {n: v for n, v in zip(names, vals)}
        for vals in zip(
            *(flagship["ds"][n].to_list() for n in names)
        )
    ][:32]
    fn.batch(rows)  # warm: the audit learns widths from batch 1
    before = rl.snapshot()
    fn.batch(rows)
    runtime = rl.delta(before)
    static = fn.audit().to_json()["transferCensus"]
    assert static["deviceToHostTransfers"] >= 1
    rec = rl.reconcile_transfer_census(
        runtime, static, rows=len(rows), batches=1
    )
    assert rec["consistent"], rec
    assert runtime["d2hTransfers"] == static["deviceToHostTransfers"]
    assert runtime["d2hBytes"] == static["downBytesPerRow"] * len(rows)
    # the predictor-feed prefetch crossed host->device this batch too
    assert runtime["h2dTransfers"] >= 1 and runtime["h2dBytes"] > 0


def test_host_predict_batches_record_no_downloads(flagship):
    """Below the cutoff the predictor runs host-side — the runtime census
    must NOT invent device crossings for an all-host batch."""
    fn = score_function(flagship["model"])  # default cutoff 16384
    names = [f.name for f in flagship["model"].raw_features]
    rows = [
        {n: v for n, v in zip(names, vals)}
        for vals in zip(
            *(flagship["ds"][n].to_list() for n in names)
        )
    ][:16]
    fn.batch(rows)
    before = rl.snapshot()
    fn.batch(rows)
    assert rl.delta(before)["d2hTransfers"] == 0


# ------------------------------------------------------------- device memory
def test_cpu_device_memory_graceful_zero(flagship):
    """On CPU ``memory_stats()`` is None: the poll (and the report's
    high-water gauge) must report an explicit zero, while the live-array
    census still works."""
    poll = rl.poll_device_memory()
    assert poll["backend"] == "cpu"
    assert poll["deviceBytesInUse"] == 0 and poll["devicePeakBytes"] == 0
    assert poll["liveArrayBytes"] >= 0
    mem = flagship["model"].run_report["run"]["deviceMemory"]
    assert mem["highWaterBytes"] == 0
    assert mem["polls"] > 0
    assert mem["backend"] == "cpu"


# -------------------------------------------------------- regression sentinel
@pytest.fixture(scope="module")
def twin_runs(tmp_path_factory):
    """Two clean twins on the INJECTABLE telemetry clock (the repo's
    no-real-sleeps convention): with a frozen clock both twins record
    identical (zero) timings, so the negative verdict is deterministic —
    only counter/census/quality differences could ever flag, and clean
    twins have none. A prior warmup run keeps compile-cache noise out."""
    d1 = str(tmp_path_factory.mktemp("twin_a"))
    d2 = str(tmp_path_factory.mktemp("twin_b"))
    _train()  # warmup: the process's program acquisition happens here
    tspans.set_clock(FakeClock())
    try:
        a, _, _ = _train(run_dir=d1)
        b, _, _ = _train(run_dir=d2)
    finally:
        tspans.set_clock(None)
    return a.run_report, b.run_report


def test_twin_clean_runs_diff_clean(twin_runs):
    base, cur = twin_runs
    report = rl.diff_runs(base, cur)
    assert len(report.findings) == 0, report.pretty()
    assert report.data["runDiff"]["regressions"] == 0
    # the degenerate twin — a report against itself — is clean too
    assert len(rl.diff_runs(base, base).findings) == 0


def test_slow_stage_chaos_run_flags_regression(twin_runs):
    """Seeded slow_stage chaos on the same frozen clock: every train
    transform carries simulated extra seconds (no real sleeps), so the
    chaos run's fit phase is EXACTLY the injected seconds while the clean
    baseline's is zero — diff_runs must report TPR001 deterministically."""
    base, _ = twin_runs
    tevents.reset_for_tests()
    counters_before = rl.snapshot()
    plan = faults.FaultPlan(seed=13).slow_stage(delay=2.0)
    tspans.set_clock(FakeClock())
    try:
        with faults.installed(plan):
            slow_model, _, _ = _train()
    finally:
        tspans.set_clock(None)
    slow = slow_model.run_report
    assert any(kind == "slow" for kind, _ in plan.fired)  # chaos fired
    report = rl.diff_runs(base, slow)
    codes = {f.code for f in report.findings}
    assert "TPR001" in codes, report.pretty()
    fit_findings = [f for f in report.findings if f.subject == "fit"]
    assert fit_findings and fit_findings[0].severity.value == "warning"
    # the verdict is observable: run_regression event + ledger counter
    recs = [r for r in tevents.recent() if r["kind"] == "run_regression"]
    assert recs and "TPR001" in recs[-1]["codes"]
    assert (
        rl.delta(counters_before)["runRegressions"] >= len(report.findings)
    )
    # layer timings carry the simulated seconds too
    assert any(l["seconds"] >= 2.0 for l in slow["run"]["layers"])


def test_regression_sentinel_wraps_diff(twin_runs, tmp_path):
    base, cur = twin_runs
    path = str(tmp_path / "RUN_baseline.json")
    with open(path, "w") as fh:
        json.dump(base, fh)
    sentinel = rl.RegressionSentinel(path)
    assert len(sentinel.check(cur)) == 0
    # a doctored 10x-slower fit phase trips the same sentinel
    doctored = json.loads(json.dumps(cur))
    doctored["run"]["phases"]["fit"]["seconds"] = (
        base["run"]["phases"]["fit"]["seconds"] * 10 + 5.0
    )
    assert any(
        f.code == "TPR001" for f in sentinel.check(doctored).findings
    )


def _mini_run(phases=None, compiled=0, census_bytes=0, quality=None):
    return {
        "schema_version": 1,
        "metric": "train_run_wallclock",
        "value": 1.0,
        "unit": "s",
        "metrics": {},
        "run": {
            "schemaVersion": 1,
            "runId": "r",
            "wallSeconds": 1.0,
            "phases": phases or {},
            "layers": [],
            "compileStats": {"programsCompiled": compiled},
            "featurizeStats": {},
            "transferCensus": {
                "hostToDevice": {
                    "count": 1, "bytes": census_bytes, "seconds": 0.0,
                },
                "deviceToHost": {"count": 0, "bytes": 0, "seconds": 0.0},
            },
            "deviceMemory": {},
            "quality": quality,
        },
    }


class TestDiffCodes:
    def test_tpr002_compile_blowup(self):
        report = rl.diff_runs(
            _mini_run(compiled=2), _mini_run(compiled=12),
            emit_events=False,
        )
        assert {f.code for f in report.findings} == {"TPR002"}

    def test_tpr003_transfer_growth(self):
        report = rl.diff_runs(
            _mini_run(census_bytes=1 << 20),
            _mini_run(census_bytes=200 << 20),
            emit_events=False,
        )
        assert {f.code for f in report.findings} == {"TPR003"}

    def test_tpr003_needs_absolute_floor(self):
        # 10 bytes -> 100 bytes is a 10x ratio but far below the floor
        report = rl.diff_runs(
            _mini_run(census_bytes=10), _mini_run(census_bytes=100),
            emit_events=False,
        )
        assert len(report.findings) == 0

    def test_tpr004_quality_drop_and_direction(self):
        base = _mini_run(quality={"AuROC": 0.9, "RMSE": 1.0})
        worse = _mini_run(quality={"AuROC": 0.8, "RMSE": 1.5})
        codes = [
            f for f in rl.diff_runs(base, worse, emit_events=False).findings
        ]
        assert {f.code for f in codes} == {"TPR004"}
        assert {f.subject for f in codes} == {"AuROC", "RMSE"}
        # improvements in both directions stay silent
        better = _mini_run(quality={"AuROC": 0.95, "RMSE": 0.5})
        assert not rl.diff_runs(base, better, emit_events=False).findings

    def test_tpr001_respects_min_seconds_floor(self):
        base = _mini_run(phases={"ingest": {"seconds": 0.01}})
        cur = _mini_run(phases={"ingest": {"seconds": 0.05}})
        assert not rl.diff_runs(base, cur, emit_events=False).findings


# ------------------------------------------------------- summary degradation
def test_summary_degraded_is_counted_and_evented(flagship, monkeypatch):
    import importlib

    mi = importlib.import_module(
        "transmogrifai_tpu.insights.model_insights"
    )

    def bomb(model):
        raise RuntimeError("insights exploded")

    monkeypatch.setattr(mi, "model_insights", bomb)
    tevents.reset_for_tests()
    before = rl.snapshot()
    pretty = flagship["model"].summary_pretty()
    assert "Trained on" in pretty  # summary still renders
    assert rl.delta(before)["summaryDegraded"] == 1
    recs = [r for r in tevents.recent() if r["kind"] == "summary_degraded"]
    assert recs and recs[-1]["section"] == "insights"
    assert "insights exploded" in recs[-1]["error"]


# ------------------------------------------------------------------ runs CLI
class TestRunsCli:
    def _run_cli(self, argv):
        from transmogrifai_tpu.cli import main

        with pytest.raises(SystemExit) as ei:
            main(argv)
        return ei.value.code

    def test_list_and_last(self, flagship, capsys):
        assert self._run_cli(["runs", "--dir", flagship["run_dir"]]) == 0
        out = capsys.readouterr().out
        assert flagship["model"].run_report["run"]["runId"] in out
        assert self._run_cli(
            ["runs", "--dir", flagship["run_dir"], "--last"]
        ) == 0
        out = capsys.readouterr().out
        assert "h2d" in out and "device high-water" in out

    def test_diff_clean_and_regressed(self, flagship, tmp_path, capsys):
        d = str(tmp_path)
        report = flagship["model"].run_report
        rl.save_run_report(json.loads(json.dumps(report)), d)
        slow = json.loads(json.dumps(report))
        slow["run"]["runId"] = "slowtwin"
        slow["run"]["phases"]["fit"]["seconds"] = (
            report["run"]["phases"]["fit"]["seconds"] * 10 + 5.0
        )
        rl.save_run_report(slow, d)
        assert self._run_cli(["runs", "--dir", d, "--diff", "prev", "prev"]) == 0
        assert "clean" in capsys.readouterr().out
        assert self._run_cli(["runs", "--dir", d, "--diff", "prev", "last"]) == 1
        assert "TPR001" in capsys.readouterr().out

    def test_empty_dir(self, tmp_path, capsys):
        assert self._run_cli(["runs", "--dir", str(tmp_path)]) == 0
        assert "no RUN_" in capsys.readouterr().out


def test_bench_validate_reports_covers_run_artifacts(flagship, tmp_path):
    bench = _load_bench()
    root = str(tmp_path)
    rl.save_run_report(
        json.loads(json.dumps(flagship["model"].run_report)), root
    )
    assert bench.validate_reports(root) == 0
    # a torn artifact fails the gate
    with open(os.path.join(root, "RUN_torn.json"), "w") as fh:
        fh.write('{"schema_version": 1}')
    assert bench.validate_reports(root) == 1


# ------------------------------------------------------------ overhead guard
def test_recorder_overhead_under_two_percent(flagship):
    """Acceptance guard, the PR-6/PR-7 absolute-cost pattern: price one
    layer pulse, one phase bracket, and one memory poll with tight
    micro-benchmarks, multiply by what the flagship train actually
    recorded, and require the attributed recorder cost under 2% of the
    measured train wall."""
    n = 300
    probe = rl.RunRecorder()
    probe.start()
    t0 = time.perf_counter()
    for i in range(n):
        probe.on_layer_start(i)
        probe.on_layer_end(i, total=n, stages=1, rows=100)
    per_layer = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    for _ in range(n):
        with probe.phase("probe"):
            pass
    per_phase = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    for _ in range(20):
        probe.poll_memory()
    per_poll = (time.perf_counter() - t0) / 20

    run = flagship["model"].run_report["run"]
    n_layers = len(run["layers"])
    n_phases = len(run["phases"])
    n_polls = run["deviceMemory"]["polls"]
    # layer/phase pulses already include one poll each — pricing polls
    # again on top over-counts, which only makes the bound harder
    attributed = (
        n_layers * per_layer + n_phases * per_phase + n_polls * per_poll
    )
    # absolute floor, the RunTolerances.phase_min_seconds pattern: when
    # every program the flagship needs is already warm from earlier
    # suites the train collapses to tens of milliseconds, and 2% of a
    # 40 ms train is below the recorder's fixed per-pulse cost — a bound
    # about warm-cache luck, not recorder overhead. The relative bound
    # still governs any train above 1.25 s (every cold/real one).
    assert attributed < max(0.02 * flagship["wall"], 0.025), (
        f"recorder overhead {attributed:.4f}s on a "
        f"{flagship['wall']:.2f}s train ({n_layers} layers, "
        f"{n_phases} phases, {n_polls} polls)"
    )
