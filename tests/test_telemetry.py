"""Unified telemetry plane (telemetry/) — trace spans, the metrics
registry over the process ledgers, Prometheus exposition, the structured
event log, and the serving-latency histogram pipeline.

Covers: span nesting + thread isolation, ring-buffer bounds, histogram
quantile accuracy vs numpy, the Prometheus renderer's golden output,
event-log ordering under threads, the consistent cross-ledger snapshot,
the end-to-end train()+score() wiring (Chrome trace nesting, phase
breakdown, summary line, metadata payload), and the <2% overhead guard
(the PR-6 absolute-cost pattern). Marker: ``telemetry``.
"""
import json
import threading
import time

import numpy as np
import pytest

import transmogrifai_tpu.types as T
from transmogrifai_tpu import Dataset
from transmogrifai_tpu.compiler import stats as cstats
from transmogrifai_tpu.featurize import stats as fstats
from transmogrifai_tpu.features import from_dataset
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.selector import BinaryClassificationModelSelector
from transmogrifai_tpu.telemetry import events as tevents
from transmogrifai_tpu.telemetry import export as texport
from transmogrifai_tpu.telemetry import metrics as tmetrics
from transmogrifai_tpu.telemetry import spans as tspans
from transmogrifai_tpu.types.columns import column_from_values
from transmogrifai_tpu.workflow.workflow import Workflow

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _restore_telemetry():
    """Tests swap the clock / enabled-state / buffer bounds; every one of
    those must be restored or later suites measure fake time."""
    yield
    tspans.set_clock(None)
    tspans.set_enabled(True)
    tspans.configure_buffers(trace_buffer=65536, serve_ring=64)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _dataset(n=160, seed=0):
    rng = np.random.default_rng(seed)
    return Dataset.of({
        "label": column_from_values(T.RealNN, rng.integers(0, 2, n).tolist()),
        "age": column_from_values(T.Real, rng.normal(40.0, 9.0, n).tolist()),
        "city": column_from_values(
            T.PickList, [["ankara", "bern", "cairo"][i % 3] for i in range(n)]
        ),
    })


LR_MODELS = [(LogisticRegression(), {"reg_param": [0.01]})]


@pytest.fixture(scope="module")
def flagship():
    """One telemetry-enabled train + serve, with wall-clock and recording
    deltas captured for the span-wiring and overhead assertions."""
    from transmogrifai_tpu.local.scoring import score_function
    from transmogrifai_tpu.utils import uid as uid_util

    uid_util.reset()
    tspans.reset_for_tests()
    reg = tmetrics.REGISTRY
    spans_before = reg.counter("tptpu_spans_recorded_total").value
    batches_before = reg.counter("tptpu_serve_batches_total").value
    ds = _dataset()
    label, predictors = from_dataset(ds, response="label")
    checked = label.sanity_check(
        transmogrify(predictors), remove_bad_features=True
    )
    pred = (
        BinaryClassificationModelSelector(seed=7, models=LR_MODELS)
        .set_input(label, checked)
        .get_output()
    )
    t0 = time.perf_counter()
    model = Workflow().set_result_features(pred).set_input_dataset(ds).train()
    fn = score_function(model)
    rows = [{"age": 31.0 + i, "city": "bern"} for i in range(32)]
    fn.batch(rows)
    fn.columns(ds)
    wall = time.perf_counter() - t0
    return {
        "model": model,
        "fn": fn,
        "wall": wall,
        "spans": reg.counter("tptpu_spans_recorded_total").value
        - spans_before,
        "batches": reg.counter("tptpu_serve_batches_total").value
        - batches_before,
        "events": list(tspans.snapshot_events()),
    }


# ------------------------------------------------------------------- spans
def test_span_nesting_builds_serve_trace_tree():
    clock = FakeClock()
    tspans.set_clock(clock)
    tspans.reset_for_tests()
    with tspans.span("serve/request", rows=3):
        with tspans.span("serve/stage/a"):
            clock.advance(0.010)
        with tspans.span("serve/stage/b"):
            clock.advance(0.020)
        clock.advance(0.005)
    traces = tspans.recent_serve_traces()
    assert traces, "root serve/* span must land in the serving ring"
    t = traces[-1]
    assert t["name"] == "serve/request"
    assert t["attrs"] == {"rows": 3}
    assert [c["name"] for c in t["children"]] == [
        "serve/stage/a", "serve/stage/b",
    ]
    assert t["children"][0]["durMs"] == 10.0
    assert t["children"][1]["durMs"] == 20.0
    assert t["durMs"] == 35.0


@pytest.mark.parametrize("root", ["selector/sweep", "train/run"])
def test_spans_outside_a_serve_trace_keep_no_children(root):
    """Only a root ``serve/*`` span's tree reaches the serving ring, so no
    other span builds one; a ``serve/*`` span nested elsewhere neither."""
    tspans.reset_for_tests()
    with tspans.span(root) as outer:
        with tspans.span("selector/evaluate") as mid:
            with tspans.span("serve/stage/x"):
                pass
    assert outer.children is None and mid.children is None
    assert tspans.recent_serve_traces() == []


def test_a_serve_tree_keeps_grandchildren_and_not_handed_over_spans():
    tspans.reset_for_tests()
    with tspans.span("serve/request") as root:
        with tspans.span("serve/stage/a"):
            with tspans.span("serve/stage/a/inner"):
                pass
        # a span whose parent is handed over keeps out of the tree
        with tspans.span("serve/stage/b", parent=root):
            pass
    (t,) = tspans.recent_serve_traces()
    assert t["children"] == [{
        "name": "serve/stage/a", "durMs": t["children"][0]["durMs"],
        "children": [{"name": "serve/stage/a/inner",
                      "durMs": t["children"][0]["children"][0]["durMs"]}],
    }]


def test_add_seconds_sums_into_the_innermost_span():
    tspans.reset_for_tests()
    with tspans.span("selector/evaluate", lanes=2) as outer:
        tspans.add_seconds("metric_seconds", {"AuPR": 0.25, "AuROC": 0.5})
        with tspans.span("tree/await_outputs") as inner:
            tspans.add_seconds("metric_seconds", {"AuPR": 1.0})
        tspans.add_seconds("metric_seconds", {"AuPR": 0.5})
    assert outer.attrs["metric_seconds"] == {"AuPR": 0.75, "AuROC": 0.5}
    assert inner.attrs["metric_seconds"] == {"AuPR": 1.0}
    rec = tspans.snapshot_events()[-1]
    assert rec["args"] == {
        "lanes": 2, "metric_seconds": {"AuPR": 0.75, "AuROC": 0.5},
    }


def test_add_seconds_is_a_no_op_off_or_outside_a_span():
    tspans.reset_for_tests()
    tspans.add_seconds("metric_seconds", {"AuPR": 1.0})  # no span open
    tspans.set_enabled(False)
    with tspans.span("selector/evaluate") as sp:
        tspans.add_seconds("metric_seconds", {"AuPR": 1.0})
    assert sp.attrs == {} and tspans.snapshot_events() == []


def test_span_records_have_monotonic_ts_and_duration():
    clock = FakeClock()
    tspans.set_clock(clock)
    tspans.reset_for_tests()
    with tspans.span("train/fit", stage="X"):
        clock.advance(1.5)
    rec = tspans.snapshot_events()[-1]
    assert rec["name"] == "train/fit"
    assert rec["ts"] == 100.0 and rec["dur"] == 1.5
    assert rec["args"] == {"stage": "X"}


def test_spans_are_thread_isolated():
    tspans.reset_for_tests()
    barrier = threading.Barrier(4)

    def work(i):
        barrier.wait()
        for _ in range(20):
            with tspans.span(f"train/thread{i}"):
                with tspans.span(f"train/thread{i}/inner"):
                    pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    recs = tspans.snapshot_events()
    # every thread's spans carry one consistent tid, distinct per thread
    tids = {}
    for r in recs:
        name = r["name"].split("/")[1].removesuffix("inner").rstrip("/")
        tids.setdefault(name, set()).add(r["tid"])
    assert all(len(s) == 1 for s in tids.values())
    assert len({next(iter(s)) for s in tids.values()}) == 4


def test_disabled_telemetry_records_nothing():
    tspans.reset_for_tests()
    tspans.set_enabled(False)
    with tspans.span("train/layer", index=0):
        pass
    tspans.record_serve_batch("batch", 4, 0.0, {"featurize": 0.1})
    tspans.record_span("train/fit", 0.0, 1.0)
    assert tspans.snapshot_events() == []
    assert tspans.recent_serve_traces() == []


def test_disabled_telemetry_drops_events_too(tmp_path, monkeypatch):
    tevents.reset_for_tests()
    log = tmp_path / "events.jsonl"
    monkeypatch.setenv("TPTPU_EVENT_LOG", str(log))
    tspans.set_enabled(False)
    rec = tevents.emit("breaker_transition", stage="X", to="open")
    assert rec["seq"] == 0 and rec["kind"] == "breaker_transition"
    assert tevents.count() == 0 and tevents.recent() == []
    assert not log.exists()
    tspans.set_enabled(True)
    assert tevents.emit("breaker_transition", stage="X", to="open")["seq"] == 1
    assert log.exists()


def test_histogram_snapshot_is_not_torn_under_concurrent_observes():
    """count and the quantiles must come from ONE locked read: a snapshot
    racing an observe() may be from before or after it, but never
    ``count: 0`` with real quantiles (or vice versa)."""
    h = tmetrics.Histogram("tptpu_test_torn_seconds")
    stop = threading.Event()
    bad: list[dict] = []

    def writer():
        while not stop.is_set():
            h.observe(0.01)

    def reader():
        for _ in range(2000):
            s = h.snapshot()
            quants = (s["p50"], s["p95"], s["p99"])
            if (s["count"] == 0) != all(q is None for q in quants):
                bad.append(s)

    w = threading.Thread(target=writer)
    r = threading.Thread(target=reader)
    w.start(); r.start()
    r.join(); stop.set(); w.join()
    assert not bad, f"torn snapshots: {bad[:3]}"


def test_ring_buffer_bounds_hold():
    tspans.reset_for_tests()
    tspans.configure_buffers(trace_buffer=16, serve_ring=4)
    for i in range(50):
        with tspans.span("train/bound_probe", i=i):
            pass
        tspans.record_serve_batch("batch", 1, tspans.clock(), {})
    events = tspans.snapshot_events()
    assert len(events) == 16
    # newest survive, oldest evicted
    assert events[-1]["args"] == {"rows": 1, "entry": "batch"}
    assert len(tspans.recent_serve_traces()) == 4
    assert tspans.buffer_bounds() == (16, 4)


def test_injectable_clock_is_the_tpl004_seam():
    clock = FakeClock()
    tspans.set_clock(clock)
    assert tspans.clock() == 100.0
    clock.advance(5.0)
    assert tspans.clock() == 105.0
    tspans.set_clock(None)
    assert tspans.clock() != 105.0


# -------------------------------------------------------------- histograms
def test_histogram_quantiles_track_numpy():
    rng = np.random.default_rng(3)
    samples = rng.lognormal(mean=-6.0, sigma=1.2, size=20_000)
    h = tmetrics.Histogram("t_q")
    for v in samples:
        h.observe(float(v))
    for q in (0.50, 0.95, 0.99):
        est = h.quantile(q)
        ref = float(np.quantile(samples, q))
        # exponential buckets grow 1.3x: the interpolated estimate must
        # stay within one bucket's relative resolution of numpy
        assert abs(est - ref) / ref < 0.35, (q, est, ref)
    assert h.count == 20_000
    assert abs(h.sum - samples.sum()) < 1e-6 * samples.sum()


def test_histogram_empty_and_bucket_counts():
    h = tmetrics.Histogram("t_e", bounds=(0.1, 1.0))
    assert h.quantile(0.5) is None
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    cum, count, total = h.bucket_counts()
    assert cum == [1, 2, 3] and count == 3
    assert total == pytest.approx(5.55)


def test_exponential_buckets_shape():
    b = tmetrics.exponential_buckets(1e-3, 2.0, 4)
    assert b == (1e-3, 2e-3, 4e-3, 8e-3)
    with pytest.raises(ValueError):
        tmetrics.exponential_buckets(0.0, 2.0, 4)


# ---------------------------------------------------------------- registry
def test_registry_dedupes_by_name_and_labels():
    reg = tmetrics.MetricsRegistry()
    assert reg.counter("c") is reg.counter("c")
    assert reg.gauge("g") is reg.gauge("g")
    h1 = reg.histogram("h", labels={"stage": "a"})
    h2 = reg.histogram("h", labels={"stage": "b"})
    assert h1 is not h2
    assert reg.histogram("h", labels={"stage": "a"}) is h1
    assert set(reg.histograms_named("h")) == {h1, h2}


def test_cross_ledger_snapshot_is_consistent_under_writers():
    """Satellite: the three ledgers share one lock, so a reader holding
    ``snapshot_lock()`` sees a consistent point-in-time view ACROSS
    ledgers — paired writes can never tear."""
    stop = threading.Event()
    cs, fs = cstats.stats(), fstats.stats()
    # earlier suites bump these cumulative process ledgers independently:
    # compare DELTAS from a baseline taken before the writers start
    with tmetrics.snapshot_lock():
        a0 = cs.snapshot()["dedupHits"]
        b0 = fs.snapshot()["poolTasks"]

    def writer():
        while not stop.is_set():
            # the PAIR is atomic under the shared re-entrant lock
            with tmetrics.snapshot_lock():
                cs.bump("dedupHits")
                fs.bump("poolTasks")

    threads = [threading.Thread(target=writer) for _ in range(3)]
    for th in threads:
        th.start()
    try:
        for _ in range(200):
            with tmetrics.snapshot_lock():
                a = cs.snapshot()["dedupHits"] - a0
                b = fs.snapshot()["poolTasks"] - b0
            assert a == b, "torn cross-ledger snapshot"
    finally:
        stop.set()
        for th in threads:
            th.join()


def test_ledger_delta_helpers_are_the_shared_core():
    before = cstats.snapshot()
    cstats.stats().record_compile("probe_prog")
    d = cstats.delta(before)
    assert d["programsCompiled"] == 1
    assert d["programsCompiledByName"] == {"probe_prog": 1}
    fbefore = fstats.snapshot()
    fstats.stats().record_stage("ProbeStage", rows=100, seconds=0.5)
    fd = fstats.delta(fbefore)
    assert fd["stagesExecuted"] == 1
    assert fd["stageRowsPerSec"]["ProbeStage"]["rows"] == 100


# -------------------------------------------------------------- event log
def test_event_log_sequence_is_strictly_monotonic_under_threads():
    tevents.reset_for_tests()
    barrier = threading.Barrier(8)

    def emitter(i):
        barrier.wait()
        for j in range(50):
            tevents.emit("probe", worker=i, j=j)

    threads = [threading.Thread(target=emitter, args=(i,)) for i in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    recs = tevents.recent()
    seqs = [r["seq"] for r in recs]
    # buffer order IS seq order, gapless, and count() survives eviction
    assert seqs == list(range(1, 401))
    assert tevents.count() == 400


def test_event_log_jsonl_roundtrip(tmp_path):
    tevents.reset_for_tests()
    tevents.emit("failover", host="h1", reason="heartbeat")
    tevents.emit("breaker_transition", stage="s", transition="closed->open")
    path = str(tmp_path / "events.jsonl")
    assert tevents.write(path) == 2
    lines = [json.loads(l) for l in open(path).read().splitlines()]
    assert [l["kind"] for l in lines] == ["failover", "breaker_transition"]
    assert lines[0]["seq"] == 1 and lines[1]["seq"] == 2
    assert tevents.to_jsonl().count("\n") == 1


def test_event_log_disk_append_via_env(tmp_path, monkeypatch):
    tevents.reset_for_tests()
    path = str(tmp_path / "live.jsonl")
    monkeypatch.setenv("TPTPU_EVENT_LOG", path)
    tevents.emit("drift_alert", feature="age")
    tevents.emit("checkpoint_save", layer=0)
    lines = open(path).read().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[1])["kind"] == "checkpoint_save"


# ------------------------------------------------------------- prometheus
def test_render_prometheus_golden_output():
    reg = tmetrics.MetricsRegistry()
    reg.counter("tptpu_test_total").inc(3)
    reg.gauge("tptpu_g").set(2.5)
    h = reg.histogram("tptpu_h", labels={"stage": "total"}, bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    reg.register_source("src", lambda: {"fooBar": 7, "byName": {"a": 1}})
    golden = "\n".join([
        "# TYPE tptpu_test_total counter",
        "tptpu_test_total 3",
        "# TYPE tptpu_g gauge",
        "tptpu_g 2.5",
        "# TYPE tptpu_h histogram",
        'tptpu_h_bucket{le="0.1",stage="total"} 1',
        'tptpu_h_bucket{le="1",stage="total"} 2',
        'tptpu_h_bucket{le="+Inf",stage="total"} 3',
        'tptpu_h_sum{stage="total"} 5.55',
        'tptpu_h_count{stage="total"} 3',
        "# TYPE tptpu_src_by_name gauge",
        'tptpu_src_by_name{name="a"} 1',
        "# TYPE tptpu_src_foo_bar gauge",
        "tptpu_src_foo_bar 7",
    ]) + "\n"
    assert texport.render_prometheus(reg) == golden


def test_render_prometheus_exposes_every_ledger_counter():
    """Acceptance: every compileStats, featurizeStats, and resilience
    counter appears in the exposition (zero-valued on a fresh source)."""
    text = texport.render_prometheus()
    from transmogrifai_tpu.compiler.stats import _COUNTER_KEYS as CK
    from transmogrifai_tpu.featurize.stats import _COUNTER_KEYS as FK
    from transmogrifai_tpu.resilience.distributed import _ZERO_LEDGER

    def snake(k):
        return texport._snake(k)

    for key in CK:
        assert f"tptpu_compile_{snake(key)}" in text, key
    for key in FK:
        assert f"tptpu_featurize_{snake(key)}" in text, key
    for key in _ZERO_LEDGER:
        assert f"tptpu_resilience_{snake(key)}" in text, key
    for key in (
        "score_functions", "quarantined_rows", "guarded_rows",
        "drift_alerts", "breaker_trips", "breaker_short_circuits",
    ):
        assert f"tptpu_serving_{key}" in text, key


def test_dead_source_does_not_kill_exposition():
    reg = tmetrics.MetricsRegistry()
    reg.register_source("dead", lambda: 1 / 0)
    reg.counter("tptpu_ok_total").inc()
    text = texport.render_prometheus(reg)
    assert "tptpu_ok_total 1" in text


# --------------------------------------------------- end-to-end train+serve
def test_train_and_serve_emit_nested_spans(flagship):
    names = {r["name"] for r in flagship["events"]}
    for expect in (
        "train/ingest", "train/layer", "train/fit", "train/transform",
        "train/eval", "serve/batch",
    ):
        assert expect in names, f"missing span family {expect}"
    # Perfetto nests by time containment: every train/fit span must sit
    # inside some train/layer span on the same thread
    layers = [
        r for r in flagship["events"] if r["name"] == "train/layer"
    ]
    fits = [r for r in flagship["events"] if r["name"] == "train/fit"]
    assert layers and fits
    for f in fits:
        assert any(
            l["tid"] == f["tid"]
            and l["ts"] <= f["ts"]
            and f["ts"] + f["dur"] <= l["ts"] + l["dur"] + 1e-9
            for l in layers
        ), "train/fit span not contained in any train/layer span"


def test_chrome_trace_export_opens_in_perfetto_format(flagship, tmp_path):
    path = str(tmp_path / "trace.json")
    doc = texport.export_chrome_trace(path)
    on_disk = json.load(open(path))
    assert on_disk["traceEvents"] == doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert len(doc["traceEvents"]) >= len(flagship["events"])
    ev = doc["traceEvents"][0]
    assert ev["ph"] == "X" and "ts" in ev and "dur" in ev
    assert ev["cat"] == ev["name"].split("/", 1)[0]


def test_phase_breakdown_attributes_train_time(flagship):
    pb = texport.phase_breakdown()
    assert set(pb) == {
        "ingest", "featurize", "compile", "fit", "eval", "explain",
    }
    # a real train spent real time fitting and transforming. The
    # featurize check reads the UNROUNDED span events: when an earlier
    # suite in the same process warmed every stage cache, the whole
    # transform loop can legitimately take <0.5 ms, and the rounded
    # phase_breakdown() cell floors to 0.0 — the spans must still exist
    assert pb["fit"] > 0.0
    featurize_s = sum(
        rec["dur"] for rec in flagship["events"]
        if rec["name"].startswith("train/transform")
    )
    assert featurize_s > 0.0


def test_serve_latency_histograms_have_stage_families(flagship):
    lat = texport.serve_latency_summary()
    assert lat["total"]["count"] >= flagship["batches"]
    for fam in ("featurize", "download"):
        assert fam in lat and lat[fam]["count"] >= 1
        assert lat[fam]["p50Ms"] is not None
        assert lat[fam]["p50Ms"] <= lat[fam]["p99Ms"]


def test_serve_ring_and_metadata_payload(flagship):
    fn = flagship["fn"]
    traces = tspans.recent_serve_traces()
    assert any(t.get("entry") == "batch" for t in traces)
    assert any(t.get("entry") == "columns" for t in traces)
    batch_trace = [t for t in traces if t.get("entry") == "batch"][-1]
    assert batch_trace["rows"] == 32
    assert "featurize" in batch_trace["stagesMs"]
    md = fn.metadata()
    tel = md["telemetry"]
    assert tel["serveBatches"] >= 2
    assert tel["serveRows"] >= 32
    assert tel["serveLatencyMs"]["total"]["p50Ms"] is not None


def test_summary_pretty_has_consolidated_telemetry_line(flagship):
    pretty = flagship["model"].summary_pretty()
    assert "Telemetry:" in pretty
    assert "serve p50/p95/p99" in pretty
    assert "python -m transmogrifai_tpu metrics" in pretty


def test_warmup_emits_completion_event():
    # one warmup runs per (scope, names) per process, and earlier suites
    # may have consumed the train/score scopes — start a fresh scoped one
    from transmogrifai_tpu.compiler import warmup
    from transmogrifai_tpu.utils import aot

    if not aot._enabled():
        pytest.skip("program bank disabled")
    tevents.reset_for_tests()
    warmup.reset_for_tests()
    th = warmup.start_warmup(
        frozenset({"predict_boosted"}), scope="telemetry-test"
    )
    assert th is not None
    th.join(timeout=30)
    recs = [r for r in tevents.recent() if r["kind"] == "warmup_complete"]
    assert recs and recs[-1]["programs"] >= 0
    assert recs[-1]["overlapSeconds"] >= 0.0


def test_overhead_under_two_percent(flagship):
    """Acceptance guard, PR-6 absolute-cost pattern: price one span and
    one serve-batch recording with a tight micro-benchmark, multiply by
    how many the flagship train+serve actually recorded, and require the
    attributed telemetry cost under 2% of the measured wall."""
    n = 5000
    t0 = time.perf_counter()
    for _ in range(n):
        with tspans.span("train/overhead_probe"):
            pass
    per_span = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    for _ in range(n):
        tspans.record_serve_batch(
            "batch", 1, tspans.clock(),
            {"sentinel": 0.0, "featurize": 0.0, "dispatch": 0.0},
        )
    per_batch = (time.perf_counter() - t0) / n
    attributed = (
        flagship["spans"] * per_span + flagship["batches"] * per_batch
    )
    assert attributed < 0.02 * flagship["wall"], (
        f"telemetry overhead {attributed:.4f}s on a "
        f"{flagship['wall']:.2f}s train+serve "
        f"({flagship['spans']} spans, {flagship['batches']} batches)"
    )


# ------------------------------------------------------------------- events
def test_breaker_transition_emits_event():
    from transmogrifai_tpu.resilience.sentinel import (
        BreakerConfig, CircuitBreaker,
    )

    tevents.reset_for_tests()
    clock = FakeClock()
    br = CircuitBreaker(
        "stage_x", BreakerConfig(failure_threshold=2, clock=clock)
    )
    br.record_failure()
    br.record_failure()  # -> open
    recs = [r for r in tevents.recent() if r["kind"] == "breaker_transition"]
    assert recs and recs[-1]["transition"] == "closed->open"
    assert recs[-1]["stage"] == "stage_x"
    clock.advance(60.0)
    assert br.allow()  # -> half_open
    br.record_success()  # -> closed
    transitions = [
        r["transition"] for r in tevents.recent()
        if r["kind"] == "breaker_transition"
    ]
    assert transitions == ["closed->open", "open->half_open",
                           "half_open->closed"]


def test_cli_metrics_and_trace_commands(tmp_path, capsys):
    from transmogrifai_tpu.cli import run_metrics, run_trace

    assert run_metrics(as_json=False) == 0
    out = capsys.readouterr().out
    assert "tptpu_compile_programs_compiled" in out
    assert run_metrics(as_json=True) == 0
    snap = json.loads(capsys.readouterr().out)
    assert "sources" in snap and "histograms" in snap
    trace_path = str(tmp_path / "t.json")
    events_path = str(tmp_path / "e.jsonl")
    assert run_trace(trace_path, events_path) == 0
    doc = json.load(open(trace_path))
    assert "traceEvents" in doc


# ------------------------------------------- ids, parents and traces (PR 25)
def test_span_ids_parent_and_trace_on_one_thread():
    tspans.reset_for_tests()
    with tspans.span("selector/sweep") as root:
        assert tspans.current() is root
        with tspans.span("selector/validate") as mid:
            with tspans.span("tree/fit_dispatch") as leaf:
                pass
        tspans.record_span("serve/stage/x", 0.0, 0.1)
    assert tspans.current() is None
    with tspans.span("selector/sweep") as other:
        pass
    recs = {r["id"]: r for r in tspans.snapshot_events()}
    assert len(recs) == 5, "ids are unique"
    assert root.parent is None and root.trace == root.id
    assert (mid.parent, mid.trace) == (root.id, root.id)
    assert (leaf.parent, leaf.trace) == (mid.id, root.id)
    assert recs[leaf.id]["parent"] == mid.id and recs[leaf.id]["trace"] == root.id
    post_hoc = next(r for r in recs.values() if r["name"] == "serve/stage/x")
    assert post_hoc["parent"] == root.id and post_hoc["trace"] == root.id
    assert other.trace == other.id != root.id, "one trace per root"
    assert root.id < mid.id < leaf.id < other.id, "one process-wide counter"


def test_span_parent_is_handed_across_a_thread_pool():
    from concurrent.futures import ThreadPoolExecutor

    tspans.reset_for_tests()

    def work(handle, i):
        assert tspans.current() is None, "a pool thread starts with no stack"
        with tspans.span("selector/family", parent=handle, family=i) as fam:
            with tspans.span("tree/bin_prepare") as inner:
                pass
        return fam, inner

    with tspans.span("selector/validate") as caller:
        handle = tspans.current()
        with ThreadPoolExecutor(max_workers=3) as pool:
            got = list(pool.map(lambda i: work(handle, i), range(3)))
    for fam, inner in got:
        assert (fam.parent, fam.trace) == (caller.id, caller.id)
        assert (inner.parent, inner.trace) == (fam.id, caller.id)
    recs = tspans.snapshot_events()
    assert {r["trace"] for r in recs} == {caller.id}
    by_id = {r["id"]: r for r in recs}
    assert by_id[got[0][0].id]["tid"] != by_id[caller.id]["tid"]
    # without the hand-over the link is lost: the worker's span is a root
    with tspans.span("selector/validate") as caller2:
        with ThreadPoolExecutor(max_workers=1) as pool:
            orphan = pool.submit(lambda: work(None, 9)).result()[0]
    assert orphan.parent is None and orphan.trace == orphan.id != caller2.id


def test_chrome_trace_export_carries_ids():
    tspans.reset_for_tests()
    with tspans.span("selector/sweep", rows=7) as root:
        with tspans.span("selector/row_select"):
            pass
    events = texport.export_chrome_trace()["traceEvents"]
    child, parent = events[0]["args"], events[1]["args"]
    assert parent == {"rows": 7, "id": root.id, "parent": None, "trace": root.id}
    assert child["parent"] == root.id and child["trace"] == root.id


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``."""

    made: list = []

    def __init__(self, name, **kwargs):
        type(self).made.append((name, kwargs))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_span_enters_a_profiler_annotation_only_when_enabled(monkeypatch):
    monkeypatch.setattr(tspans, "_ANNOTATION", _CountingAnnotation)
    monkeypatch.setattr(_CountingAnnotation, "made", [])
    tspans.reset_for_tests()
    with tspans.span("tree/thresholds", rows=3) as sp:
        pass
    assert _CountingAnnotation.made == [(
        "tptpu:tree/thresholds",
        {"id": sp.id, "parent": 0, "trace": sp.id},
    )]
    tspans.record_span("serve/stage/x", 0.0, 0.1)
    assert len(_CountingAnnotation.made) == 1, "post-hoc records stay as is"
    tspans.set_enabled(False)
    with tspans.span("tree/thresholds") as off:
        assert tspans.current() is None
    assert len(_CountingAnnotation.made) == 1, "disabled: no annotation made"
    assert off.id is None and off.trace is None


def test_tptpu_annotations_land_in_a_profiler_trace(tmp_path):
    """The program's spans on the profiler's clock: inside a
    ``jax.profiler`` trace, ``tptpu:<name>`` events nest as the spans do."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tspans.span("selector/sweep"):
            with tspans.span("tree/thresholds"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {
        e.name: (int(e.start_ns), int(e.start_ns + e.duration_ns))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith("tptpu:")
    }
    assert set(found) == {"tptpu:selector/sweep", "tptpu:tree/thresholds"}
    outer, inner = found["tptpu:selector/sweep"], found["tptpu:tree/thresholds"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]
    assert inner[1] - inner[0] >= 2_000_000


# ------------------------------------- the sweep's and the tree fit's spans
SWEEP_TREE = {
    "selector/sweep": None,
    "selector/row_select": "selector/sweep",
    "selector/validate": "selector/sweep",
    "selector/family": "selector/validate",
    "tree/bin_prepare": "selector/family",
    "tree/thresholds": "tree/bin_prepare",
    "tree/upload": "tree/bin_prepare",
    "tree/bin_dispatch": "tree/bin_prepare",
    "tree/feature_groups": "tree/bin_prepare",
    "tree/fit_dispatch": "selector/family",
    "selector/refit": "selector/sweep",
    # the fold lanes' metrics under the family, the refit lane's (the train
    # evaluation) under the refit
    "selector/evaluate": ("selector/family", "selector/refit"),
    # one read of the fold lanes' outputs under the family; under the
    # refit the winner lane's outputs and its lane of the tree stack
    "tree/await_outputs": ("selector/family", "selector/refit"),
}


def _tree_table(n=600, seed=0):
    rng = np.random.default_rng(seed)
    x = np.concatenate(
        [rng.normal(size=(n, 4)), rng.integers(0, 2, (n, 3))], axis=1
    ).astype(np.float32)
    y = (x[:, 0] + x[:, 4] + rng.normal(size=n) > 0.5).astype(np.float64)
    return x, y


@pytest.fixture(scope="module")
def xgb_sweep():
    """One ``ModelSelector.fit_arrays`` over an XGBoost grid (2 points,
    one split + the refit lane) on a small table, scatter histograms."""
    from transmogrifai_tpu.models import gbdt
    from transmogrifai_tpu.models import trees as TR
    from transmogrifai_tpu.selector.model_selector import make_candidates
    from transmogrifai_tpu.selector.validators import TrainValidationSplit

    x, y = _tree_table()
    models = make_candidates("BinaryClassification", ["OpXGBoostClassifier"])
    for _est, grid in models:
        grid.update(num_round=[2], max_depth=[3])
    selector = BinaryClassificationModelSelector(
        seed=3, models=models, validator=TrainValidationSplit(seed=3)
    )
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(TR, "_resolved_impl", lambda: "scatter")
        gbdt._BINNED_CACHE.clear()
        tspans.reset_for_tests()
        t0 = time.perf_counter()
        selected = selector.fit_arrays(x, y, np.ones(len(y), np.float32))
        wall = time.perf_counter() - t0
    return {
        "selected": selected, "wall": wall,
        "records": list(tspans.snapshot_events()),
    }


def test_fit_arrays_yields_the_sweep_span_tree(xgb_sweep):
    recs = xgb_sweep["records"]
    by_id = {r["id"]: r for r in recs}
    (root,) = [r for r in recs if r["parent"] is None]
    assert root["name"] == "selector/sweep"
    assert {r["trace"] for r in recs} == {root["id"]}, "one trace id"
    assert {r["name"] for r in recs} == set(SWEEP_TREE)
    for r in recs:
        want = SWEEP_TREE[r["name"]]
        got = by_id[r["parent"]]["name"] if r["parent"] else None
        assert got in (want if isinstance(want, tuple) else (want,)), r
    counts = {n: sum(r["name"] == n for r in recs) for n in SWEEP_TREE}
    assert counts.pop("tree/await_outputs") == 3
    assert counts.pop("selector/evaluate") == 2
    assert set(counts.values()) == {1}, counts
    # the candidate pool ran the family on its own thread
    fam = next(r for r in recs if r["name"] == "selector/family")
    assert fam["tid"] != root["tid"]
    # attributes at the boundaries
    assert root["args"] == {
        "rows": 600, "cols": 7, "families": 1, "points": 2, "lanes": 4,
    }
    evaluated = [r["args"] for r in recs if r["name"] == "selector/evaluate"]
    timed = ("metric_seconds", "predict_seconds")
    # bytes: the [4, 600] float32 margins the fold lanes are scored from,
    # and the refit lane's row of them; reads: what the caller reads of the
    # metrics, the selector's one in the fold lanes, all of them in the
    # refit's train evaluation
    assert [{k: v for k, v in a.items() if k not in timed}
            for a in evaluated] == [
        {"lanes": 2, "rows": 600, "classes": 2, "bytes": 9600,
         "reads": "AuPR"},
        {"lanes": 1, "rows": 600, "classes": 2, "bytes": 2400,
         "reads": "all"},
    ]
    args = {r["name"]: r.get("args", {}) for r in recs}
    assert args["selector/row_select"] == {
        # a mask of ones keeps every row: the families get x and y themselves
        "rows_in": 600, "rows_out": 600, "bytes_copied": 0,
    }
    assert args["selector/validate"] == {"extra_masks": 1, "folds": 1}
    assert args["selector/family"] == {
        "family": "XGBoostClassifier", "points": 2, "attempts": 1,
    }
    assert args["tree/fit_dispatch"] == {
        "lanes": 4, "rounds": 2, "depth": 3, "bins": 32,
        "hist_impl": "scatter", "hist_tiles": "none",
        # (g, h); at 8 slots the kernel builds the 4 lanes in one pass:
        # its operand (4 variants x 4 lanes x 8 slots) is one whole
        # 128-lane tile, room for the 2 statistics and no more
        "stat_channels": 2, "stat_channels_built": 2,
        "objective": "binary:logistic", "tree_weights": "0.02 0.02",
    }
    assert args["tree/feature_groups"] == {"narrow": 3, "wide": 4}
    refit = dict(args["selector/refit"])
    assert refit.pop("predict_seconds") >= 0.0
    assert refit == {"prefit": True}
    assert args["tree/bin_prepare"]["cache"] == "miss"


def test_sweep_scoring_names_its_seconds(xgb_sweep):
    """The fold lanes' turning of outputs into predictions rides their
    ``selector/evaluate`` span, the winner's on ``selector/refit``; the
    evaluator's groups of metrics ride each ``selector/evaluate``: the fold
    lanes' the selector's metric alone, the train evaluation's all four."""
    recs = xgb_sweep["records"]
    fold, train = [r["args"] for r in recs if r["name"] == "selector/evaluate"]
    (refit,) = [r["args"] for r in recs if r["name"] == "selector/refit"]
    assert set(fold["metric_seconds"]) == {fold["reads"]} == {"AuPR"}
    assert train["reads"] == "all"
    assert set(train["metric_seconds"]) == {
        "AuPR", "AuROC", "Counts", "ThresholdCurve"}
    for args in (fold, train):
        assert all(s >= 0.0 for s in args["metric_seconds"].values())
    assert fold["predict_seconds"] >= 0.0 and refit["predict_seconds"] >= 0.0
    assert "predict_seconds" not in train
    # the seconds lie inside the spans that hold them
    (fold_rec,) = [r for r in recs if r.get("args") is fold]
    assert (
        fold["predict_seconds"] + sum(fold["metric_seconds"].values())
        <= fold_rec["dur"]
    )


def test_per_fold_scoring_states_what_it_reads():
    """A family without batched scoring takes the validator's per-fold
    loop: its ``selector/evaluate`` spans carry the same attributes, and
    its fold lanes are scored by the selector's metric alone."""
    x, y = _tree_table(seed=4)
    selector = BinaryClassificationModelSelector(seed=5, models=LR_MODELS)
    tspans.reset_for_tests()
    selector.fit_arrays(x, y, np.ones(len(y), np.float32))
    recs = tspans.snapshot_events()
    evaluated = [r["args"] for r in recs if r["name"] == "selector/evaluate"]
    folds = [a for a in evaluated if a["reads"] != "all"]
    assert folds and all(a["reads"] == "AuPR" for a in folds)
    assert [a["reads"] for a in evaluated].count("all") == 1
    for a in folds:
        assert a["predict_seconds"] >= 0.0
        assert set(a["metric_seconds"]) == {"AuPR"}
    (train,) = [a for a in evaluated if a["reads"] == "all"]
    assert set(train["metric_seconds"]) == {
        "AuPR", "AuROC", "Counts", "ThresholdCurve"}
    (refit,) = [r["args"] for r in recs if r["name"] == "selector/refit"]
    assert refit["predict_seconds"] >= 0.0


def test_sweep_children_lie_inside_parents_and_root_self_time_is_small(
    xgb_sweep,
):
    recs = xgb_sweep["records"]
    by_id = {r["id"]: r for r in recs}
    (root,) = [r for r in recs if r["parent"] is None]
    for r in recs:
        if r["parent"] is None:
            continue
        p = by_id[r["parent"]]
        assert p["ts"] <= r["ts"], (r, p)
        assert r["ts"] + r["dur"] <= p["ts"] + p["dur"] + 1e-6, (r, p)
    covered = sum(r["dur"] for r in recs if r["parent"] == root["id"])
    assert root["dur"] - covered < 0.10 * root["dur"]
    # on one thread at a time, the spans without children and the self
    # time of those with children add up to the root
    parents = {r["parent"] for r in recs}
    leaves = sum(r["dur"] for r in recs if r["id"] not in parents)
    selfs = sum(
        r["dur"] - sum(c["dur"] for c in recs if c["parent"] == r["id"])
        for r in recs if r["id"] in parents
    )
    assert leaves + selfs == pytest.approx(root["dur"], rel=1e-6)


def test_binned_records_miss_then_hit_and_the_ledger_agrees():
    from transmogrifai_tpu.models import gbdt

    x, _y = _tree_table(seed=1)
    est = gbdt.XGBoostClassifier(max_bins=8)
    gbdt._BINNED_CACHE.clear()
    tspans.reset_for_tests()
    before = gbdt.bin_cache_stats().snapshot()
    first = est._binned(x)
    second = est._binned(x)
    assert second[1] is first[1], "the hit hands back the cached codes"
    preps = [
        r["args"] for r in tspans.snapshot_events()
        if r["name"] == "tree/bin_prepare"
    ]
    assert [a["cache"] for a in preps] == ["miss", "hit"]
    entries = list(gbdt._BINNED_CACHE.values())
    assert len(entries) == 1
    for a in preps:
        assert a["cache_entries"] == 1
        assert a["cache_device_bytes"] == entries[0][2].nbytes == 600 * 7 * 4
        assert a["cache_host_bytes"] == x.nbytes + entries[0][1].nbytes
    # the miss did the work, the hit none of it
    names = [r["name"] for r in tspans.snapshot_events()]
    assert names.count("tree/thresholds") == 1
    assert names.count("tree/bin_dispatch") == 1
    # the miss names the route its column statistics took (a plane this
    # small stays on the host), and the ledger counts it
    (thr,) = [
        r["args"] for r in tspans.snapshot_events()
        if r["name"] == "tree/thresholds"
    ]
    assert thr == {
        "rows": 600, "cols": 7, "bins": 8, "dtype": "float32",
        "route": "host", "why": "small",
    }
    now = gbdt.bin_cache_stats().snapshot()
    assert now["thresholdsHost"] - before["thresholdsHost"] == 1
    assert now["thresholdsDevice"] == before["thresholdsDevice"]
    assert now["binCacheLookups"] - before["binCacheLookups"] == 2
    assert now["binCacheHits"] - before["binCacheHits"] == 1
    assert now["binCacheEntries"] == 1
    assert now["binCacheDeviceBytes"] == preps[-1]["cache_device_bytes"]
    # an operator's scrape sees the same ledger
    text = texport.render_prometheus()
    assert f"tptpu_tree_bin_cache_lookups {now['binCacheLookups']}" in text
    assert "tptpu_tree_bin_cache_device_bytes 16800" in text
    assert f"tptpu_tree_thresholds_host {now['thresholdsHost']}" in text


def test_disabled_telemetry_leaves_sweep_path_unrecorded():
    from transmogrifai_tpu.models import gbdt
    from transmogrifai_tpu.models import trees as TR

    x, _y = _tree_table(seed=2)
    est = gbdt.XGBoostClassifier(max_bins=8)
    gbdt._BINNED_CACHE.clear()
    tspans.reset_for_tests()
    before = gbdt.bin_cache_stats().snapshot()
    spans_before = tmetrics.REGISTRY.counter("tptpu_spans_recorded_total").value
    tspans.set_enabled(False)
    thresholds, binned, _groups = est._binned(x)
    est._binned(x)
    host = TR.await_outputs(binned)
    tspans.set_enabled(True)
    assert thresholds.shape == (7, 7) and host.shape == (600, 7)
    assert tspans.snapshot_events() == []
    assert gbdt.bin_cache_stats().snapshot() == before, "no ledger change"
    assert (
        tmetrics.REGISTRY.counter("tptpu_spans_recorded_total").value
        == spans_before
    )
    assert len(gbdt._BINNED_CACHE) == 1, "the cache itself still works"


TREE_SCOPES = (
    "tree/histogram", "tree/split_search", "tree/partition", "tree/leaf",
    "tree/gradients", "tree/outputs",
)


@pytest.mark.parametrize("program", ["boost", "forest", "bin"])
def test_device_scope_names_are_in_the_lowered_program(program):
    """``jax.named_scope`` names reach the op metadata of the fit programs
    (what a device trace shows for each op)."""
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.models import trees as TR

    n, f, k = 64, 5, 2
    S = jax.ShapeDtypeStruct
    groups = (S((2,), jnp.int32), S((3,), jnp.int32))
    kf = S((k,), jnp.float32)
    if program == "boost":
        lowered = TR._boost_rounds_batched.lower(
            S((n, f), jnp.int32), S((n,), jnp.float32), S((k, n), jnp.float32),
            S((k, n), jnp.float32), kf, S((), jnp.float32),
            S((), jnp.float32), kf, S((), jnp.float32), groups,
            num_rounds=1, max_depth=2, num_bins=4,
            objective="binary:logistic", hist_impl="scatter",
        )
        wanted = TREE_SCOPES + ("tree/group_columns",)
    elif program == "forest":
        lowered = TR._forest_trees_scan.lower(
            S((n, f), jnp.int32), S((n,), jnp.float32), S((k, n), jnp.float32),
            S((1,), jnp.uint32), kf, kf, kf, kf, groups, None,
            num_trees=2, max_depth=2, num_bins=4, bootstrap=True, lowp=False,
            hist_impl="scatter", feature_subset=2, info_gain_norm=4.0,
        )
        wanted = TREE_SCOPES + ("tree/group_columns", "tree/node_subset")
    else:
        lowered = jax.jit(TR.bin_data).lower(
            S((n, f), jnp.float32), S((f, 3), jnp.float32)
        )
        wanted = ("tree/bin",)
    text = lowered.as_text(debug_info=True)
    missing = [s for s in wanted if f"{s}/" not in text and f"{s}\"" not in text]
    assert not missing, missing


def test_sweep_span_overhead_under_two_percent(xgb_sweep):
    """The <2% guard with the profiler annotation inside ``span``: price a
    span (annotation included), multiply by what one sweep records."""
    n = 5000
    t0 = time.perf_counter()
    for _ in range(n):
        with tspans.span("tree/overhead_probe", rows=1):
            pass
    per_span = (time.perf_counter() - t0) / n
    attributed = len(xgb_sweep["records"]) * per_span
    assert len(xgb_sweep["records"]) < 40, "some tens of spans a sweep"
    assert attributed < 0.02 * xgb_sweep["wall"], (
        per_span, attributed, xgb_sweep["wall"],
    )
