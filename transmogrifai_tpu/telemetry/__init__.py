"""Unified telemetry plane — trace spans, one metrics registry over the
process ledgers, Prometheus-style export, and the serving-latency
histogram pipeline.

The observability substrate under ROADMAP item 1's standing scoring
service: the reference ships run-level introspection (ModelInsights,
per-stage summaries — SURVEY §1 L3); this plane is the live counterpart.

* :mod:`telemetry.spans` — ``span("train/layer", index=3)`` structured
  trace spans (thread-safe, injectable clock, bounded buffers), a ring of
  recent serving traces, Chrome-trace export viewable in Perfetto.
* :mod:`telemetry.metrics` — counters / gauges / exponential-bucket
  histograms, plus the shared snapshot/delta core the compileStats,
  featurizeStats, and resilience ledgers sit on (one lock ⇒ consistent
  cross-ledger snapshots).
* :mod:`telemetry.events` — the structured JSONL event log (failovers,
  breaker transitions, drift alerts, checkpoint saves, warmup
  completions) with monotonic sequence numbers.
* :mod:`telemetry.export` — ``render_prometheus()``, chrome trace export,
  the span-derived bench phase breakdown, and the ``summary_pretty()``
  line.
* :mod:`telemetry.runlog` — the training-run flight recorder: one
  schema-versioned ``RunReport`` per ``Workflow.train()`` (per-phase /
  layer / fold timings, runtime host↔device transfer census, device-
  memory high-water, live progress/ETA) plus the cross-run
  ``diff_runs`` / ``RegressionSentinel`` regression verdicts.

CLI: ``python -m transmogrifai_tpu metrics`` / ``... trace`` /
``... runs``. Docs: docs/observability.md (span taxonomy + metric
catalogue + the run ledger).
"""
from __future__ import annotations

from . import events  # noqa: F401
from . import runlog  # noqa: F401
from .export import (  # noqa: F401
    export_chrome_trace,
    metrics_snapshot,
    phase_breakdown,
    render_prometheus,
    serve_latency_summary,
    serving_snapshot,
    summary_line,
)
from .metrics import (  # noqa: F401
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    LedgerCore,
    MetricsRegistry,
    exponential_buckets,
    snapshot_lock,
)
from .spans import (  # noqa: F401
    clock,
    current,
    enabled,
    record_serve_batch,
    record_span,
    recent_serve_traces,
    reset_for_tests,
    set_clock,
    set_enabled,
    span,
)

emit = events.emit
