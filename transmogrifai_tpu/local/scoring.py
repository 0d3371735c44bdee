"""Per-row local scoring — `Map[String, Any] => Map[String, Any]`.

Reference: local/.../OpWorkflowModelLocal.scala:43-126 — the fitted workflow
exports a plain closure that scores one record dict at a time without any
cluster runtime (there via MLeap precomputed per-stage closures,
OpWorkflowModelLocal.scala:79-121; here the fitted DAG is walked ONCE at
closure-build time into a flat stage plan, so each call runs column codecs +
the per-stage transforms with no Dataset assembly or DAG re-walk).

Batch sizes are padded up to power-of-two buckets so the jitted model
predict compiles one program per bucket instead of one per distinct batch
length (single-row calls always hit the size-1 program).

For throughput, ``score_function(model)(...)`` exposes ``.batch`` accepting
a list of dicts scored as one columnar batch.

Serving sentinels (resilience/sentinel.py): every incoming row passes a
**SchemaSentinel** (missing / wrong-type / non-finite / unparseable values
handled per a configurable policy); rows that fail validation or poison a
stage are **quarantined** — recorded with (row index, feature, reason) and
replaced by the default prediction — so one bad row never kills a batch.
Stage execution runs behind a per-stage **circuit breaker** (K consecutive
failures open it; scoring degrades to default predictions for the affected
result features until a half-open probe recovers), and a **drift sentinel**
compares the live stream's per-feature fill rate and value distribution
against the training profiles captured by ``Workflow.train()``. Stage
outputs still pass the PR-1 ``ScoreGuard`` NaN/Inf containment. All
counters surface on ``score_fn.metadata()``.
"""
from __future__ import annotations

import logging
import os
import threading
import weakref
from typing import Any, Callable

import numpy as np

from ..insights import ledger as _attr_ledger
from ..insights import loco as _loco
from ..insights.drift import AttributionDriftMonitor
from ..resilience import faults
from ..resilience.guards import ScoreGuard, ScoreGuardError
from ..serving import deadline as _sdl
from ..serving import shedding as _sshed
from ..telemetry import events as _tevents
from ..telemetry import metrics as _tm
from ..telemetry import runlog as _runlog
from ..telemetry import spans as _tspans
from ..resilience.sentinel import (
    BreakerConfig,
    CircuitBreaker,
    DriftConfig,
    DriftSentinel,
    QuarantineLog,
    QuarantineRecord,
    SchemaSentinel,
    SchemaViolationError,
)
from ..types import Prediction
from ..types.columns import (
    PredictionColumn,
    column_from_values,
    concat_columns,
    empty_like,
)
from ..workflow.workflow import WorkflowModel

log = logging.getLogger(__name__)

_BUCKET_CAP = 8192

#: weakrefs to every live score function in the process — the ``serving``
#: ledger source of ``telemetry.render_prometheus()`` aggregates their
#: quarantine / guard / drift / breaker counters. The lock brackets the
#: prune+append so concurrent score_function() builds cannot drop one.
_LIVE_SCORE_FNS: list = []
_LIVE_LOCK = threading.Lock()


def _serving_source() -> dict[str, Any]:
    """Aggregate serve-side health counters across live score functions
    (reads instance counters only — never runs the drift report, which
    mutates alert bookkeeping)."""
    out = {
        "scoreFunctions": 0,
        "quarantinedRows": 0,
        "guardedRows": 0,
        "driftAlerts": 0,
        "breakerTrips": 0,
        "breakerShortCircuits": 0,
    }
    with _LIVE_LOCK:
        refs = list(_LIVE_SCORE_FNS)
    for ref in refs:
        fn = ref()
        if fn is None:
            continue
        try:
            quarantined = fn.quarantine.stats()["quarantinedRows"]
            guarded = fn.guard.stats()["guardedRows"]
            drift_alerts = getattr(fn.drift, "alerts_total", 0)
            trips = circuits = 0
            for br in fn.breakers.values():
                circuits += br.short_circuits
                trips += br.transitions.get("closed->open", 0)
                trips += br.transitions.get("half_open->open", 0)
        except Exception:  # a half-built closure must not kill exposition
            continue
        out["scoreFunctions"] += 1
        out["quarantinedRows"] += quarantined
        out["guardedRows"] += guarded
        out["driftAlerts"] += drift_alerts
        out["breakerTrips"] += trips
        out["breakerShortCircuits"] += circuits
    return out


_tm.REGISTRY.register_source("serving", _serving_source)


def _retrain_ledger() -> dict[str, Any] | None:
    """The continuous-retraining ledger (resilience/retrain.py) — None
    when the module is unavailable; monitoring must never break
    scoring."""
    try:
        from ..resilience.retrain import ledger_snapshot

        return ledger_snapshot()
    except Exception:
        return None


def _all_null(col) -> bool:
    """True when every row of the column is missing (validity mask all
    False, or every object value None for mask-less column types)."""
    mask = getattr(col, "mask", None)
    if mask is not None:
        return not np.asarray(mask, dtype=bool).any()
    try:
        return all(v is None for v in col.to_list())
    except Exception:
        return False


def _bucket(n: int) -> int:
    """Smallest power-of-two >= n (capped), else next multiple of the cap:
    bounded program count, <=2x padding overhead."""
    if n >= _BUCKET_CAP:
        return -(-n // _BUCKET_CAP) * _BUCKET_CAP
    b = 1
    while b < n:
        b *= 2
    return b


def score_function(
    model: WorkflowModel,
    guard: ScoreGuard | None = None,
    sentinel: SchemaSentinel | bool | None = None,
    breaker: BreakerConfig | bool | None = None,
    drift: DriftConfig | bool | None = None,
    isolation: str = "degrade",
    quantized: bool | None = None,
) -> Callable[[dict[str, Any]], dict[str, Any]]:
    """Returns ``row_dict -> result_dict`` (model.scoreFunction,
    OpWorkflowModelLocal.scala:79). Result keys are the result-feature names;
    Prediction features expand to their reference map keys
    (prediction/probability_*/rawPrediction_*).

    ``guard`` configures NaN/Inf containment per stage (default: replace
    bad rows with defaults and count them); ``sentinel`` the schema
    validation (default policy coerces what it can and quarantines
    unparseable rows; pass ``False`` to disable); ``breaker`` the per-stage
    circuit breaker config (``False`` disables); ``drift`` the drift
    sentinel config (active when the model carries training profiles;
    ``False`` disables). ``isolation="degrade"`` (the default) contains a
    stage exception to quarantined rows / degraded result features;
    ``"raise"`` restores fail-fast propagation for callers that prefer an
    error over silent default predictions. The installed components are
    exposed as ``score_fn.guard`` / ``.sentinel`` / ``.breakers`` /
    ``.drift`` / ``.quarantine`` and their counters via
    ``score_fn.metadata()``.

    ``quantized=True`` builds the fused serving program over the
    quantized feature plane (featurize/quantize.py): numeric value
    columns cross the boundary as uint8 codes with an in-graph dequant
    epilogue, categorical code columns shrink to their narrowest dtype.
    ``None`` (the default) defers to the ``TPTPU_FUSED_QUANT`` env knob;
    staged scoring and parity seams are unaffected either way."""
    from ..compiler import cache as _ccache
    from ..compiler import warmup as _warmup
    from ..models.base import PredictorModel
    from ..workflow.dag import compute_dag

    from ..stages.base import Estimator

    _ccache.enable_persistent_cache()
    # overlap loading the banked scoring executables with closure build
    # (compiler.warmup — one background load per process)
    _warmup.start_warmup(_warmup.SCORE_PROGRAMS, scope="score")

    # ---- build-time: flatten the fitted DAG into an ordered stage plan
    plan = []
    for layer in compute_dag(list(model.result_features)):
        for stage in layer:
            t = model.fitted.get(stage.uid, stage)
            if isinstance(t, Estimator):
                # same guard as apply_transformations_dag — fail at
                # closure-build time, not deep inside the first call
                raise ValueError(f"Stage {t} was never fitted")
            plan.append(t)
    # featurize plane: one fusion planner per closure — after the first
    # batch learns each vectorizer's width, later batches assemble the
    # whole plane into ONE [N, total_width] buffer (featurize/engine.py)
    from ..featurize.engine import FusionPlanner

    fusion = FusionPlanner(plan)
    # pipelined dispatch: columns that feed a fitted predictor stage get
    # their device upload prefetched the moment they materialize, so the
    # transfer overlaps the host stages between producer and predictor
    # (consumed via compiler.dispatch.device_f32 in the model's predict;
    # only batches above the host-predict cutoff ever dispatch on device)
    _predictor_feeds = frozenset(
        t.input_names[-1] for t in plan if isinstance(t, PredictorModel)
    )
    #: predictor-produced outputs — the columns whose render is a
    #: device->host crossing on the runtime transfer census when the
    #: batch dispatched on device (same per-row accounting convention as
    #: the static TPX census: 24 download bytes per prediction row)
    _predictor_outputs = frozenset(
        t.output_name for t in plan if isinstance(t, PredictorModel)
    )
    _device_predict_min = int(
        os.environ.get("TPTPU_HOST_PREDICT_MAX", "16384")
    )

    def _census_downloads(
        b: int, n: int, degraded: list[str], seconds: float
    ) -> None:
        """Runtime d2h census at the download point (telemetry/runlog.py):
        one crossing per rendered predictor output for a device-dispatched
        batch, 24 bytes/row (f64 pred+prob+raw — the static census's
        ``downBytesPerRow``), so ``runs --diff`` and the reconciliation
        tests can square runtime against ``audit()``'s prediction."""
        if b <= _device_predict_min:
            return  # host-predict regime: nothing crossed the boundary
        cols = [
            nm for nm in result_names
            if nm in _predictor_outputs and nm not in degraded
        ]
        if not cols:
            return
        per = seconds / len(cols)
        for _ in cols:
            _runlog.record_download(24 * n, per)
    raw_features = list(model.raw_features)
    result_names = [f.name for f in model.result_features]
    result_ftypes = {f.name: f.ftype for f in model.result_features}
    # build-time validation: every result feature must be produced by the
    # plan (or be a raw input) — a stage-plan bug must fail here, not
    # surface as rows silently missing keys at score time
    produced = {f.name for f in raw_features}
    produced.update(t.output_name for t in plan)
    missing = [n for n in result_names if n not in produced]
    if missing:
        raise ValueError(
            f"stage plan does not produce result feature(s) {missing}"
        )
    guard = guard if guard is not None else ScoreGuard()
    result_name_set = set(result_names)

    # ---- serving sentinels (None or True = defaults, False = off)
    if sentinel is None or sentinel is True:
        sentinel = SchemaSentinel(raw_features)
    elif sentinel is False:
        sentinel = None
    if breaker is None or breaker is True:
        breaker = BreakerConfig()
    elif breaker is False:
        breaker = None
    breakers: dict[str, CircuitBreaker] = {}
    profiles = getattr(model, "serving_profiles", None)
    if drift is False:
        profiles, drift = None, None
    drift_sentinel = DriftSentinel(
        profiles, drift if isinstance(drift, DriftConfig) else None
    )
    qlog = QuarantineLog()
    raise_on_stage_error = isolation == "raise"
    if isolation not in ("degrade", "raise"):
        raise ValueError(f"unknown isolation mode {isolation!r}")

    # ---- explainability plane (insights/): batched LOCO attributions for
    # ``explain=k`` calls ride the LAST fitted predictor's feature plane;
    # column groups resolve once from the fit-static vector metadata on
    # the first sweep. The attribution drift monitor compares serve-time
    # contribution distributions against the train-time baseline profile
    # persisted in the model manifest (attributionProfiles).
    _explain_model = next(
        (t for t in reversed(plan) if isinstance(t, PredictorModel)), None
    )
    _explain_vec = (
        _explain_model.input_names[-1] if _explain_model is not None else None
    )
    _explain_state: dict[str, Any] = {}
    attribution_drift = AttributionDriftMonitor(
        getattr(model, "attribution_profiles", None)
    )

    # ---- fused scoring graph (compiler/fused.py): the steady-state batch
    # path above the host-predict cutoff compiles the member vectorizers,
    # the combiner plane, the SanityChecker gathers, and the model predict
    # into ONE donated XLA dispatch — host ingest codecs up, predictor
    # core down, nothing else crosses the boundary. Unfuseable plans and
    # dispatch-time errors degrade to the staged loop below, counted
    # (fusedFallbacks / TPX008) and evented.
    #: ``reason`` holds the BUILD obstruction only (Unfuseable message /
    #: build error) — the dynamic TPTPU_FUSED=0 opt-out is derived in
    #: ``_fused_reason`` so flipping the env never erases it. The lock
    #: brackets build-once and the counter read-modify-writes: service
    #: workers share ONE closure, and a worker observing ``built`` before
    #: ``program`` publishes (or a torn ``+=``) would silently run staged
    #: / undercount the TPX008 fallbacks.
    fused_holder: dict[str, Any] = {
        "program": None, "built": False, "reason": None,
    }
    fused_counters: dict[str, Any] = {
        "dispatches": 0, "fallbacks": 0, "lastFallback": None,
        "consecutiveErrors": 0, "fallbackReasons": {},
    }
    #: quantized-plane opt-in resolves once at closure build: the arg
    #: wins, else TPTPU_FUSED_QUANT=1
    _fused_quantized = (
        quantized if quantized is not None
        else os.environ.get("TPTPU_FUSED_QUANT", "0") == "1"
    )
    _fused_lock = threading.Lock()
    #: consecutive dispatch errors that disable the fused program for this
    #: closure — a deterministically-broken program must not re-pay a
    #: failed trace (and a warning) on EVERY steady-state batch
    _FUSED_MAX_CONSECUTIVE_ERRORS = 3

    def _fused_reason() -> str | None:
        if os.environ.get("TPTPU_FUSED", "1") == "0":
            return "TPTPU_FUSED=0"
        return fused_holder["reason"]

    def _fused_program():
        """The compiled fused serving program, or None (opt-out /
        unfuseable plan shape — see ``_fused_reason``). The build is
        static — it traces/compiles nothing until the first dispatch."""
        if os.environ.get("TPTPU_FUSED", "1") == "0":
            return None
        with _fused_lock:
            if not fused_holder["built"]:
                from ..compiler import fused as _fused

                try:
                    fused_holder["program"] = _fused.build_fused_plan(
                        plan, raw_features, result_names, fusion=fusion,
                        quantize=_fused_quantized,
                    )
                    log.info(
                        "fused scoring graph ready (%s): %d member(s), "
                        "plane width %d -> %d",
                        fused_holder["program"].fingerprint,
                        len(fused_holder["program"].members),
                        fused_holder["program"].plane_width,
                        fused_holder["program"].width,
                    )
                except _fused.Unfuseable as e:
                    fused_holder["reason"] = str(e)
                    log.info("fused scoring graph unavailable: %s", e)
                except Exception as e:  # defensive — never break builds
                    fused_holder["reason"] = f"{type(e).__name__}: {e}"
                    log.warning(
                        "fused scoring graph build failed", exc_info=True
                    )
                fused_holder["built"] = True
            return fused_holder["program"]

    def _count_fused_dispatch() -> None:
        with _fused_lock:
            fused_counters["dispatches"] += 1
            fused_counters["consecutiveErrors"] = 0

    def _count_fused_fallback(
        reason: str, exc: Exception | None = None, b: int = 0
    ):
        from ..compiler import stats as cstats

        with _fused_lock:
            fused_counters["fallbacks"] += 1
            fused_counters["lastFallback"] = reason
            fused_counters["fallbackReasons"][reason] = (
                fused_counters["fallbackReasons"].get(reason, 0) + 1
            )
            prog = fused_holder["program"]
            errors = 0
            if reason == "dispatch_error" and prog is not None:
                fused_counters["consecutiveErrors"] += 1
                errors = fused_counters["consecutiveErrors"]
        cstats.stats().record_fused_fallback(reason)
        _tevents.emit("fused_fallback", reason=reason)
        # a program the compiler refuses fails the same way on every
        # batch: on the first error find out (one extra lowering, on the
        # failure path only) and say so once, at error, instead of three
        # warnings and three failed traces
        refused = prog.build_error(b) if errors == 1 else None
        disabled = None
        if refused is not None:
            import jax

            disabled = (
                f"does not compile on {jax.default_backend()} "
                f"({type(refused).__name__})"
            )
        elif errors >= _FUSED_MAX_CONSECUTIVE_ERRORS:
            # a program failing every batch is broken, not unlucky: stop
            # retrying (each retry re-pays a failed trace), keep the
            # staged loop, and say so in the audit (TPX008 reason)
            disabled = (
                f"disabled after {errors} consecutive dispatch errors "
                f"(last: {type(exc).__name__ if exc else reason})"
            )
        if disabled is not None:
            with _fused_lock:
                fused_holder["program"] = None
                fused_holder["reason"] = disabled
        if refused is not None:
            log.error(
                "fused program %s (%s) %s and is disabled for this "
                "closure; batches take the staged loop: %s",
                prog.fingerprint, prog.pspec.descriptor, disabled, refused,
            )
            return
        log.warning(
            "fused dispatch degraded to the staged loop (%s%s)%s",
            reason,
            "" if exc is None else f": {type(exc).__name__}: {exc}",
            " — fused program disabled for this closure" if disabled
            else "",
        )

    def _explain_gate(m: int, led) -> bool:
        """The shed/deadline gates shared by the staged sweep and the
        fused in-graph lanes; False = attributions degrade for this batch
        (typed and counted — scores are never affected)."""
        # shed tier 1 (serving/shedding.py): explain work is the FIRST
        # casualty of overload — cheaper to drop than detail spans, drift
        # windows, or admissions
        if _sshed.explain_shed():
            led.count_shed(m)
            _tm.REGISTRY.counter("tptpu_serve_explain_shed_total").inc(m)
            return False
        # deadline accounting: the explain family has its own p95 in the
        # serve-latency histograms; a request whose remaining budget
        # cannot cover it keeps its SCORES and drops the explanations —
        # a soft skip, unlike the hard stage-family checkpoints
        bgt = _sdl.current()
        if bgt is not None:
            required = _sdl.family_p95("explain")
            remaining = bgt.remaining()
            if remaining <= 0.0 or remaining < required:
                led.count_deadline_skip()
                _tm.REGISTRY.counter(
                    "tptpu_serve_explain_deadline_skips_total"
                ).inc()
                _tevents.emit(
                    "explain_deadline_skip",
                    remainingMs=round(remaining * 1e3, 3),
                    requiredMs=round(required * 1e3, 3),
                )
                return False
        return True

    def _run_explain(
        cols: dict[str, Any],
        m: int,
        k: int,
        dead: set,
        fam: dict[str, float] | None,
    ) -> list[dict[str, float]] | None:
        """Batched LOCO over the already-assembled feature plane: per-row
        top-k attribution maps for the ``m`` live rows, or ``None`` when
        explain degraded (shed under load, skipped on a spent deadline
        budget, or the predictor/plane is dead this batch). Explain work
        is pure observability — it NEVER fails scoring; any degradation
        is typed and counted."""
        led = _attr_ledger.stats()
        if _explain_model is None:
            raise ValueError(
                "explain=k requires a fitted predictor stage in the "
                "scoring plan"
            )
        if (
            _explain_model.output_name in dead
            or _explain_vec in dead
            or _explain_vec not in cols
        ):
            return None  # no healthy plane/prediction to explain against
        if not _explain_gate(m, led):
            return None
        # explain is pure observability: from here on ANY failure (an
        # allocation error on the lane plane, an unexpected predict
        # error) degrades to attributions=None and a counter — it must
        # never discard the batch's already-rendered scores
        try:
            ts = _tspans.clock()
            vec = cols[_explain_vec]
            x = np.asarray(vec.values, dtype=np.float32)
            # one-shot atomic publish of (groups, names): concurrent
            # service workers racing the first sweep must never observe
            # the pair half-built
            resolved = _explain_state.get("resolved")
            if resolved is None:
                groups = _loco.column_groups(
                    getattr(vec, "metadata", None), x.shape[1]
                )
                resolved = _explain_state["resolved"] = (
                    groups, [name for name, _ in groups]
                )
            groups, names = resolved
            pcol = cols[_explain_model.output_name]
            prob = getattr(pcol, "probability", None)
            base_prob = None if prob is None else np.asarray(prob)
            # regression predictions track the prediction itself
            # (PredictionColumn carries `prediction`, [N] float64)
            base_pred = (
                np.asarray(pcol.prediction) if base_prob is None else None
            )
            diffs, info = _loco.explain_batch(
                _explain_model, x, groups,
                base_prob=base_prob, base_pred=base_pred,
            )
            diffs = diffs[:m]
            maps, hits = _loco.top_k_maps(diffs, names, k)
            dur = _tspans.clock() - ts
            led.record_explain(
                m, dur, lanes=info["lanes"], deduped=info["deduped"],
                padded=info["padded"],
            )
            led.record_groups(names, diffs, hits)
            _tm.REGISTRY.counter("tptpu_serve_explain_rows_total").inc(m)
            # attribution drift observes the sweep unless the drift shed
            # tier engaged (monitoring yields before scoring does)
            if attribution_drift.enabled and not _sshed.drift_shed():
                attribution_drift.observe(names, diffs)
            if fam is not None:
                # the explain family rides record_serve_batch like the
                # other stage families — its histogram feeds the deadline
                # p95 above
                fam["explain"] = fam.get("explain", 0.0) + dur
                _tspans.record_span(
                    "serve/explain", ts, dur, rows=m, lanes=len(names)
                )
            return maps
        except Exception as e:
            led.count_error()
            _tm.REGISTRY.counter("tptpu_serve_explain_errors_total").inc()
            log.warning(
                "explain sweep failed (%s: %s) — scores kept, "
                "attributions degraded to None", type(e).__name__, e,
            )
            return None

    def _guarded(t, col, num_rows, count=True):
        """Per-stage output: fault-injection hook, then the NaN/Inf guard
        (default scope guards result-feature outputs only, so intermediate
        columns match batch WorkflowModel.score bit for bit; ``num_rows``
        keeps bucket-padding replicas out of the degradation counters;
        ``count=False`` for isolation re-runs, whose degradation the
        primary run already counted)."""
        fault_plan = faults.active()
        if fault_plan is not None:
            corrupted = fault_plan.on_stage_output(t, col)
            if corrupted is not None:
                col = corrupted
        return guard.apply(
            t, col,
            is_result=t.output_name in result_name_set,
            num_rows=num_rows,
            count=count,
        )

    def _fused_explain_request(prog, b: int, n: int) -> dict | None:
        """Resolve column groups and build the in-graph lane masks for a
        fused ``explain=k`` batch, honoring the shared shed/deadline
        gates plus the lane budget (the fused sweep is ONE dispatch — a
        sweep that cannot fit degrades attributions, never scores)."""
        led = _attr_ledger.stats()
        if not _explain_gate(n, led):
            return None
        resolved = _explain_state.get("resolved")
        if resolved is None:
            groups = _loco.column_groups(
                prog.predictor_input_meta, prog.width
            )
            resolved = _explain_state["resolved"] = (
                groups, [nm for nm, _ in groups]
            )
        groups, names = resolved
        from ..compiler.bucketing import lane_bucket

        kb = lane_bucket(len(groups))
        if (kb + 1) * b * max(1, prog.width) > _loco._lane_budget():
            led.count_budget_skip()
            _tevents.emit(
                "explain_budget_skip",
                lanes=kb, rows=b, width=prog.width,
            )
            return None
        return {
            "masks": _loco.group_masks(groups, prog.width, lanes=kb),
            "groups": groups, "names": names,
            "kb": kb, "pad": kb - len(groups), "seconds": 0.0,
        }

    def _dispatch_fused(
        prog, cols, b: int, n: int, explain_k: int,
        fam_seconds, runinfo,
    ) -> bool:
        """The whole fused segment as ONE donated dispatch: ingest codecs
        up, predictor core (plus in-graph explain lanes) down, host
        epilogue shared with the staged path. Returns True when the batch
        committed; any raise degrades to the staged loop (counted by the
        caller)."""
        lane_state = None
        lane_masks = None
        if explain_k:
            lane_state = _fused_explain_request(prog, b, n)
            if lane_state is not None:
                lane_masks = lane_state["masks"]
        ts = _tspans.clock()
        core, lane_core, info = prog.run(cols, b, n, lane_masks)
        pred, prob, raw = prog.epilogue(core)
        pcol = PredictionColumn(
            Prediction,
            np.asarray(pred, dtype=np.float64),
            None if prob is None else np.asarray(prob, dtype=np.float64),
            None if raw is None else np.asarray(raw, dtype=np.float64),
        )
        cols[prog.predictor.output_name] = _guarded(
            prog.predictor, pcol, n
        )
        _count_fused_dispatch()
        dur = _tspans.clock() - ts
        if fam_seconds is not None:
            fam_seconds["dispatch"] = (
                fam_seconds.get("dispatch", 0.0) + dur
            )
            if _tspans.stage_detail(n):
                _tspans.record_span(
                    "serve/fused", ts, dur, rows=n, lanes=info["lanes"]
                )
        if runinfo is not None:
            runinfo["fused"] = True
            if lane_state is not None and lane_core is not None:
                # lane scores tracked against each row's base class —
                # pure observability: a failure here degrades the
                # attributions, never the already-rendered scores
                try:
                    t2 = _tspans.clock()
                    lane_pred, lane_prob, _ = prog.epilogue(lane_core)
                    base, base_class = _loco.base_from_arrays(prob, pred)
                    scores = _loco.scores_from_outputs(
                        lane_pred, lane_prob, base_class,
                        lane_state["kb"], b,
                    )
                    diffs = (base[None, :] - scores).T
                    runinfo["fused_diffs"] = np.ascontiguousarray(
                        diffs[:, : len(lane_state["groups"])]
                    )
                    # only the MARGINAL host cost (lane epilogue) — the
                    # dispatch itself is already charged to the dispatch
                    # family above; double-charging it here would inflate
                    # the explain family p95 the deadline gate budgets
                    lane_state["seconds"] = _tspans.clock() - t2
                    runinfo["fused_lane_state"] = lane_state
                except Exception as e:
                    _attr_ledger.stats().count_error()
                    _tm.REGISTRY.counter(
                        "tptpu_serve_explain_errors_total"
                    ).inc()
                    log.warning(
                        "fused explain lanes failed (%s: %s) — scores "
                        "kept, attributions degraded to None",
                        type(e).__name__, e,
                    )
        return True

    def _finish_fused_explain(
        runinfo: dict, m: int, k: int, fam: dict[str, float] | None
    ) -> list[dict[str, float]] | None:
        """Ledger/drift/top-k bookkeeping for an explain sweep that rode
        the fused dispatch — mirrors ``_run_explain``'s tail exactly so
        the two paths share counters and semantics."""
        led = _attr_ledger.stats()
        state = runinfo.get("fused_lane_state")
        diffs = runinfo.get("fused_diffs")
        if state is None or diffs is None:
            return None
        try:
            from ..compiler import stats as cstats

            ts = _tspans.clock()
            names = state["names"]
            diffs = diffs[:m]
            maps, hits = _loco.top_k_maps(diffs, names, k)
            cstats.stats().record_sweep(
                lanes=len(state["groups"]), padded=state["pad"]
            )
            led.record_explain(
                m, state["seconds"] + (_tspans.clock() - ts),
                lanes=state["kb"], deduped=0, padded=state["pad"],
            )
            led.record_groups(names, diffs, hits)
            _tm.REGISTRY.counter("tptpu_serve_explain_rows_total").inc(m)
            if attribution_drift.enabled and not _sshed.drift_shed():
                attribution_drift.observe(names, diffs)
            if fam is not None:
                fam["explain"] = fam.get("explain", 0.0) + state["seconds"]
                _tspans.record_span(
                    "serve/explain", ts, state["seconds"], rows=m,
                    lanes=len(names),
                )
            return maps
        except Exception as e:
            led.count_error()
            _tm.REGISTRY.counter("tptpu_serve_explain_errors_total").inc()
            log.warning(
                "fused explain post-processing failed (%s: %s) — scores "
                "kept, attributions degraded to None",
                type(e).__name__, e,
            )
            return None

    def _run_plan(
        cols: dict[str, Any],
        b: int,
        n: int,
        row_indices: tuple[int, ...] | None,
        breaker_mode: str = "active",
        skip: frozenset = frozenset(),
        fam_seconds: dict[str, float] | None = None,
        explain_k: int = 0,
        runinfo: dict | None = None,
    ) -> tuple[set, list, dict]:
        """Execute the stage plan over already-built raw columns, with
        per-stage fault isolation. Returns ``(dead, failures, cause)``:
        ``dead`` holds output names not produced (failed, short-circuited
        by an open breaker, or downstream of either), ``failures`` the
        ``(stage, exception)`` pairs from this run, and ``cause`` maps each
        dead name to ``"failure"`` or ``"short_circuit"`` (short-circuit
        wins on mixed ancestry so recovery re-runs never bypass an open
        breaker). ``breaker_mode="active"`` gates and records; ``"observe"``
        (the isolation re-runs) touches no breaker — it skips the stages in
        ``skip``, the snapshot of breakers already open BEFORE the primary
        run, so a pre-existing short circuit is honored while the stage
        whose fresh failure is being isolated can still be probed.
        ``ScoreGuardError``/``SchemaViolationError`` are explicit
        escalations and propagate.

        Primary active runs above the host-predict cutoff first try the
        FUSED program (one donated dispatch for members + combiner +
        gathers + predict); a missing/ineligible program or a dispatch
        error degrades to the staged loop below, counted and audited
        (TPX008). Re-runs (``breaker_mode="observe"``), fault-plan
        batches, and host-predict-size batches always run staged."""
        fp = faults.active()
        dead: set[str] = set()
        failures: list[tuple[Any, Exception]] = []
        cause: dict[str, str] = {}
        prog = None
        if (
            breaker_mode == "active" and not skip and fp is None
            and b > _device_predict_min
        ):
            prog = _fused_program()
            if prog is None and fused_holder["built"]:
                # the batch was fused-eligible but the plan never
                # admitted a program: count it per-reason (leg (c)'s
                # coverage gain is exactly this sub-map shrinking) without
                # touching the degraded-at-dispatch fusedFallbacks counter
                why = _fused_reason()
                if why is not None and why != "TPTPU_FUSED=0":
                    from ..compiler import stats as cstats

                    with _fused_lock:
                        fused_counters["fallbackReasons"]["unfuseable"] = (
                            fused_counters["fallbackReasons"].get(
                                "unfuseable", 0
                            ) + 1
                        )
                    cstats.stats().record_unfused_batch("unfuseable")
            if prog is not None and any(
                br.state != "closed"
                for nm, br in breakers.items() if nm in prog.covered
            ):
                # a not-closed covered breaker routes the batch staged:
                # an open one must never be bypassed, and a recovery-due
                # one needs the staged loop to run its half-open probe —
                # the fused path never calls allow()/record_success, so
                # dispatching over it would wedge the breaker open
                prog = None
        with fusion.batch(b):
            if prog is not None:
                _plan_loop(
                    cols, b, n, row_indices, breaker_mode, skip,
                    dead, failures, cause, fp, fam_seconds,
                    stages=prog.prefix,
                )
                done = False
                if not dead and not failures:
                    # same deadline gate as the staged predictor boundary
                    # — OUTSIDE the fallback try, so a typed
                    # DeadlineExceeded propagates instead of counting as
                    # a fused failure
                    _sdl.checkpoint("dispatch")
                    try:
                        done = _dispatch_fused(
                            prog, cols, b, n, explain_k, fam_seconds,
                            runinfo,
                        )
                    except (ScoreGuardError, SchemaViolationError):
                        raise  # explicit escalations stay escalations
                    except Exception as e:
                        _count_fused_fallback("dispatch_error", e, b)
                else:
                    _count_fused_fallback("prefix_degraded")
                if done:
                    return dead, failures, cause
                # counted fail-soft seam: the batch degrades to today's
                # staged loop over the fused segment's stages
                _plan_loop(
                    cols, b, n, row_indices, breaker_mode, skip,
                    dead, failures, cause, fp, fam_seconds,
                    stages=prog.fused_stages,
                )
            else:
                _plan_loop(
                    cols, b, n, row_indices, breaker_mode, skip,
                    dead, failures, cause, fp, fam_seconds,
                )
        return dead, failures, cause

    def _plan_loop(
        cols, b, n, row_indices, breaker_mode, skip,
        dead, failures, cause, fp, fam_seconds=None, stages=None,
    ) -> None:
        """The stage loop of ``_run_plan`` (split out so the fusion batch
        context brackets exactly one plan execution). ``fam_seconds``
        (primary runs only) accumulates per-stage-family seconds —
        ``featurize`` for host transform stages, ``dispatch`` for fitted
        predictors — feeding the serve-latency histograms; per-stage
        detail spans engage above the TPTPU_TRACE_STAGE_ROWS floor.
        ``stages`` restricts the walk to a sub-plan (the fused path's
        host prefix, or its staged continuation after a fallback)."""
        detail = fam_seconds is not None and _tspans.stage_detail(n)
        for t in (plan if stages is None else stages):
            if any(nm in dead for nm in t.input_names):
                dead.add(t.output_name)
                up = {cause.get(nm) for nm in t.input_names if nm in dead}
                cause[t.output_name] = (
                    "short_circuit" if "short_circuit" in up else "failure"
                )
                continue
            # deadline gate at the dispatch family boundary: a request
            # whose remaining budget can't cover the predictor's p95 is
            # rejected HERE, before the expensive dispatch — the raise is
            # outside the stage try, so it propagates as a typed
            # DeadlineExceeded instead of counting as a stage failure.
            # It must also run BEFORE br.allow(): allow() in half-open
            # claims the single probe slot, and a raise between the claim
            # and record_success/record_failure would leak it, wedging
            # the breaker half-open forever
            if isinstance(t, PredictorModel):
                _sdl.checkpoint("dispatch")
            br = None
            if breaker is not None:
                if breaker_mode == "active":
                    br = breakers.get(t.output_name)
                    if br is None:
                        # setdefault: two service workers racing the first
                        # execution of a stage must share ONE breaker, not
                        # silently drop one of two
                        br = breakers.setdefault(
                            t.output_name,
                            CircuitBreaker(t.output_name, breaker),
                        )
                    if not br.allow():
                        dead.add(t.output_name)
                        cause[t.output_name] = "short_circuit"
                        continue
                elif t.output_name in skip:
                    dead.add(t.output_name)
                    cause[t.output_name] = "short_circuit"
                    continue
            try:
                if fp is not None:
                    fp.on_stage_transform(t, row_indices)
                t0 = breaker.clock() if br is not None else 0.0
                ts = _tspans.clock() if fam_seconds is not None else 0.0
                col = t.transform_columns(
                    *[cols[nm] for nm in t.input_names], num_rows=b
                )
                # slow-stage chaos: simulated extra seconds ride the
                # breaker-deadline elapsed time, the stage-family latency,
                # and the active request budget — no real sleep anywhere
                extra = fp.on_stage_duration(t) if fp is not None else 0.0
                if extra:
                    _sdl.consume(extra)
                elapsed = (
                    breaker.clock() - t0 + extra if br is not None else 0.0
                )
                if fam_seconds is not None:
                    tdur = _tspans.clock() - ts + extra
                    fam = (
                        "dispatch" if isinstance(t, PredictorModel)
                        else "featurize"
                    )
                    fam_seconds[fam] = fam_seconds.get(fam, 0.0) + tdur
                    if detail:
                        _tspans.record_span(
                            f"serve/stage/{type(t).__name__}", ts, tdur,
                            rows=n,
                        )
                cols[t.output_name] = _guarded(
                    t, col, n, count=breaker_mode == "active"
                )
                if (
                    t.output_name in _predictor_feeds
                    and b > _device_predict_min
                ):
                    vals = getattr(cols[t.output_name], "values", None)
                    if (
                        vals is not None
                        and getattr(vals, "dtype", None) == np.float32
                    ):
                        from ..compiler.dispatch import prefetch_f32

                        prefetch_f32(vals)
            except (ScoreGuardError, SchemaViolationError):
                # explicit escalations propagate — but a half-open probe
                # claimed by allow() above must be released on the way
                # out, or the breaker wedges half-open with no probe to
                # ever report back
                if br is not None:
                    br.release_probe()
                raise
            except Exception as e:
                if br is not None:
                    br.record_failure()
                if raise_on_stage_error:
                    raise  # isolation="raise": fail-fast, breaker recorded
                dead.add(t.output_name)
                cause[t.output_name] = "failure"
                failures.append((t, e))
                log.warning(
                    "stage %s failed at score time (%s: %s)",
                    t.output_name, type(e).__name__, e,
                )
                continue
            if br is not None:
                if breaker.deadline is not None and elapsed > breaker.deadline:
                    br.record_failure(overrun=True)
                else:
                    br.record_success()

    def _raw_columns(
        prepared: list[dict[str, Any] | None], n: int, b: int
    ) -> dict[str, Any]:
        """Raw columns from validated rows; quarantined slots (None) become
        all-missing rows so batch shape stays stable."""
        cols: dict[str, Any] = {}
        for f in raw_features:
            vals = [None if p is None else p.get(f.name) for p in prepared]
            if f.is_response and all(v is None for v in vals):
                vals = [0] * n  # score-time null labels
            if b > n:
                # pad with copies of the first row: valid for every column
                # type (incl. non-nullable RealNN); padded outputs are
                # sliced off below
                vals = vals + [vals[0]] * (b - n)
            cols[f.name] = column_from_values(f.ftype, vals)
        return cols

    # ---- default predictions: the all-missing row scored once, plainly
    # (no fault hooks, guards, or breakers — defaults must stay
    # deterministic even under an installed FaultPlan). The memo lock
    # keeps concurrent service workers from computing (and potentially
    # half-publishing) the neutral row twice.
    _neutral: dict[str, Any] = {}
    _neutral_lock = threading.Lock()

    def _neutral_columns() -> dict[str, Any]:
        with _neutral_lock:
            return _neutral_columns_locked()

    def _neutral_columns_locked() -> dict[str, Any]:
        if "cols" not in _neutral:
            cols = {
                f.name: column_from_values(
                    f.ftype, [0] if f.is_response else [None]
                )
                for f in raw_features
            }
            dead: set[str] = set()
            for t in plan:
                if any(nm in dead for nm in t.input_names):
                    dead.add(t.output_name)
                    continue
                try:
                    col = t.transform_columns(
                        *[cols[nm] for nm in t.input_names], num_rows=1
                    )
                    # the default prediction must honor the guard too — a
                    # NaN neutral score would otherwise fan out to every
                    # quarantined row unsanitized (no fault hooks, no
                    # counting; guard 'raise' mode lands in the dead set)
                    cols[t.output_name] = guard.apply(
                        t, col,
                        is_result=t.output_name in result_name_set,
                        num_rows=1, count=False,
                    )
                except Exception:
                    dead.add(t.output_name)
            _neutral["cols"] = {
                name: None if name in dead or name not in cols else cols[name]
                for name in result_names
            }
        return _neutral["cols"]

    def _default_value(name: str) -> Any:
        with _neutral_lock:
            vals = _neutral.get("values")
            if vals is None:
                vals = _neutral["values"] = {
                    nm: None if col is None else col.to_list()[0]
                    for nm, col in _neutral_columns_locked().items()
                }
        v = vals[name]
        # rows must not alias one shared mutable default (Prediction maps)
        if isinstance(v, dict):
            return dict(v)
        if isinstance(v, list):
            return list(v)
        return v

    def _default_column(name: str, n: int) -> Any:
        col = _neutral_columns()[name]
        if col is not None:
            return col.take(np.zeros(n, dtype=np.int64))
        return empty_like(result_ftypes[name], n)

    def _prepare_rows(
        rows: list[dict[str, Any]],
    ) -> tuple[list[dict[str, Any] | None], dict[int, list]]:
        """Fault hook → schema validation, per row. Returns the sanitized
        rows (None = quarantined) and the quarantine reasons by row index.
        (Drift observes the BUILT raw columns afterwards — one vectorized
        bulk merge per feature instead of a per-row histogram update.)"""
        fp = faults.active()
        if fp is not None:
            rows = list(rows)
            for i, row in enumerate(rows):
                corrupted = fp.on_score_row(row, i)
                if corrupted is not None:
                    rows[i] = corrupted
        prepared: list[dict[str, Any] | None] = []
        invalid: dict[int, list] = {}
        if sentinel is None:
            return list(rows), invalid
        # bulk validation: a type census per column proves clean batches
        # clean in O(fields) array passes; only suspicious rows re-run the
        # exact per-row check (identical counters/coercions/raise order)
        for i, (clean, reasons) in enumerate(sentinel.check_rows(rows)):
            if reasons:
                invalid[i] = reasons
                prepared.append(None)
            else:
                prepared.append(clean)
        return prepared, invalid

    def _pre_open_snapshot() -> frozenset:
        """Output names whose breaker is short-circuiting RIGHT NOW — taken
        before a primary run so the isolation pass can honor pre-existing
        open breakers without being blinded by ones the failure under
        isolation just opened."""
        return frozenset(
            nm for nm, br in breakers.items() if br.would_short_circuit()
        )

    def _bisect_rows(
        indices, build_cols, on_ok, on_poisoned, skip, budget=None
    ) -> None:
        """Binary-search the poisoning rows after a batch-level stage
        failure: run the plan on half-batches, splitting only the failing
        halves, down to single rows — O(k log n) plan executions for k bad
        rows instead of n single-row re-runs. Subsets are visited left to
        right, so callbacks fire in original row order. Breakers are never
        touched; stages in ``skip`` (open before the primary run) stay
        skipped. The re-run ``budget`` bounds the blowup when a stage
        fails DETERMINISTICALLY for every row (a misdeployed model must
        not multiply serving latency by the batch size): once exhausted,
        remaining failing subsets are quarantined wholesale."""
        if budget is None:
            budget = {"left": 16 + 4 * max(1, len(indices)).bit_length()}
        m = len(indices)
        bb = _bucket(m)
        cols2 = build_cols(indices, bb)
        budget["left"] -= 1
        _, fails2, _ = _run_plan(
            cols2, bb, m, tuple(indices), breaker_mode="observe", skip=skip
        )
        if not fails2:
            on_ok(indices, cols2, m)
            return
        t, e = fails2[0]
        if m == 1:
            on_poisoned(indices[0], t, e)
            return
        if budget["left"] <= 0:
            log.warning(
                "isolation budget exhausted: quarantining %d rows "
                "wholesale after persistent failure of '%s'",
                m, t.output_name,
            )
            for i in indices:
                on_poisoned(i, t, e)
            return
        mid = m // 2
        _bisect_rows(indices[:mid], build_cols, on_ok, on_poisoned, skip, budget)
        _bisect_rows(indices[mid:], build_cols, on_ok, on_poisoned, skip, budget)

    def score_batch(
        rows: list[dict[str, Any]], explain: int = 0
    ) -> list[dict[str, Any]]:
        n = len(rows)
        explain = int(explain or 0)
        if explain < 0:
            raise ValueError(f"explain must be >= 0, got {explain}")
        if n == 0:
            return []
        # serve-path telemetry: a handful of clock reads per batch
        # (sentinel → featurize → dispatch → download family seconds),
        # recorded in one record_serve_batch call at the end
        tel = _tspans.enabled()
        started = _tspans.clock() if tel else 0.0
        fam: dict[str, float] = {}
        qlog.start_batch()
        # deadline gates (serving/deadline.py): each stage-family boundary
        # rejects a request whose remaining budget can't cover that
        # family's p95 — near-free no-ops without an active budget
        _sdl.checkpoint("sentinel")
        prepared, invalid = _prepare_rows(rows)
        if tel:
            fam["sentinel"] = _tspans.clock() - started
        _sdl.checkpoint("featurize")
        # quarantined rows are COMPACTED OUT before the plan runs: a bad
        # row must never reach a stage (an all-missing placeholder could
        # still poison one and feed the breaker), so only survivors score
        survivors = [i for i in range(n) if i not in invalid]
        out: list[dict[str, Any]] = [{} for _ in range(n)]
        m = len(survivors)
        degraded: list[str] = []
        fail_names: list[str] = []
        failures: list = []
        poisoned: dict[int, tuple[str, Exception]] = {}
        attr_maps: list[dict[str, float]] | None = None
        runinfo: dict[str, Any] = {}
        if m:
            b = _bucket(m)
            tc = _tspans.clock() if tel else 0.0
            cols = _raw_columns([prepared[i] for i in survivors], m, b)
            if drift_sentinel.enabled and not _sshed.drift_shed():
                # observed post codec (typed, coerced values), one
                # vectorized bulk merge per feature; quarantined rows never
                # reach the plan, so they are not part of the window.
                # Skipped at shed tier >= 3 — drift observation is
                # monitoring, and monitoring yields before scoring does
                drift_sentinel.observe_columns(cols, m)
            if tel:
                # the row→column codec counts as featurize time; the plan
                # loop adds the per-stage featurize/dispatch seconds on top
                fam["featurize"] = _tspans.clock() - tc
            pre_open = _pre_open_snapshot()
            dead, failures, cause = _run_plan(
                cols, b, m, tuple(survivors),
                fam_seconds=fam if tel else None,
                explain_k=explain, runinfo=runinfo,
            )
            degraded = [nm for nm in result_names if nm in dead]
            td = _tspans.clock() if tel else 0.0
            for name in result_names:
                if name in degraded:
                    continue
                # to_list renders Prediction columns as reference-keyed maps
                rendered = cols[name].to_list()
                for j, i in enumerate(survivors):
                    out[i][name] = rendered[j]
            if tel:
                fam["download"] = _tspans.clock() - td
            if not runinfo.get("fused"):
                # fused batches counted their real download inside the
                # dispatch — the staged render convention must not
                # double-count it
                _census_downloads(b, m, degraded, fam.get("download", 0.0))
            if explain:
                # attributions ride the batch AFTER scores render: the
                # sweep reuses the assembled feature plane and the batch's
                # own PredictionColumn as the base (no extra base
                # dispatch); fused batches already carried their lanes in
                # the single dispatch and only finish bookkeeping here
                attr_maps = (
                    _finish_fused_explain(
                        runinfo, m, explain, fam if tel else None
                    )
                    if runinfo.get("fused")
                    else _run_explain(
                        cols, m, explain, dead, fam if tel else None
                    )
                )
            # per-row isolation: a fresh stage failure bisects the
            # survivors so only the poisoning row(s) are quarantined;
            # results dead from an OPEN breaker are NOT recovered (that
            # would bypass the short circuit) — they degrade batch-wide
            fail_names = [
                nm for nm in degraded if cause.get(nm) == "failure"
            ]
            if failures and fail_names:
                if m == 1:
                    # no re-run for a single row: the batch WAS the row (a
                    # transiently-injected fault must count exactly once)
                    t, e = failures[0]
                    poisoned[survivors[0]] = (t.output_name, e)
                else:
                    def _build(idxs, bb):
                        return _raw_columns(
                            [prepared[i] for i in idxs], len(idxs), bb
                        )

                    def _ok(idxs, cols2, mm):
                        for nm in fail_names:
                            if nm not in cols2:
                                continue  # downstream of an open breaker
                            rendered = cols2[nm].to_list()
                            for j, i in enumerate(idxs):
                                out[i][nm] = rendered[j]

                    def _poison(i, t, e):
                        poisoned[i] = (t.output_name, e)

                    _bisect_rows(survivors, _build, _ok, _poison, pre_open)
        # whatever is still missing degrades to the default prediction
        for nm in degraded:
            for i in survivors:
                if nm not in out[i]:
                    out[i][nm] = _default_value(nm)
        for i, reasons in invalid.items():
            for feat, kind, reason in reasons:
                qlog.add(QuarantineRecord(i, feat, kind, reason))
            for nm in result_names:
                out[i][nm] = _default_value(nm)
        for i, (stage_name, e) in poisoned.items():
            qlog.add(QuarantineRecord(
                i, stage_name, "stage", f"{type(e).__name__}: {e}"
            ))
            for nm in result_names:
                out[i][nm] = _default_value(nm)
        if explain:
            # every row answers the explain request: a top-k map for rows
            # that were explained, None for quarantined/poisoned rows and
            # for batches whose explain work was shed or skipped
            for j, i in enumerate(survivors):
                out[i]["attributions"] = (
                    None if attr_maps is None or i in poisoned
                    else attr_maps[j]
                )
            for i in invalid:
                out[i]["attributions"] = None
        if m and b > _device_predict_min:
            # release any prefetched device buffers this batch created —
            # they must not outlive the batch and pin device memory
            from ..compiler.dispatch import clear_prefetch

            clear_prefetch()
        if tel:
            _tspans.record_serve_batch("batch", n, started, fam)
        return out

    def score_columns(dataset, explain: int = 0) -> dict[str, Any]:
        """Columnar scoring: Dataset in, ``{result_name: Column}`` out.

        The counterpart of sklearn's ``pipeline.predict(dataframe)`` — the
        input is already columnar, so the per-value row-dict codec
        (``column_from_values`` per raw feature, ``to_list`` per result) is
        skipped entirely — and with it the row-dict schema validation
        (typed columns can't carry wrong-typed values; the drift sentinel,
        breakers, and stage isolation still apply). Rows are padded to the
        same power-of-two buckets by replicating row 0; outputs are sliced
        back with ``take``. A stage failure isolates per row: poisoning
        rows get default values in the AFFECTED result columns only (the
        row-dict path quarantines the whole row). ``explain=k`` adds an
        ``"attributions"`` entry: one top-k map per row (or None when the
        sweep was shed/skipped)."""
        n = len(dataset)
        explain = int(explain or 0)
        if explain < 0:
            raise ValueError(f"explain must be >= 0, got {explain}")
        if n == 0:
            return {}
        tel = _tspans.enabled()
        started = _tspans.clock() if tel else 0.0
        fam: dict[str, float] = {}
        qlog.start_batch()
        b = _bucket(n)
        cols: dict[str, Any] = {}
        pad = None
        if b > n:
            pad = np.concatenate(
                [np.arange(n), np.zeros(b - n, dtype=np.int64)]
            )
        for f in raw_features:
            if f.name not in dataset:
                # same tolerance as the row path (r.get): absent response
                # scores with null labels, absent predictors as all-null
                fill = 0 if f.is_response else None
                cols[f.name] = column_from_values(f.ftype, [fill] * b)
                continue
            c = dataset[f.name]
            if f.is_response and _all_null(c):
                # PRESENT but all-null response: substitute the same
                # score-time null-label fill the row path uses
                # (_raw_columns) — label-dependent stages must see the
                # 0-fill on both entry points, or batch and columnar
                # scores diverge on unlabeled data
                cols[f.name] = column_from_values(f.ftype, [0] * b)
                continue
            cols[f.name] = c if pad is None else c.take(pad)
        if drift_sentinel.enabled and not _sshed.drift_shed():
            drift_sentinel.observe_columns(cols, n)
        if tel:
            # column intake (padding/take + drift observe) counts as
            # featurize time — there is no row-dict sentinel on this path
            fam["featurize"] = _tspans.clock() - started
        pre_open = _pre_open_snapshot()
        runinfo: dict[str, Any] = {}
        dead, failures, cause = _run_plan(
            cols, b, n, tuple(range(n)), fam_seconds=fam if tel else None,
            explain_k=explain, runinfo=runinfo,
        )
        td = _tspans.clock() if tel else 0.0
        keep = np.arange(n)
        degraded = [nm for nm in result_names if nm in dead]
        out = {
            name: (cols[name] if b == n else cols[name].take(keep))
            for name in result_names
            if name not in degraded
        }
        if tel:
            fam["download"] = _tspans.clock() - td
        if not runinfo.get("fused"):
            _census_downloads(b, n, degraded, fam.get("download", 0.0))
        attr_maps: list[dict[str, float]] | None = None
        if explain:
            attr_maps = (
                _finish_fused_explain(
                    runinfo, n, explain, fam if tel else None
                )
                if runinfo.get("fused")
                else _run_explain(
                    cols, n, explain, dead, fam if tel else None
                )
            )
        fail_names = [nm for nm in degraded if cause.get(nm) == "failure"]
        if failures and fail_names and n > 1:
            segments: dict[str, list] = {nm: [] for nm in fail_names}

            def _build(idxs, bb):
                arr = np.asarray(
                    list(idxs) + [idxs[0]] * (bb - len(idxs)), dtype=np.int64
                )
                return {f.name: cols[f.name].take(arr) for f in raw_features}

            def _ok(idxs, cols2, m):
                trim = np.arange(m)
                for nm in fail_names:
                    if nm not in cols2:  # downstream of an open breaker
                        segments[nm].append(_default_column(nm, m))
                        continue
                    seg = cols2[nm]
                    segments[nm].append(
                        seg if len(seg) == m else seg.take(trim)
                    )

            def _poison(i, t, e):
                qlog.add(QuarantineRecord(
                    i, t.output_name, "stage", f"{type(e).__name__}: {e}"
                ))
                for nm in fail_names:
                    segments[nm].append(_default_column(nm, 1))

            # callbacks fire in index order, so the segments concatenate
            # back into the original row order
            _bisect_rows(list(range(n)), _build, _ok, _poison, pre_open)
            for nm in fail_names:
                try:
                    out[nm] = concat_columns(segments[nm])
                except Exception:  # mixed shapes: degrade the whole column
                    out[nm] = _default_column(nm, n)
        elif failures and fail_names:  # n == 1
            t, e = failures[0]
            qlog.add(QuarantineRecord(
                0, t.output_name, "stage", f"{type(e).__name__}: {e}"
            ))
        for nm in degraded:
            if nm not in out:
                out[nm] = _default_column(nm, n)
        if explain:
            out["attributions"] = attr_maps
        if b > _device_predict_min:
            from ..compiler.dispatch import clear_prefetch

            clear_prefetch()  # see score_batch: bound buffer lifetime
        if tel:
            _tspans.record_serve_batch("columns", n, started, fam)
        return out

    def score_one(row: dict[str, Any], explain: int = 0) -> dict[str, Any]:
        # single-row scoring IS batch scoring: one shared quarantine /
        # guard / breaker / drift / explain path, pinned by parity tests
        return score_batch([row], explain=explain)[0]

    def audit(programs: bool = False) -> Any:
        """Static serving-plan audit (analysis/plan_audit.py): symbolic
        [N, width] shape propagation over this closure's stage plan, the
        per-stage host↔device transfer census, recompile-hazard and
        donation checks. Widths sharpen after the first scored batch
        (the fusion planner learns them); re-run any time — it executes
        nothing. When the fused graph is available the census reports its
        two-crossing contract (ingest up, render down) and the fused
        module joins the TPX003 donation scan; a missing/degraded fused
        path surfaces as TPX008.

        ``programs=True`` adds the compiled-program contract audit
        (analysis/program.py, TPJ0xx): the FITTED fused program traces
        over its real fit-static params (a model array folded as a jaxpr
        constant instead of a traced argument is TPJ001 — the PR-11
        structural-fingerprint contract, checked by construction), the
        banked serving programs the plan's families dispatch audit over
        their registered bucket shapes, and the jaxpr-derived per-batch
        transfer counts reconcile as the THIRD census leg against the
        static plan census (disagreement is TPJ006)."""
        from ..analysis.plan_audit import audit_serving_plan

        prog = _fused_program()
        with _fused_lock:
            counters = dict(fused_counters)
        report = audit_serving_plan(
            plan, raw_features, result_names,
            fusion=fusion, bucketed=True,
            host_predict_max=_device_predict_min,
            fused=prog,
            fused_reason=_fused_reason(),
            fused_counters=counters,
        )
        if programs:
            from ..analysis import program as _aprog
            from ..compiler import warmup as _warm

            names = set(_warm.SCORE_PROGRAMS) - {
                "fused_serve", "fused_serve_explain",
            }
            traced: dict = {}
            sub = _aprog.audit_programs(names=names, include_ast=False)
            traced.update(sub.data.pop("programs", {}))
            report.extend(sub)
            if prog is not None:
                sub = _aprog.audit_fused_program(prog)
                traced.update(sub.data.pop("programs", {}))
                report.extend(sub)
            report.data["programs"] = traced
            counts = _aprog.program_transfer_counts(plan=plan, fused=prog)
            report.extend(
                _aprog.reconcile_program_census(
                    report.data["transferCensus"], counts
                )
            )
        return report

    def metadata() -> dict[str, Any]:
        """Score-path health: guard + sentinel + quarantine + breaker +
        drift counters, one report — plus the training-side distributed
        ledger (hosts lost, failovers, reshards) so serving ops can see
        the model behind this closure finished on a degraded mesh, the
        process-wide compile-plane (compiler.stats) and featurize-plane
        (featurize.stats) ledgers, and the static plan audit
        (``analysis`` — findings + the host↔device transfer census)."""
        from ..compiler import stats as cstats
        from ..featurize import stats as fstats
        from ..telemetry.export import serving_snapshot

        try:
            analysis = audit().to_json()
        except Exception as e:  # the audit must never break monitoring
            log.debug("plan audit skipped: %s", e)
            analysis = None
        # the slow, lock-free parts first (the drift reports walk every
        # feature's/group's histogram and may emit events) — holding the
        # shared snapshot lock here would stall every scoring thread
        drift_report = drift_sentinel.report()
        attribution_drift_report = attribution_drift.report()
        breaker_stats = {nm: br.stats() for nm, br in breakers.items()}
        # then ONE consistent point-in-time read of the process ledgers:
        # their recorders serialize on the same lock, so a concurrent
        # scorer can no longer move counts between the compileStats and
        # featurizeStats reads (torn cross-ledger view)
        with _tm.snapshot_lock():
            compile_snap = cstats.snapshot()
            featurize_snap = fstats.snapshot()
            attribution_snap = _attr_ledger.snapshot()
        resolved = _explain_state.get("resolved")
        with _fused_lock:
            prog = fused_holder["program"]
            fused_snap = dict(fused_counters)
            fused_snap["fallbackReasons"] = dict(
                fused_counters["fallbackReasons"]
            )
        return {
            "analysis": analysis,
            "fused": {
                "active": prog is not None,
                "reason": _fused_reason(),
                "fingerprint": None if prog is None else prog.fingerprint,
                "quantized": (
                    prog is not None and getattr(prog, "quantized", False)
                ),
                "dispatches": fused_snap["dispatches"],
                "fallbacks": fused_snap["fallbacks"],
                "lastFallback": fused_snap["lastFallback"],
                "fallbackReasons": fused_snap["fallbackReasons"],
            },
            "compileStats": compile_snap,
            "featurizeStats": featurize_snap,
            "scoreGuard": guard.stats(),
            "sentinel": None if sentinel is None else sentinel.stats(),
            "quarantine": qlog.stats(),
            "breakers": breaker_stats,
            "drift": drift_report,
            "attributions": {
                "available": _explain_model is not None,
                "groups": None if resolved is None else resolved[1],
                "ledger": attribution_snap,
                "drift": attribution_drift_report,
            },
            "distributed": getattr(model, "dist_summary", None),
            "retrainLedger": _retrain_ledger(),
            "telemetry": serving_snapshot(),
        }

    def prime_fused() -> bool:
        """Build the fused serving program now instead of on the first
        eligible batch (the standing service calls this at start, after
        priming the fusion planner). Returns availability."""
        return _fused_program() is not None

    score_one.batch = score_batch  # type: ignore[attr-defined]
    score_one.columns = score_columns  # type: ignore[attr-defined]
    score_one.fusion = fusion  # type: ignore[attr-defined]
    score_one.prime_fused = prime_fused  # type: ignore[attr-defined]
    score_one.fused_state = fused_holder  # type: ignore[attr-defined]
    score_one.guard = guard  # type: ignore[attr-defined]
    score_one.sentinel = sentinel  # type: ignore[attr-defined]
    score_one.breakers = breakers  # type: ignore[attr-defined]
    score_one.drift = drift_sentinel  # type: ignore[attr-defined]
    score_one.quarantine = qlog  # type: ignore[attr-defined]
    score_one.attribution_drift = attribution_drift  # type: ignore[attr-defined]
    score_one.audit = audit  # type: ignore[attr-defined]
    score_one.metadata = metadata  # type: ignore[attr-defined]
    # the model keeps weak references to its live score functions so
    # summary_pretty() can report serve-side resilience counters next to
    # the train-side retry ledger
    monitors = getattr(model, "_serving_monitors", None)
    if monitors is None:
        monitors = model._serving_monitors = []  # type: ignore[attr-defined]
    monitors[:] = [r for r in monitors if r() is not None]  # prune dead refs
    monitors.append(weakref.ref(score_one))
    # process-wide serving source (telemetry exposition) tracks it too
    with _LIVE_LOCK:
        # r is a weakref deref — runs no user code, takes no locks
        _LIVE_SCORE_FNS[:] = [r for r in _LIVE_SCORE_FNS if r() is not None]  # tp: disable=TPC004
        _LIVE_SCORE_FNS.append(weakref.ref(score_one))
    return score_one
