"""Workflow + WorkflowModel: result-feature-driven training and scoring.

Reference: core/.../OpWorkflow.scala (train :347, DAG assembly :90-110,
validation :280-338) and core/.../OpWorkflowModel.scala (score :259,
summary :187-223).

The user declares result features; the workflow reconstructs the stage DAG
from lineage, materializes raw data through a reader, reserves a holdout via
the model selector's splitter (OpWorkflow.scala:380-384), fits the DAG layer
by layer, evaluates the selected model on the holdout, and returns a fitted
WorkflowModel that can score/evaluate/summarize/save.
"""
from __future__ import annotations

import logging
import os
from typing import Any, Sequence

import numpy as np

from ..dataset import Dataset
from ..features.feature import Feature
from ..readers.core import DataReader, DatasetReader
from ..selector.model_selector import ModelSelector, SelectedModel
from ..stages.base import Estimator, PipelineStage
from ..telemetry import runlog as _runlog
from ..telemetry import spans as _tspans
from ..types.columns import NumericColumn, VectorColumn
from .dag import compute_dag, raw_features_of, validate_stages
from .fit import apply_transformations_dag, fit_and_transform_dag

log = logging.getLogger(__name__)

#: one-shot latch for the summary-degradation warning (further failures
#: still count on the run ledger and the event log, just without the
#: per-call log noise)
_SUMMARY_DEGRADED_WARNED = [False]


def _report_summary_degraded(section: str, e: Exception) -> None:
    """A ``summary_pretty`` section failed to render: count it on the run
    ledger (``summaryDegraded``), land a ``summary_degraded`` event in the
    structured log, and warn ONCE per process — a broken summary section
    must be observable, not a silent debug-level swallow."""
    detail = f"{type(e).__name__}: {e}"
    try:
        from ..telemetry import events as _tevents

        _runlog.stats().bump("summaryDegraded")
        _tevents.emit("summary_degraded", section=section, error=detail)
    except Exception:  # the degradation report must not break the summary
        pass
    if not _SUMMARY_DEGRADED_WARNED[0]:
        _SUMMARY_DEGRADED_WARNED[0] = True
        log.warning(
            "summary_pretty %s section degraded (%s) — counted as "
            "summaryDegraded on the run ledger; further degradations "
            "log at debug level", section, detail,
        )
    else:
        log.debug("summary_pretty %s section skipped: %s", section, detail)


class Workflow:
    def __init__(self):
        self.result_features: tuple[Feature, ...] = ()
        self.reader: DataReader | None = None
        self._stage_overrides: dict[str, dict[str, Any]] = {}
        self._raw_feature_filter = None
        self._rff_score_reader: DataReader | None = None
        self.blocklisted_features: list[str] = []
        self._prefitted: dict[str, PipelineStage] = {}
        self._workflow_cv = False
        self._detect_sensitive = False
        self._mesh: Any = "auto"

    # ----------------------------------------------------------- configure
    def set_result_features(self, *features: Feature) -> "Workflow":
        self.result_features = tuple(features)
        return self

    def set_input_dataset(self, dataset: Dataset) -> "Workflow":
        self.reader = DatasetReader(dataset)
        return self

    def set_reader(self, reader: DataReader) -> "Workflow":
        self.reader = reader
        return self

    def set_stage_parameters(self, overrides: dict[str, dict[str, Any]]) -> "Workflow":
        """Per-stage param overrides keyed by stage uid or class name,
        applied reflectively before fit (OpWorkflow.setStageParameters,
        OpWorkflow.scala:179-201)."""
        self._stage_overrides.update(overrides)
        return self

    def with_model_stages(self, model: "WorkflowModel") -> "Workflow":
        """Warm start (OpWorkflow.withModelStages, OpWorkflow.scala:468-472):
        fitted stages from a previous model are swapped in by estimator uid,
        so only new estimators train."""
        self._prefitted.update(model.fitted)
        return self

    def with_workflow_cv(self) -> "Workflow":
        """Workflow-level cross-validation (OpWorkflow.withWorkflowCV,
        OpWorkflow.scala:403-453): label-dependent estimators upstream of the
        model selector are re-fit inside every CV fold, so their statistics
        cannot leak validation rows into candidate selection."""
        self._workflow_cv = True
        return self

    def set_parallelism(self, mesh) -> "Workflow":
        """Pin the execution mesh for train/score. Default "auto": all
        visible devices data-parallel (the reference row-partitions every
        stage by construction — FitStagesUtil.scala:96-118); on a single
        device this resolves to None and everything is plain jit. Pass None
        to force single-device execution."""
        self._mesh = mesh
        return self

    def _resolve_mesh(self):
        from ..parallel.mesh import default_execution_mesh

        return default_execution_mesh() if self._mesh == "auto" else self._mesh

    def with_sensitive_feature_detection(self) -> "Workflow":
        """Scan raw text features for personal data at train time and record
        SensitiveFeatureInformation in the model summary
        (SensitiveFeatureInformation.scala, SURVEY.md §5.5)."""
        self._detect_sensitive = True
        return self

    def with_raw_feature_filter(
        self,
        score_dataset: Dataset | None = None,
        score_reader: DataReader | None = None,
        **params: Any,
    ) -> "Workflow":
        """Attach a RawFeatureFilter (OpWorkflow.withRawFeatureFilter):
        before fitting, raw features failing fill/drift/leakage rules are
        blocklisted and the DAG is rewritten without them."""
        from ..prep.raw_feature_filter import RawFeatureFilter

        self._raw_feature_filter = RawFeatureFilter(**params)
        if score_dataset is not None:
            score_reader = DatasetReader(score_dataset)
        self._rff_score_reader = score_reader
        return self

    def _apply_blocklist(self, blocklist: list[str]) -> None:
        """DAG rewrite minus blocklisted raw features (OpWorkflow.setBlocklist,
        OpWorkflow.scala:118-167): stages lose blocklisted inputs; stages with
        no inputs left are dropped and their outputs blocklisted in turn."""
        if not blocklist:
            return
        dead = set(blocklist)
        layers = compute_dag(self.result_features)
        for layer in layers:
            for stage in layer:
                kept = tuple(
                    f for f in stage.input_features if f.name not in dead
                )
                if len(kept) == len(stage.input_features):
                    continue
                if not kept or stage.input_types is not None:
                    # variable-arity (sequence) stages shrink; fixed-arity
                    # stages cannot lose a positional input — they die and
                    # their output is blocklisted in turn
                    dead.add(stage.output_name)
                else:
                    stage.input_features = kept
        for rf in self.result_features:
            if rf.name in dead:
                raise ValueError(
                    f"RawFeatureFilter removed everything feeding result "
                    f"feature '{rf.name}'"
                )
        self.blocklisted_features = sorted(dead)

    # ----------------------------------------------------------- pre-flight
    def validate(self) -> "Report":
        """Pre-flight static analysis of the declared DAG (no data needed):
        feature-type compatibility per stage edge, response-lineage leakage
        into predictors, duplicate/orphan outputs, cycles and layer
        consistency — the eager equivalent of the reference's compile-time
        typed pipelines (analysis/preflight.py; docs/analysis.md catalogues
        the TPA codes). Returns the :class:`~transmogrifai_tpu.analysis.Report`;
        ``train()`` runs the same pass and refuses on errors."""
        from ..analysis.preflight import preflight

        return preflight(self.result_features, mode="train")

    # --------------------------------------------------------------- train
    def _stages(self, validate: bool = True) -> list[PipelineStage]:
        layers = compute_dag(self.result_features)
        if validate:
            validate_stages(layers)
        return [s for layer in layers for s in layer]

    def _apply_overrides(self, stages: Sequence[PipelineStage]) -> None:
        for stage in stages:
            for key in (stage.uid, type(stage).__name__):
                if key in self._stage_overrides:
                    stage.set_params(**self._stage_overrides[key])

    def compute_data_up_to(self, *features: Feature) -> Dataset:
        """Materialize the DAG up to the given features without running the
        full train (OpWorkflowCore.computeDataUpTo; used by the runner's
        Features run type, OpWorkflowRunner.scala:190)."""
        targets = list(features) or list(self.result_features)
        if not targets:
            raise ValueError("computeDataUpTo needs target features")
        if self.reader is None:
            raise ValueError("No input data: call set_input_dataset or set_reader")
        stages = list({s.uid: s for f in targets for s in f.parent_stages()}.values())
        self._apply_overrides(stages)
        raw = self.reader.generate_dataset(raw_features_of(targets))
        data, _ = fit_and_transform_dag(raw, targets, prefitted=self._prefitted)
        return data

    def train(
        self,
        checkpoint_dir: str | None = None,
        resume: bool = False,
        on_mesh_mismatch: str = "reshard",
        progress: Any = None,
        run_dir: str | None = None,
        stream: bool | None = None,
    ) -> "WorkflowModel":
        """Fit the DAG. With ``checkpoint_dir``, every completed layer (and
        every finished CV candidate sweep) is persisted atomically there;
        ``resume=True`` restores completed layers into the ``prefitted``
        warm-start dict so only unfinished work re-runs (docs/robustness.md).

        Checkpoints record the device topology they were written under;
        resuming on a different mesh (N→M devices, including M=1)
        reshards the saved arrays onto the current mesh by default —
        ``on_mesh_mismatch="raise"`` turns a topology change into a
        ``CheckpointMeshMismatch`` instead. Training also runs inside an
        elastic failover loop (resilience/distributed.py): a declared host
        loss (heartbeat timeout, exhausted collective retries, injected
        ``fail_host``) degrades the mesh to the surviving hosts' devices
        and re-enters the fit from the last completed layer checkpoint
        instead of aborting.

        Every train is flight-recorded (telemetry/runlog.py): per-phase
        and per-layer/fold/candidate timings, compile/featurize ledger
        deltas, the runtime host<->device transfer census, and device-
        memory high-water gauges land in a schema-versioned RunReport on
        the returned model (``model.run_report``, ``summary_json()["run"]``,
        the manifest). ``progress`` is an optional callback receiving
        phase/layer/fold pulse dicts with a live seconds-per-layer EWMA
        ETA. ``run_dir`` (default None = fall back to ``$TPTPU_RUN_DIR``;
        pass ``""`` to disable persistence even when the env var is set)
        persists the report as a ``RUN_*.json`` artifact and auto-diffs
        it against the directory's latest run, warning on TPR-coded
        regressions (``python -m transmogrifai_tpu runs --diff`` compares
        any two).

        ``stream=True`` (or automatically when the reader declares
        ``is_unbounded()``) routes ingest through the out-of-core chunked
        fit (workflow/stream.py): fit-time stats fold through streaming
        monoid aggregation chunk by chunk, the featurize pool pipelines
        chunk k+1 while chunk k reduces under a bounded in-flight window
        (``TPTPU_STREAM_INFLIGHT``), torn/corrupt chunks quarantine
        instead of folding, and with ``checkpoint_dir`` a per-chunk
        stream cursor makes a mid-ingest crash resume with < 1 chunk of
        rework. ``stream=False`` forces full materialization even for an
        unbounded reader. See docs/robustness.md "Out-of-core fit"."""
        # one trace per train: every span of this call on this thread
        # (ingest, layers, fits, the selector's sweep) shares this root
        with _tspans.span("train/run"):
            return self._train(
                checkpoint_dir, resume, on_mesh_mismatch, progress, run_dir,
                stream,
            )

    def _train(
        self, checkpoint_dir, resume, on_mesh_mismatch, progress, run_dir,
        stream,
    ) -> "WorkflowModel":
        if not self.result_features:
            raise ValueError("setResultFeatures must be called before train")
        if self.reader is None:
            raise ValueError("No input data: call set_input_dataset or set_reader")
        if resume and checkpoint_dir is None:
            raise ValueError("resume=True requires checkpoint_dir")
        if on_mesh_mismatch not in ("reshard", "raise"):
            # an unrecognized policy must not silently mean "reshard" for
            # a caller who asked to fail on topology changes
            raise ValueError(
                f"unknown on_mesh_mismatch {on_mesh_mismatch!r} "
                "(choose 'reshard' or 'raise')"
            )
        # flight recorder (telemetry/runlog.py): one RunReport per train —
        # phases/layers/folds, ledger deltas, runtime transfer census,
        # device-memory high-water, live progress/ETA. Purely
        # observability: every recorder path is exception-contained.
        recorder = _runlog.RunRecorder(progress=progress).start()
        # pre-flight static analysis: refuse a provably-broken DAG (type
        # clash, leakage, cycle, ...) BEFORE reading any data — the eager
        # stand-in for the reference's compile-time typed pipelines. The
        # report (incl. surviving warnings) rides the model summary.
        preflight_report = self.validate().raise_if_errors()
        # preflight already covered the structural checks — skip the
        # second validate_stages pass inside _stages()
        stages = self._stages(validate=False)
        self._apply_overrides(stages)
        # async warmup (compiler.warmup): load the banked executables the
        # model families in THIS DAG will need on a background thread, so
        # program acquisition overlaps the reader/feature phases below
        # instead of serializing in front of the first fit dispatch
        from ..compiler import cache as _ccache
        from ..compiler import warmup as _warmup
        from ..featurize import stats as _fstats

        _ccache.enable_persistent_cache()
        _warmup.start_warmup(_warmup.train_programs(stages), scope="train")
        # featurize-plane ledger for THIS train (rows/s per stage, pool
        # utilization, interning + fallback-kernel counts) — the delta
        # over the whole ingest lands in the selector summary
        featurize_baseline = _fstats.snapshot()
        selectors = [s for s in stages if isinstance(s, ModelSelector)]
        if len(selectors) > 1:
            raise ValueError(
                "Only one ModelSelector is allowed per workflow "
                f"(found {len(selectors)})"  # FitStagesUtil.cutDAG:310 parity
            )
        selector = selectors[0] if selectors else None

        raw_features = raw_features_of(self.result_features)
        use_stream = (
            stream if stream is not None else self.reader.is_unbounded()
        )
        ckpt = None
        stream_summary = None
        if use_stream:
            if not hasattr(self.reader, "stream_batches"):
                raise ValueError(
                    "stream=True requires a chunked reader exposing "
                    "stream_batches() (readers/streaming.py); "
                    f"{type(self.reader).__name__} does not"
                )
            if checkpoint_dir is not None:
                # created BEFORE ingest: the stream cursor persists per
                # chunk so a mid-ingest crash resumes instead of
                # re-ingesting; a fresh train wipes stale state once here
                from ..resilience.checkpoint import CheckpointManager

                ckpt = CheckpointManager(checkpoint_dir)
                if not resume:
                    ckpt.clear()
            from .stream import stream_ingest

            with recorder.phase("ingest"):
                with _tspans.span(
                    "train/ingest", features=len(raw_features), stream=1
                ):
                    raw, stream_summary = stream_ingest(
                        self.reader, raw_features,
                        recorder=recorder, checkpoint=ckpt, resume=resume,
                    )
            recorder.set_phase_rows("ingest", stream_summary["rowsSeen"])
            recorder.set_stream_summary(stream_summary)
            log.info(
                "Streamed raw data: %d rows over %d chunks "
                "(%d quarantined), %d buffered for fit",
                stream_summary["rowsSeen"], stream_summary["chunksDone"],
                stream_summary["quarantinedTotal"], raw.num_rows,
            )
        else:
            with recorder.phase("ingest"):
                with _tspans.span(
                    "train/ingest", features=len(raw_features)
                ):
                    raw = self.reader.generate_dataset(raw_features)
            recorder.set_phase_rows("ingest", raw.num_rows)
        if raw.num_rows == 0:
            raise ValueError("Input dataset cannot be empty")
        log.info("Generated raw data: %d rows, %d features", raw.num_rows, len(raw_features))

        sensitive_info = None
        if self._detect_sensitive:
            from ..prep.sensitive import detect_sensitive_features

            sensitive_info = [
                s.to_json()
                for s in detect_sensitive_features(raw, raw_features)
            ]
            if sensitive_info:
                log.info("Sensitive features detected: %s", sensitive_info)

        rff_results = None
        if self._raw_feature_filter is not None:
            label_names = [f.name for f in raw_features if f.is_response]
            score_data = (
                self._rff_score_reader.generate_dataset(
                    [f for f in raw_features if not f.is_response]
                )
                if self._rff_score_reader is not None
                else None
            )
            blocklist = self._raw_feature_filter.compute_exclusions(
                raw,
                raw_features,
                score=score_data,
                label_name=label_names[0] if label_names else None,
            )
            rff_results = self._raw_feature_filter.results
            if blocklist:
                log.info("RawFeatureFilter blocklisted: %s", blocklist)
                self._apply_blocklist(blocklist)
                raw_features = raw_features_of(self.result_features)
                raw = raw.drop(blocklist)
                validate_stages(compute_dag(self.result_features))

        train_data, holdout_data = raw, None
        if selector is not None and selector.splitter is not None:
            train_idx, holdout_idx = selector.splitter.split(raw.num_rows)
            if len(holdout_idx):
                train_data = raw.take(train_idx)
                holdout_data = raw.take(holdout_idx)

        # checkpoint/resume (resilience/): completed layers restore into the
        # prefitted warm-start dict; the selector checkpoints CV candidates
        signature = None
        dag_layers = None
        base_prefitted = dict(self._prefitted)
        if checkpoint_dir is not None:
            from ..resilience.checkpoint import (
                CheckpointManager,
                dag_signature,
                dataset_fingerprint,
            )

            fresh_ckpt = ckpt is None  # stream mode created + cleared it
            if fresh_ckpt:
                ckpt = CheckpointManager(checkpoint_dir)
            dag_layers = compute_dag(self.result_features)
            signature = dag_signature(
                dag_layers, dataset_fingerprint(train_data)
            )
            if fresh_ckpt and not resume:
                # fresh train: stale entries from a previous run in the
                # same dir must never mix into a later crash + resume
                ckpt.clear()
            if selector is not None:
                selector._checkpoint = ckpt
                # candidate RESULTS are only consumed on an explicit resume;
                # a fresh train always re-sweeps (and overwrites the files)
                selector._checkpoint_resume = resume

        # every estimator fit below runs under the ambient execution mesh:
        # tree fits shard_map rows with psum'd histograms, solver fits ride
        # GSPMD row sharding; None (single device) = plain jit. The
        # FailoverController wraps the whole fit phase: on a declared host
        # loss the mesh degrades to the surviving hosts' devices and the
        # fit re-enters from the last completed layer checkpoint.
        import contextlib

        from ..parallel.mesh import use_execution_mesh
        from ..resilience import distributed
        from ..resilience.distributed import HostLostError

        controller = distributed.active_controller()
        own_controller = controller is None
        if own_controller:
            controller = distributed.FailoverController()
        controller.bind(self._resolve_mesh(), checkpoint=ckpt)

        def load_checkpointed_layers() -> dict[str, Any]:
            pf = dict(base_prefitted)
            if ckpt is not None and (
                resume or controller.counters["failovers"]
            ):
                # the strict policy applies to the user-initiated resume
                # only: after a failover THIS run changed the mesh on
                # purpose, so the reload must reshard, not crash
                policy = (
                    "reshard" if controller.counters["failovers"]
                    else on_mesh_mismatch
                )
                pf.update(ckpt.load_layers(
                    signature, dag_layers,
                    mesh_info=distributed.mesh_fingerprint(controller.mesh),
                    mesh_policy=policy,
                ))
                controller.counters["reshardEvents"] += ckpt.reshard_events
            return pf

        # the fit phase runs with the recorder INSTALLED so the layer /
        # fold / candidate pulses in fit.py, cv.py and validators.py land
        # on this run; an ExitStack keeps the existing failover-loop
        # structure intact (a re-entered fit phase accumulates seconds)
        _rec_stack = contextlib.ExitStack()
        _rec_stack.enter_context(_runlog.recording(recorder))
        _rec_stack.enter_context(
            recorder.phase("fit", rows=train_data.num_rows)
        )
        try:
            install = (
                distributed.installed_controller(controller)
                if own_controller
                else contextlib.nullcontext()
            )
            with install:
                prefitted = load_checkpointed_layers()
                cv_results = None
                while True:
                    try:
                        with use_execution_mesh(controller.mesh):
                            if self._workflow_cv and selector is not None:
                                if cv_results is None:
                                    from .cv import workflow_cv_results

                                    # NOTE: checkpoint-restored stages stay
                                    # OUT of the per-fold refits — they were
                                    # fit on the full training split, and
                                    # prefitting them here would leak
                                    # validation rows into candidate
                                    # selection; only the user's explicit
                                    # warm-start stages are honored (same
                                    # semantics as an uninterrupted
                                    # withWorkflowCV train)
                                    cv_results = workflow_cv_results(
                                        selector, train_data,
                                        prefitted=self._prefitted,
                                    )
                                    log.info(
                                        "Workflow-level CV: %d candidate "
                                        "results from per-fold DAG refits",
                                        len(cv_results),
                                    )
                                # re-handed on every attempt: the selector
                                # consumes them, and a failover AFTER the
                                # sweep finished must not re-run training's
                                # most expensive phase
                                selector.precomputed_results = cv_results

                            fitted_data, fitted = fit_and_transform_dag(
                                train_data, self.result_features,
                                prefitted=prefitted, checkpoint=ckpt,
                            )
                        break
                    except HostLostError as e:
                        # elastic degraded-mesh failover: shrink the mesh to
                        # the survivors (raises when no failover is left),
                        # restore every completed layer from the checkpoint,
                        # and re-enter the fit instead of aborting
                        controller.failover(e)
                        prefitted = load_checkpointed_layers()
        finally:
            _rec_stack.close()
            if selector is not None:
                selector._checkpoint = None
                selector._checkpoint_resume = False
        dist_summary = controller.summary()

        selector_info = None
        if selector is not None:
            selector_info = {
                "estimatorUid": selector.uid,
                "labelName": selector.input_names[0],
                "vectorName": selector.input_names[1],
                "predName": selector.output_name,
                "evaluator": selector.evaluator.name,
                "problemKind": selector.problem_kind,
            }
            sel_stage = fitted.get(selector.uid)
            if isinstance(sel_stage, SelectedModel):
                # failover counters ride the selector summary next to the
                # PR-1 candidateAttempts ledger (same reporting convention);
                # the featurize ledger here covers the WHOLE train ingest
                # (the delta captured inside fit_arrays only sees the
                # selector's own array work)
                sel_stage.summary["distributedResilience"] = dist_summary
                sel_stage.summary["featurizeStats"] = _fstats.delta(
                    featurize_baseline
                )
                if stream_summary is not None:
                    # the reduced fit stats are large (per-field exact
                    # partials); the selector summary carries the chunk /
                    # quarantine / window accounting only
                    sel_stage.summary["streamIngest"] = {
                        k: v for k, v in stream_summary.items()
                        if k != "fitStats"
                    }

        holdout_metrics = None
        if selector is not None and holdout_data is not None:
            sel_model = fitted[selector.uid]
            assert isinstance(sel_model, SelectedModel)
            with recorder.phase("eval", rows=len(holdout_data)):
                with _tspans.span("train/eval", rows=len(holdout_data)):
                    transformed = apply_transformations_dag(
                        holdout_data, self.result_features, fitted
                    )
                    label_name, vec_name = selector.input_names
                    label = transformed[label_name]
                    vec = transformed[vec_name]
                    assert isinstance(label, NumericColumn) and isinstance(
                        vec, VectorColumn
                    )
                    holdout_metrics = sel_model.evaluate_holdout(
                        np.asarray(vec.values, dtype=np.float32),
                        label.values.astype(np.float64),
                        selector.evaluator,
                    )
            log.info("Holdout metrics: %s", holdout_metrics)

        label_summary = None
        if selector_info is not None:
            label_summary = _label_summary(
                fitted_data, selector_info, self.result_features
            )

        # serving-drift profiles (resilience/sentinel.py): per-raw-feature
        # fill rate + value histogram over the training rows, persisted in
        # the model artifact so score_function's drift sentinel can compare
        # the live stream against what the model was trained on
        from ..resilience.sentinel import compute_serving_profiles

        serving_profiles = compute_serving_profiles(train_data, raw_features)

        # attribution baseline (insights/drift.py): one batched LOCO sweep
        # over a bounded training sample, sketching each feature group's
        # contribution distribution — the serve-time attribution drift
        # monitor compares explain=k sweeps against this. Persisted next
        # to servingProfiles; TPTPU_ATTRIBUTION_PROFILE_ROWS=0 disables.
        attribution_profiles = None
        if selector_info is not None:
            with recorder.phase("attribution"):
                attribution_profiles = _attribution_baseline(
                    fitted, selector_info, fitted_data
                )

        # freeze the flight recorder into the run report, persist it as a
        # RUN_*.json artifact when a run dir is configured, and auto-diff
        # against the directory's previous run (the regression sentinel)
        run_report = _finalize_run_report(
            recorder, holdout_metrics, train_data.num_rows,
            run_dir if run_dir is not None else os.environ.get("TPTPU_RUN_DIR"),
        )

        model = WorkflowModel(
            result_features=self.result_features,
            raw_features=tuple(raw_features),
            fitted=fitted,
            selector_info=selector_info,
            train_rows=train_data.num_rows,
            holdout_rows=0 if holdout_data is None else holdout_data.num_rows,
            rff_results=None if rff_results is None else rff_results.to_json(),
            blocklisted=list(self.blocklisted_features),
            sensitive_info=sensitive_info,
            label_summary=label_summary,
            training_params=dict(self._stage_overrides),
            serving_profiles=serving_profiles,
            attribution_profiles=attribution_profiles,
            dist_summary=dist_summary,
            analysis=preflight_report.to_json(),
            run_report=run_report,
        )
        if selector is not None:
            # keep the live evaluator object so custom evaluators keep working
            # on the in-memory model (the name in selector_info covers load)
            model._live_evaluator = selector.evaluator
        return model


def _finalize_run_report(
    recorder: "_runlog.RunRecorder",
    holdout_metrics: dict[str, Any] | None,
    train_rows: int,
    run_dir: str | None,
) -> dict[str, Any] | None:
    """Freeze the flight recorder into its RunReport; with a run dir,
    diff against the directory's latest run FIRST (the regression verdict
    rides inside the new artifact), then persist ``RUN_*.json``. Contained:
    a capture failure degrades to ``run_report=None``, never a failed
    train."""
    try:
        recorder.record_quality(holdout_metrics)
        report = recorder.finalize(train_rows=train_rows)
        if run_dir:
            baseline = _runlog.latest_run_report(run_dir)
            if baseline is not None:
                diff = _runlog.diff_runs(baseline, report)
                report["run"]["regression"] = {
                    "baselineRunId": (baseline.get("run") or {}).get("runId"),
                    "baselineFile": (baseline.get("run") or {}).get("file"),
                    "findings": [f.to_json() for f in diff.findings],
                }
                if diff.findings:
                    log.warning(
                        "train run regressed vs %s:\n%s",
                        (baseline.get("run") or {}).get("file", "<baseline>"),
                        diff.pretty(),
                    )
            path = _runlog.save_run_report(report, run_dir)
            log.info("run report written: %s", path)
        return report
    except Exception as e:  # observability must never fail a train
        log.warning("run report capture failed: %s", e)
        return None


def _attribution_baseline(
    fitted: dict[str, Any],
    selector_info: dict[str, Any],
    fitted_data: Dataset,
) -> dict[str, Any] | None:
    """Train-time baseline attribution profile (insights/drift.py) — a
    best-effort capture that must never fail a train; one bounded batched
    LOCO sweep, counted under the ``train/attribution`` span so
    ``phase_breakdown()`` attributes its seconds to ``explain``."""
    import os

    try:
        max_rows = int(os.environ.get("TPTPU_ATTRIBUTION_PROFILE_ROWS", "256"))
    except ValueError:
        max_rows = 256
    if max_rows <= 0:
        return None
    sel_model = fitted.get(selector_info["estimatorUid"])
    vec_name = selector_info["vectorName"]
    if sel_model is None or vec_name not in fitted_data:
        return None
    vec = fitted_data[vec_name]
    if not isinstance(vec, VectorColumn):
        return None
    try:
        from ..insights.drift import compute_attribution_profile

        with _tspans.span("train/attribution", rows=min(max_rows, len(vec))):
            return compute_attribution_profile(
                sel_model,
                np.asarray(vec.values, dtype=np.float32),
                vec.metadata,
                max_rows=max_rows,
            )
    except Exception as e:  # observability must never break training
        log.warning("attribution baseline capture skipped: %s", e)
        return None


def _label_summary(
    fitted_data: Dataset,
    selector_info: dict[str, Any],
    result_features: Sequence[Feature],
) -> dict[str, Any] | None:
    """LabelSummary (ModelInsights.scala:293-325): raw lineage + sample size
    + distribution — Discrete {domain, prob} for classification problems,
    Continuous {min, max, mean, variance} for regression."""
    name = selector_info["labelName"]
    if name not in fitted_data:
        return None
    col = fitted_data[name]
    vals = np.asarray(col.values, dtype=np.float64)
    mask = np.asarray(col.mask, dtype=bool) if hasattr(col, "mask") else np.ones(len(vals), bool)
    present = vals[mask]
    label_feat = next((f for f in result_features if f.name == name), None)
    raw = label_feat.raw_features() if label_feat is not None else []
    summary: dict[str, Any] = {
        "labelName": name,
        "rawFeatureName": [f.name for f in raw],
        "rawFeatureType": [f.ftype.__name__ for f in raw],
        "stagesApplied": (
            label_feat.history()["stages"] if label_feat is not None else []
        ),
        "sampleSize": float(len(present)),
    }
    if len(present) == 0:
        summary["distribution"] = None
    elif selector_info["problemKind"] == "Regression":
        summary["distribution"] = {
            "type": "Continuous",
            "min": float(present.min()),
            "max": float(present.max()),
            "mean": float(present.mean()),
            "variance": float(present.var()),
        }
    else:
        uniq, counts = np.unique(present, return_counts=True)
        summary["distribution"] = {
            "type": "Discrete",
            "domain": [str(int(u)) if u == int(u) else str(u) for u in uniq],
            "prob": (counts / counts.sum()).tolist(),
        }
    return summary


class WorkflowModel:
    def __init__(
        self,
        result_features: tuple[Feature, ...],
        raw_features: tuple[Feature, ...],
        fitted: dict[str, PipelineStage],
        selector_info: dict[str, Any] | None,
        train_rows: int = 0,
        holdout_rows: int = 0,
        rff_results: dict[str, Any] | None = None,
        blocklisted: list[str] | None = None,
        sensitive_info: list[dict[str, Any]] | None = None,
        label_summary: dict[str, Any] | None = None,
        training_params: dict[str, Any] | None = None,
        serving_profiles: dict[str, Any] | None = None,
        attribution_profiles: dict[str, Any] | None = None,
        dist_summary: dict[str, Any] | None = None,
        analysis: dict[str, Any] | None = None,
        run_report: dict[str, Any] | None = None,
    ):
        self.result_features = result_features
        self.raw_features = raw_features
        self.fitted = fitted
        self.selector_info = selector_info
        self.train_rows = train_rows
        self.holdout_rows = holdout_rows
        self.rff_results = rff_results
        self.blocklisted = blocklisted or []
        self.sensitive_info = sensitive_info
        self.label_summary = label_summary
        self.training_params = training_params or {}
        #: per-raw-feature training distributions for the serve-time drift
        #: sentinel (fill rate + StreamingHistogram JSON); None on models
        #: saved before this field existed
        self.serving_profiles = serving_profiles
        #: per-feature-group baseline LOCO contribution histograms for the
        #: serve-time attribution drift monitor (insights/drift.py); None
        #: on models saved before the explainability plane existed
        self.attribution_profiles = attribution_profiles
        #: distributed-resilience ledger from training (hosts lost,
        #: failovers, collective retries, stragglers, reshard events, mesh
        #: history); None on models saved before this field existed
        self.dist_summary = dist_summary
        #: pre-flight static-analysis report from train() (JSON form of
        #: analysis.Report — findings that survived as warnings/info);
        #: None on models saved before the analysis plane existed
        self.analysis = analysis
        #: training-run flight-recorder report (telemetry/runlog.py):
        #: per-phase/layer/fold timings, ledger deltas, runtime transfer
        #: census, device-memory high-water; None on models saved before
        #: the run ledger existed (or when capture degraded)
        self.run_report = run_report

    # --------------------------------------------------------- persistence
    def save(self, path: str) -> None:
        """OpWorkflowModelWriter equivalent: manifest.json + arrays.npz."""
        from .persistence import save_workflow_model

        save_workflow_model(self, path)

    @staticmethod
    def load(path: str) -> "WorkflowModel":
        """Standalone load (OpWorkflowModel.load, OpWorkflowModel.scala:456)."""
        from .persistence import load_workflow_model

        return load_workflow_model(path)

    # --------------------------------------------------------------- score
    def _prepare_raw(self, dataset: Dataset | None, reader: DataReader | None) -> Dataset:
        if dataset is not None:
            reader = DatasetReader(self._with_missing_response(dataset))
        if reader is None:
            raise ValueError("score requires a dataset or reader")
        try:
            raw = reader.generate_dataset(list(self.raw_features))
        except KeyError:
            # scoring data typically lacks the response column: generate the
            # predictors only and synthesize null labels
            raw = reader.generate_dataset(
                [f for f in self.raw_features if not f.is_response]
            )
        return self._with_missing_response(raw)

    def _with_missing_response(self, dataset: Dataset) -> Dataset:
        """Scoring data often lacks the response column; synthesize NULL
        labels of the right physical type (mask=False / None — the reference
        reader produces null labels at score time). Evaluation rejects
        all-null labels loudly."""
        from ..types.columns import empty_like

        for f in self.raw_features:
            if f.is_response and f.name not in dataset:
                dataset = dataset.with_column(
                    f.name, empty_like(f.ftype, dataset.num_rows)
                )
        return dataset

    def score(
        self,
        dataset: Dataset | None = None,
        reader: DataReader | None = None,
        keep_raw_features: bool = False,
        keep_intermediate_features: bool = False,
    ) -> Dataset:
        """Apply the fitted DAG (OpWorkflowModel.score, OpWorkflowModel.scala:259)."""
        from ..compiler import cache as _ccache
        from ..compiler import warmup as _warmup

        _ccache.enable_persistent_cache()
        # overlap loading the banked scoring executables with raw-data prep
        _warmup.start_warmup(_warmup.SCORE_PROGRAMS, scope="score")
        raw = self._prepare_raw(dataset, reader)
        transformed = apply_transformations_dag(raw, self.result_features, self.fitted)
        if keep_intermediate_features:
            return transformed
        keep = [f.name for f in self.result_features if f.name in transformed]
        if keep_raw_features:
            keep = [f.name for f in self.raw_features] + keep
        return transformed.select(keep)

    def score_and_evaluate(
        self,
        dataset: Dataset | None = None,
        evaluator=None,
        reader: DataReader | None = None,
    ) -> tuple[Dataset, dict[str, Any]]:
        scores = self.score(dataset, reader=reader, keep_intermediate_features=True)
        metrics = self._evaluate_transformed(scores, evaluator)
        keep = [f.name for f in self.result_features if f.name in scores]
        return scores.select(keep), metrics

    def evaluate(
        self,
        dataset: Dataset | None = None,
        evaluator=None,
        reader: DataReader | None = None,
    ) -> dict[str, Any]:
        """Score + evaluate against the true labels present in the data."""
        transformed = self.score(
            dataset, reader=reader, keep_intermediate_features=True
        )
        return self._evaluate_transformed(transformed, evaluator)

    def _evaluate_transformed(self, transformed: Dataset, evaluator=None) -> dict[str, Any]:
        if self.selector_info is None:
            raise ValueError("evaluate requires a ModelSelector in the workflow")
        if evaluator is None:
            evaluator = getattr(self, "_live_evaluator", None)
        if evaluator is None:
            from ..evaluators import (
                BinaryClassificationEvaluator,
                ForecastEvaluator,
                MultiClassificationEvaluator,
                RegressionEvaluator,
            )

            by_name = {
                e.name: e
                for e in (
                    BinaryClassificationEvaluator(),
                    MultiClassificationEvaluator(),
                    RegressionEvaluator(),
                    ForecastEvaluator(),
                )
            }
            name = self.selector_info["evaluator"]
            if name not in by_name:
                raise ValueError(
                    f"Evaluator '{name}' is not a builtin; pass the evaluator "
                    "object explicitly to evaluate()/score_and_evaluate()"
                )
            evaluator = by_name[name]
        label = transformed[self.selector_info["labelName"]]
        if isinstance(label, NumericColumn) and not label.mask.any():
            raise ValueError(
                "evaluate requires true labels, but the response column "
                f"'{self.selector_info['labelName']}' is absent/all-null in "
                "the provided data"
            )
        pred = transformed[self.selector_info["predName"]]
        return evaluator.evaluate(label, pred)

    # ------------------------------------------------------------- summary
    def summary_json(self) -> dict[str, Any]:
        sel_summary = None
        if self.selector_info is not None:
            model = self.fitted.get(self.selector_info["estimatorUid"])
            if isinstance(model, SelectedModel):
                sel_summary = model.summary
        stage_meta = {
            uid: s.metadata
            for uid, s in self.fitted.items()
            if s.metadata
        }
        analysis = self.analysis
        if analysis is not None:
            # the TPC static-concurrency and TPS SPMD summaries ride
            # beside the TPA/TPX reports (lru-cached per process;
            # contained — a broken analyzer must never break a training
            # summary)
            analysis = dict(analysis)
            try:
                from ..analysis.concurrency import package_summary

                analysis["concurrency"] = package_summary()
            except Exception:  # pragma: no cover - defensive
                pass
            try:
                from ..analysis.spmd import package_summary as spmd_summary

                analysis["spmd"] = spmd_summary()
            except Exception:  # pragma: no cover - defensive
                pass
        try:
            from ..resilience.retrain import ledger_snapshot

            retrain_ledger = ledger_snapshot()
        except Exception:  # pragma: no cover - defensive
            retrain_ledger = None
        return {
            "trainRows": self.train_rows,
            "holdoutRows": self.holdout_rows,
            "rawFeatures": [f.name for f in self.raw_features],
            "resultFeatures": [f.name for f in self.result_features],
            "blocklistedFeatures": self.blocklisted,
            "rawFeatureFilterResults": self.rff_results,
            "sensitiveFeatures": self.sensitive_info,
            "modelSelectorSummary": sel_summary,
            "stageMetadata": stage_meta,
            "distributedResilience": self.dist_summary,
            "retrainLedger": retrain_ledger,
            "analysis": analysis,
            "run": getattr(self, "run_report", None),
        }

    def summary_pretty(self) -> str:
        """Human-readable training summary matching the reference
        README's summaryPretty rendering (/root/reference/README.md:63-96):
        the evaluated-families lead, the selected model's PARAMETER table,
        one combined holdout/training metric table, and the
        correlation-ranked top-insights + contributions tables."""
        from ..utils.table import render_table

        s = self.summary_json()
        lines: list[str] = []
        sel = s.get("modelSelectorSummary")
        if sel:
            results = sel["validationResults"]
            by_family: dict[str, list[float]] = {}
            for r in results:
                by_family.setdefault(r["modelName"], []).append(r["metricMean"])
            metric = sel["evaluationMetric"]
            n_folds = len(results[0].get("metricValues", [])) if results else 0
            lines.append(
                f"Evaluated {', '.join(sorted(by_family))} models with "
                f"{n_folds} folds and {metric} metric."
            )
            for name, vals in sorted(by_family.items()):
                lines.append(
                    f"Evaluated {len(vals)} {name} models with {metric} "
                    f"between [{min(vals)}, {max(vals)}]"
                )
            # retry/exclusion ledger (resilience): candidates that needed
            # more than one attempt, or were excluded after exhausting them
            for a in sel.get("candidateAttempts") or []:
                if a.get("excluded"):
                    lines.append(
                        f"Excluded {a['modelName']} after "
                        f"{a.get('attempts', 1)} attempt(s): {a.get('error')}"
                    )
                elif a.get("attempts", 1) > 1:
                    lines.append(
                        f"Retried {a['modelName']}: succeeded on attempt "
                        f"{a['attempts']}"
                    )
            lines.append("")
            # selected-model parameter table (README: "Selected model Random
            # Forest classifier with parameters")
            lines.append(
                f"Selected model {sel['bestModelType']} with parameters:"
            )
            params: dict[str, Any] = {"modelType": sel["bestModelType"]}
            best_model = None
            if self.selector_info is not None:
                stage = self.fitted.get(self.selector_info["estimatorUid"])
                best_model = getattr(stage, "best_model", None)
            if best_model is not None:
                params.update(best_model.get_params())
            params.update(sel.get("bestGrid", {}))
            lines.append(
                render_table(
                    ["Model Param", "Value"],
                    [[k, str(v)] for k, v in sorted(params.items())],
                )
            )
            lines.append("")
            # ONE combined metric table, holdout + training side by side
            train_m = sel.get("trainEvaluation") or {}
            hold_m = sel.get("holdoutEvaluation") or {}
            keys = [
                k for k in {**hold_m, **train_m}
                if isinstance((hold_m.get(k, train_m.get(k))), (int, float))
            ]
            if keys:
                lines.append("Model evaluation metrics:")
                lines.append(
                    render_table(
                        ["Metric Name", "Hold Out Set Value",
                         "Training Set Value"],
                        [
                            [k, str(hold_m.get(k, "")), str(train_m.get(k, ""))]
                            for k in keys
                        ],
                    )
                )
                lines.append("")
            # top insights by label correlation + model contributions
            # (README: "Top model insights computed using correlation")
            try:
                from ..insights.model_insights import model_insights

                ins = model_insights(self)
                derived = [
                    d
                    for f in ins.get("features", [])
                    for d in f.get("derivedFeatures", [])
                ]
                ilines: list[str] = []
                with_corr = [
                    d for d in derived
                    if isinstance(d.get("corr"), (int, float))
                    and np.isfinite(d["corr"])
                ]
                with_corr.sort(key=lambda d: -d["corr"])
                pos = [d for d in with_corr if d["corr"] >= 0]
                if with_corr:
                    ilines.append(
                        "Top model insights computed using correlation:"
                    )
                    if pos:
                        ilines.append(render_table(
                            ["Top Positive Insights", "Correlation"],
                            [[d["derivedFeatureName"], f"{d['corr']:.4f}"]
                             for d in pos[:7]],
                        ))
                    negs = [d for d in reversed(with_corr) if d["corr"] < 0]
                    if negs:
                        ilines.append(render_table(
                            ["Top Negative Insights", "Correlation"],
                            [[d["derivedFeatureName"], f"{d['corr']:.4f}"]
                             for d in negs[:7]],
                        ))
                    ilines.append("")
                with_contrib = [
                    d for d in derived
                    if isinstance(d.get("contribution"), (int, float))
                ]
                with_contrib.sort(key=lambda d: -abs(d["contribution"]))
                if with_contrib and any(d["contribution"] for d in with_contrib):
                    ilines.append("Top Contributions:")
                    ilines.append(render_table(
                        ["Top Contributions", "Value"],
                        [[d["derivedFeatureName"], f"{d['contribution']:.4f}"]
                         for d in with_contrib[:7]],
                    ))
                    ilines.append("")
                lines.extend(ilines)  # all-or-nothing: no dangling headers
            except Exception as e:  # insights stay best-effort, but a
                # broken section must be observable, not invisible:
                # counted on the run ledger + a summary_degraded event +
                # a one-shot warning (was a silent debug-level swallow)
                _report_summary_degraded("insights", e)
        comp = (sel or {}).get("compileStats") or {}
        if comp.get("programsCompiled") or comp.get("cacheHitsMemory") or \
                comp.get("cacheHitsDisk") or comp.get("dedupHits"):
            hits = comp.get("cacheHitsMemory", 0) + comp.get("cacheHitsDisk", 0)
            rate = comp.get("compileCacheHitRate")
            rate_s = f", {rate:.0%} hit rate" if rate is not None else ""
            lines.append(
                f"Compile plane: {comp.get('programsCompiled', 0)} "
                f"program(s) compiled, {hits} cache hit(s){rate_s}, "
                f"{comp.get('dedupHits', 0)} dedup lane(s), "
                f"{comp.get('laneBucketPads', 0)} pad lane(s), "
                f"{comp.get('warmupPrograms', 0)} warmed "
                f"({comp.get('warmupOverlapSeconds', 0.0):.2f}s overlapped)"
            )
        if comp.get("fusedDispatches") or comp.get("fusedFallbacks") or \
                comp.get("fusedFallbackReasons"):
            reasons = comp.get("fusedFallbackReasons") or {}
            reason_s = ""
            if reasons:
                top = sorted(reasons.items(), key=lambda kv: -kv[1])[:3]
                reason_s = " (" + ", ".join(
                    f"{k}: {v}" for k, v in top
                ) + ")"
            lines.append(
                f"Fused serving: {comp.get('fusedDispatches', 0)} "
                f"dispatch(es), {comp.get('fusedExplainLanes', 0)} "
                f"explain lane(s), {comp.get('fusedFallbacks', 0)} "
                f"fallback(s){reason_s}"
            )
        feat = (sel or {}).get("featurizeStats") or {}
        if feat.get("rowsFeaturized") or feat.get("poolTasks"):
            util = feat.get("poolUtilization")
            util_s = f", pool {util:.0%} util" if util is not None else ""
            per_stage = feat.get("stageRowsPerSec") or {}
            slow = min(
                (
                    (c.get("rowsPerSec"), name)
                    for name, c in per_stage.items()
                    if c.get("rowsPerSec")
                ),
                default=(None, ""),
            )
            top_s = (
                f", bottleneck stage {slow[1]} @ {slow[0]:,} rows/s"
                if slow[0] else ""
            )
            lines.append(
                f"Featurize plane: {feat.get('rowsFeaturized', 0):,} "
                f"row(s) through {feat.get('stagesExecuted', 0)} stage "
                f"pass(es), {feat.get('fusedAssemblies', 0)} fused, "
                f"{feat.get('poolTasks', 0)} pool task(s){util_s}, "
                f"{feat.get('fallbackKernels', 0)} fallback kernel(s)"
                f"{top_s}"
            )
        # explainability plane: the attribution ledger's one-line view
        # (train-time baseline sweeps + any serve-time explain=k work)
        try:
            from ..insights import ledger as _attr_ledger

            att = _attr_ledger.snapshot()
            if att.get("rowsExplained") or att.get("profilesCaptured"):
                rate = att.get("explainRowsPerSec")
                rate_s = f" @ {rate:,} rows/s" if rate else ""
                profiled = len(
                    (getattr(self, "attribution_profiles", None) or {})
                    .get("groups", {})
                )
                lines.append(
                    f"Record insights: {att.get('rowsExplained', 0):,} "
                    f"row(s) explained{rate_s}, "
                    f"{att.get('laneDispatches', 0)} lane(s) dispatched "
                    f"({att.get('lanesDeduped', 0)} deduped, "
                    f"{att.get('lanesPadded', 0)} padded), "
                    f"{profiled} group(s) profiled, "
                    f"{att.get('attributionDriftAlerts', 0)} attribution "
                    f"drift alert(s), {att.get('explainShedRows', 0)} "
                    f"row(s) shed"
                )
        except Exception as e:  # observability must never break summaries
            log.debug("record-insights summary line skipped: %s", e)
        dist = getattr(self, "dist_summary", None) or {}
        if any(
            dist.get(k)
            for k in (
                "hostsLost", "failovers", "stragglersDetected",
                "collectivesRetried", "reshardEvents",
            )
        ):
            lines.append(
                f"Distributed resilience: {dist.get('hostsLost', 0)} "
                f"host(s) lost, {dist.get('failovers', 0)} failover(s), "
                f"{dist.get('collectivesRetried', 0)} collective "
                f"retry(ies), {dist.get('stragglersDetected', 0)} "
                f"straggler(s), {dist.get('reshardEvents', 0)} reshard "
                f"event(s)"
            )
        serve = self._serving_resilience_line()
        if serve:
            lines.append(serve)
        run_line = self._run_report_lines()
        if run_line:
            lines.extend(run_line)
        # one consolidated telemetry line (span/event counts + serve
        # latency quantiles) pointing at the full export surfaces
        try:
            from ..telemetry import summary_line as _tel_line

            tel = _tel_line()
            if tel:
                lines.append(tel)
        except Exception as e:  # telemetry must never break the summary
            log.debug("telemetry summary line skipped: %s", e)
        analysis = getattr(self, "analysis", None) or {}
        if analysis.get("findings"):
            codes: dict[str, int] = {}
            for f in analysis["findings"]:
                codes[f["code"]] = codes.get(f["code"], 0) + 1
            code_s = ", ".join(
                f"{c}×{n}" if n > 1 else c for c, n in sorted(codes.items())
            )
            lines.append(
                f"Static analysis: {analysis.get('errors', 0)} error(s), "
                f"{analysis.get('warnings', 0)} warning(s) ({code_s}) — "
                "see docs/analysis.md"
            )
        lines.append(
            f"Trained on {s['trainRows']} rows (holdout {s['holdoutRows']}); "
            f"{len(s['rawFeatures'])} raw features"
        )
        return "\n".join(lines)

    def _run_report_lines(self) -> list[str]:
        """The flight recorder's summary lines: one "Run report:" line
        (wall, phases, layers, transfer census, device high-water, the
        artifact file when persisted) plus a regression line when the
        auto-diff against the run dir's previous run found TPR findings."""
        report = getattr(self, "run_report", None) or {}
        run = report.get("run") or {}
        if not run:
            return []
        lines: list[str] = []
        phases = run.get("phases") or {}
        phase_s = ", ".join(
            f"{name} {cell.get('seconds', 0.0):.2f}s"
            for name, cell in phases.items()
        )
        census = run.get("transferCensus") or {}
        h2d = census.get("hostToDevice") or {}
        d2h = census.get("deviceToHost") or {}
        mem = run.get("deviceMemory") or {}
        line = (
            f"Run report: {run.get('wallSeconds', 0.0):.2f}s wall"
            + (f" ({phase_s})" if phase_s else "")
            + f", {len(run.get('layers') or [])} layer(s), "
            f"h2d {h2d.get('count', 0)}x/{h2d.get('bytes', 0):,} B, "
            f"d2h {d2h.get('count', 0)}x/{d2h.get('bytes', 0):,} B, "
            f"device high-water {mem.get('highWaterBytes', 0):,} B "
            f"({mem.get('backend', '?')})"
        )
        if run.get("file"):
            line += f" — {run['file']}"
        lines.append(line)
        regression = run.get("regression") or {}
        findings = regression.get("findings") or []
        if findings:
            codes: dict[str, int] = {}
            for f in findings:
                codes[f["code"]] = codes.get(f["code"], 0) + 1
            code_s = ", ".join(
                f"{c}×{n}" if n > 1 else c for c, n in sorted(codes.items())
            )
            lines.append(
                f"Run regression: {len(findings)} finding(s) vs "
                f"{regression.get('baselineFile', 'previous run')} "
                f"({code_s}) — see docs/observability.md"
            )
        return lines

    def _serving_resilience_line(self) -> str | None:
        """Aggregate serve-side counters from every live score function
        built off this model (local.scoring keeps weak references), so one
        report covers train-side retries AND serve-side degradation."""
        quarantined = guarded = drift_alerts = breaker_trips = 0
        seen = False
        for ref in getattr(self, "_serving_monitors", []):
            fn = ref()
            if fn is None:
                continue
            try:
                md = fn.metadata()
            except Exception as e:  # monitoring must never break the summary
                log.debug("serving monitor skipped: %s", e)
                continue
            seen = True
            quarantined += md["quarantine"]["quarantinedRows"]
            guarded += md["scoreGuard"]["guardedRows"]
            drift = md.get("drift") or {}
            drift_alerts += drift.get("driftAlertsTotal", 0)
            for br in md["breakers"].values():
                t = br["transitions"]
                breaker_trips += t.get("closed->open", 0) + t.get(
                    "half_open->open", 0
                )
        if not seen:
            return None
        return (
            f"Serving resilience: {quarantined} quarantined row(s), "
            f"{guarded} guarded row(s), {drift_alerts} drift alert(s), "
            f"{breaker_trips} breaker trip(s)"
        )
