"""Async background warmup of the persistent program bank.

``Workflow.train`` and the serving closure know — before any data is read —
which model families the traced DAG will exercise, and therefore which
banked executables the run will need. Warmup starts a daemon thread that
loads exactly those (``utils.aot.prewarm(names=...)``) while the main
thread runs host-side ingest/feature prep, so program acquisition overlaps
work instead of serializing in front of the first fit dispatch.

One warmup runs per (scope, names) per process; repeats are free no-ops.
The loaded-program count and overlapped seconds land in the
``compileStats`` ledger (``warmupPrograms`` / ``warmupOverlapSeconds``).
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Iterable

from . import stats as _stats

log = logging.getLogger(__name__)

_LOCK = threading.Lock()
_STARTED: dict[tuple, threading.Thread] = {}

#: programs the serving path dispatches (tree predicts bin + traverse on
#: device above the host-predict cutoff; serve_trees is the Pallas
#: multi-tree traversal kernel of models/serve_pallas.py; stack_lane
#: materializes a sweep winner's lane; fused_serve* are the end-to-end
#: fused scoring graphs of compiler/fused.py — banked per structural
#: fingerprint)
SCORE_PROGRAMS = frozenset(
    {
        "predict_boosted", "predict_forest", "bin_data", "stack_lane",
        "serve_trees", "fused_serve", "fused_serve_explain",
    }
)

_TREE_PROGRAMS = frozenset(
    {
        "bin_data", "bin_column_stats", "boost_chunk", "forest_scan",
        "sweep_boost_outputs",
        "sweep_forest_outputs", "stack_lane", "predict_boosted",
        "predict_forest",
    }
)

#: estimator class name -> banked program names its fit/predict path routes
#: through ``aot_call``. Families absent here (NaiveBayes, SVC, MLP)
#: compile through the plain jit cache and bank nothing.
_FAMILY_PROGRAMS: dict[str, frozenset] = {
    "LogisticRegression": frozenset(
        {"logistic_binary_batched", "logistic_multinomial_batched"}
    ),
    "LinearRegression": frozenset({"linear_batched"}),
    "GeneralizedLinearRegression": frozenset({"glr_irls_batched"}),
    "XGBoostClassifier": _TREE_PROGRAMS,
    "XGBoostRegressor": _TREE_PROGRAMS,
    "GBTClassifier": _TREE_PROGRAMS,
    "GBTRegressor": _TREE_PROGRAMS,
    "RandomForestClassifier": _TREE_PROGRAMS,
    "RandomForestRegressor": _TREE_PROGRAMS,
    "DecisionTreeClassifier": _TREE_PROGRAMS,
    "DecisionTreeRegressor": _TREE_PROGRAMS,
    "OpWord2Vec": frozenset({"sgns_scan2"}),
    "OpLDA": frozenset({"lda_scan"}),
}


def train_programs(stages: Iterable) -> set[str] | None:
    """Banked-program names the given DAG stages will need, or ``None``
    (= warm everything) when an unmapped model family is present."""
    names: set[str] = set()
    unknown_family = False
    for stage in stages:
        cls = type(stage).__name__
        if cls == "ModelSelector":
            for est, _grid in getattr(stage, "models", []):
                fam = _FAMILY_PROGRAMS.get(type(est).__name__)
                if fam is None:
                    unknown_family = True
                else:
                    names.update(fam)
            # the winner's standalone scoring program is banked too
            names.update(SCORE_PROGRAMS)
        else:
            names.update(_FAMILY_PROGRAMS.get(cls, ()))
    if unknown_family:
        return None
    return names


def start_warmup(
    names: set[str] | frozenset | None = None, scope: str = "train"
) -> threading.Thread | None:
    """Kick the background bank load (once per (scope, names) per process
    — a later train over DIFFERENT model families warms again; loading is
    idempotent, already-resident programs are skipped by ``_MEM``);
    returns the thread (callers/tests may join) or None when this exact
    warmup already ran or the bank is disabled."""
    from ..utils import aot

    if not aot._enabled():
        return None
    key = (scope, None if names is None else tuple(sorted(names)))
    with _LOCK:
        if key in _STARTED:
            return None
        th = threading.Thread(
            target=_run, args=(names,), daemon=True,
            name=f"tptpu-warmup-{scope}",
        )
        _STARTED[key] = th
    th.start()
    return th


def _run(names) -> None:
    from ..telemetry import events as _tevents
    from ..telemetry import spans as _tspans
    from ..utils import aot

    t0 = time.monotonic()
    try:
        with _tspans.span(
            "compile/warmup", programs=-1 if names is None else len(names)
        ):
            n = aot.prewarm(names=names)
    except Exception as e:  # warmup must never take a train down
        log.info("warmup failed: %s", e)
        return
    overlap = time.monotonic() - t0
    _stats.stats().record_warmup(n, overlap)
    _tevents.emit(
        "warmup_complete", programs=n, overlapSeconds=round(overlap, 3)
    )


def join_warmup(timeout: float | None = None) -> bool:
    """Block until every started warmup thread finishes loading (the
    standing service's ``start(wait_warmup=True)`` — a service that wants
    its first batch warm, not overlapped). Returns False when a thread is
    still alive after ``timeout`` seconds."""
    with _LOCK:
        threads = list(_STARTED.values())
    deadline = None if timeout is None else time.monotonic() + timeout
    ok = True
    for th in threads:
        left = (
            None if deadline is None
            else max(0.0, deadline - time.monotonic())
        )
        th.join(timeout=left)
        ok = ok and not th.is_alive()
    return ok


def reset_for_tests() -> None:
    """Forget started scopes so a test can exercise warmup repeatedly."""
    with _LOCK:
        _STARTED.clear()
