"""Structured trace spans — low-overhead, thread-safe, Perfetto-ready.

``span("train/layer", index=3)`` is a context manager that times a region
and records it three ways:

* a **Chrome trace event** in a bounded in-process buffer (complete
  ``"ph": "X"`` events). Every record carries an ``id``, the ``parent``
  that caused it (``None`` for a root) and the ``trace`` it belongs to
  (its root's ``id``), all from one process-wide counter. On one thread
  the thread-local stack supplies the parent; across a pool the caller
  hands it over: ``handle = current()`` before ``submit``,
  ``span(name, parent=handle)`` in the worker;
* a ``jax.profiler.TraceAnnotation`` named ``tptpu:<span name>`` around
  the same block, so that in any profiler trace the program's spans lie
  on host thread lines on the device trace's clock (the in-process
  record keeps the injectable clock below);
* an **exponential-bucket duration histogram** per span name in the
  metrics registry (``tptpu_span_seconds{span="..."}``) — true
  p50/p95/p99 per stage family;
* for root ``serve/*`` spans, a compact trace in the bounded **serving
  ring buffer** (:func:`recent_serve_traces`).

The clock is injectable (:func:`set_clock`) so the telemetry suite runs on
fake time — the same seam convention the resilience components use
(TPL004). Disabling (:func:`set_enabled` or ``TPTPU_TELEMETRY=0``) makes
``span`` a near-no-op; the <2% train+serve overhead guard in
``tests/test_telemetry.py`` pins the enabled cost.

The serving hot path records through :func:`record_serve_batch` (one call
per scored batch with pre-aggregated per-family seconds) instead of one
span per stage, so single-row scoring pays a handful of clock reads, not
dozens of span objects; per-stage detail spans engage above
``TPTPU_TRACE_STAGE_ROWS`` rows (default 16).
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from typing import Any, Callable

from . import metrics as _metrics

__all__ = [
    "span",
    "current",
    "record_span",
    "record_serve_batch",
    "clock",
    "set_clock",
    "get_clock",
    "enabled",
    "set_enabled",
    "stage_detail",
    "set_detail_suppressed",
    "snapshot_events",
    "recent_serve_traces",
    "configure_buffers",
    "reset_for_tests",
]


def _env_int(name: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(name, str(default))))
    except ValueError:
        return default


_LOCK = threading.Lock()
_TLS = threading.local()

#: injectable monotonic clock (rebindable plain name — no lock needed)
_CLOCK: Callable[[], float] = time.monotonic

#: mutable module state crossed by worker/warmup threads — every write
#: below holds ``_LOCK`` (TPL001)
_STATE: dict[str, Any] = {
    "enabled": os.environ.get("TPTPU_TELEMETRY", "1") != "0",
    # raised by the serving load shedder (tier >= 1): per-stage detail
    # spans are the cheapest thing to drop under overload
    "detail_suppressed": False,
}
_EVENTS: deque = deque(maxlen=_env_int("TPTPU_TRACE_BUFFER", 65536))
_SERVE_RING: deque = deque(maxlen=_env_int("TPTPU_SERVE_TRACE_RING", 64))
_TIDS: dict[int, int] = {}
#: span ids: one process-wide counter (``next`` on it is atomic, no lock)
_IDS = itertools.count(1)
#: prefix of the profiler annotations ``span`` enters (never ``bench:``,
#: which is the benchmark's own)
ANNOTATION_PREFIX = "tptpu:"
_ANNOTATION: Any = None  # jax.profiler.TraceAnnotation, resolved on first use

#: per-batch row floor below which scoring skips per-stage detail spans
_DETAIL_MIN_ROWS = _env_int("TPTPU_TRACE_STAGE_ROWS", 16)

_CHILD_CAP = 256  # children kept per span in the serving-ring trace tree


def clock() -> float:
    return _CLOCK()


def set_clock(fn: Callable[[], float] | None = None) -> None:
    """Swap the monotonic clock (None restores ``time.monotonic``)."""
    global _CLOCK
    _CLOCK = fn if fn is not None else time.monotonic


def get_clock() -> Callable[[], float]:
    """The currently installed clock callable (for save/restore swaps)."""
    return _CLOCK


def enabled() -> bool:
    return _STATE["enabled"]


def set_enabled(on: bool) -> None:
    with _LOCK:
        _STATE["enabled"] = bool(on)


def stage_detail(rows: int) -> bool:
    """True when scoring should emit per-stage detail spans for a batch of
    ``rows`` (large enough that span cost is noise, and the load shedder
    has not suppressed detail)."""
    return (
        _STATE["enabled"]
        and not _STATE["detail_suppressed"]
        and rows >= _DETAIL_MIN_ROWS
    )


def set_detail_suppressed(on: bool) -> None:
    """Shed/restore per-stage detail spans (serving shed tier 1 — the
    first, cheapest degradation under overload). A stale read in a scoring
    thread mid-transition costs one extra/missing detail span, never
    correctness, so the read side stays lock-free."""
    with _LOCK:
        _STATE["detail_suppressed"] = bool(on)


def _tid() -> int:
    t = threading.get_ident()
    got = _TIDS.get(t)
    if got is None:
        with _LOCK:
            got = _TIDS.setdefault(t, len(_TIDS) + 1)
    return got


def _observe(name: str, dur: float) -> None:
    reg = _metrics.REGISTRY
    reg.histogram("tptpu_span_seconds", labels={"span": name}).observe(dur)
    reg.counter("tptpu_spans_recorded_total").inc()


def _annotation():
    """``jax.profiler.TraceAnnotation``, resolved once: the telemetry
    package itself imports without JAX."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


def _record(
    name: str,
    start: float,
    dur: float,
    attrs: dict | None,
    ids: tuple[int, int | None, int],
    tree_parent: "span | None" = None,
    children: list | None = None,
    ring: bool = False,
) -> None:
    """Append one record. ``ids`` is (id, parent id or None, trace id);
    ``tree_parent`` is the enclosing span on this thread, whose serving-ring
    trace tree gains this span as a child; ``ring`` sends a root
    ``serve/*`` span's own tree to the serving ring."""
    sid, parent_id, trace_id = ids
    rec: dict[str, Any] = {
        "name": name, "ts": start, "dur": dur, "tid": _tid(),
        "id": sid, "parent": parent_id, "trace": trace_id,
    }
    if attrs:
        rec["args"] = dict(attrs)
    with _LOCK:
        _EVENTS.append(rec)
    _observe(name, dur)
    if tree_parent is not None:
        kids = tree_parent.children
        if kids is None:
            kids = tree_parent.children = []
        if len(kids) < _CHILD_CAP:
            child: dict[str, Any] = {
                "name": name, "durMs": round(dur * 1e3, 3),
            }
            if children:
                child["children"] = children
            kids.append(child)
    elif ring and name.startswith("serve/"):
        trace = {
            "name": name,
            "durMs": round(dur * 1e3, 3),
            "attrs": dict(attrs) if attrs else {},
            "children": children or [],
        }
        with _LOCK:
            _SERVE_RING.append(trace)


def current() -> "span | None":
    """The innermost span open on this thread (None outside any span or
    with telemetry disabled): the handle to give a worker thread, which
    opens its spans with ``span(name, parent=handle)``."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


class span:
    """``with span("cv/fold", fold=2): ...`` — times the block and records
    it (see module docstring). ``parent=`` takes the handle of a span open
    on ANOTHER thread (:func:`current`, captured before the hand-over);
    without it the parent is the innermost span open on this thread. Once
    entered, ``id`` / ``parent`` / ``trace`` hold the record's integers, and
    ``attrs`` may still gain what only the block learns (it is recorded on
    exit). Near-free when telemetry is disabled: no id, no annotation."""

    __slots__ = (
        "name", "attrs", "children", "id", "parent", "trace",
        "_cause", "_t0", "_ann",
    )

    def __init__(self, name: str, parent: "span | None" = None, **attrs: Any):
        self.name = name
        self.attrs = attrs
        self.children: list | None = None
        self.id: int | None = None
        self.parent: int | None = None
        self.trace: int | None = None
        self._cause = parent
        self._t0 = -1.0

    def __enter__(self) -> "span":
        if not _STATE["enabled"]:
            return self
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        cause = self._cause
        if cause is None and stack:
            cause = stack[-1]
        self.id = next(_IDS)
        if cause is not None and cause.id is not None:
            self.parent, self.trace = cause.id, cause.trace
        else:
            self.trace = self.id
        stack.append(self)
        # on the profiler's clock too; a no-op object outside a trace
        self._ann = _annotation()(
            ANNOTATION_PREFIX + self.name,
            id=self.id, parent=self.parent or 0, trace=self.trace,
        )
        self._ann.__enter__()
        self._t0 = _CLOCK()
        return self

    def __exit__(self, *exc) -> bool:
        if self._t0 < 0.0:  # entered disabled
            return False
        dur = _CLOCK() - self._t0
        self._ann.__exit__(None, None, None)
        stack = getattr(_TLS, "stack", None)
        tree_parent = None
        if stack and stack[-1] is self:
            stack.pop()
            # a parent handed over from another thread keeps its serving
            # tree to itself: its children list is not shared state
            if stack and self._cause is None:
                tree_parent = stack[-1]
        _record(
            self.name, self._t0, dur, self.attrs,
            (self.id, self.parent, self.trace), tree_parent, self.children,
            ring=self.parent is None,
        )
        return False


def _post_hoc_ids() -> tuple[int, int | None, int]:
    """Ids of a record made after the fact: a child of the span open on
    this thread, else a root of its own."""
    sid = next(_IDS)
    cause = current()
    if cause is not None and cause.id is not None:
        return sid, cause.id, cause.trace
    return sid, None, sid


def record_span(name: str, start: float, dur: float, **attrs: Any) -> None:
    """Record an already-measured interval (the scoring loop aggregates
    per-stage timings with raw clock reads, then emits spans in bulk):
    no profiler annotation, since the interval is over."""
    if not _STATE["enabled"]:
        return
    _record(name, start, dur, attrs, _post_hoc_ids())


def record_serve_batch(
    entry: str, rows: int, started: float, stage_seconds: dict[str, float]
) -> None:
    """One scored batch: total + per-stage-family latency histograms
    (``tptpu_serve_seconds{stage=...}``), a ``serve/batch`` trace span,
    throughput counters, and a compact trace in the serving ring."""
    if not _STATE["enabled"]:
        return
    total = _CLOCK() - started
    reg = _metrics.REGISTRY
    reg.histogram("tptpu_serve_seconds", labels={"stage": "total"}).observe(
        total
    )
    for fam, secs in stage_seconds.items():
        reg.histogram("tptpu_serve_seconds", labels={"stage": fam}).observe(
            secs
        )
    reg.counter("tptpu_serve_batches_total").inc()
    reg.counter("tptpu_serve_rows_total").inc(rows)
    sid, parent_id, trace_id = _post_hoc_ids()
    rec = {
        "name": "serve/batch", "ts": started, "dur": total, "tid": _tid(),
        "id": sid, "parent": parent_id, "trace": trace_id,
        "args": {"rows": rows, "entry": entry},
    }
    trace = {
        "name": "serve/batch",
        "entry": entry,
        "rows": rows,
        "durMs": round(total * 1e3, 3),
        "stagesMs": {
            fam: round(secs * 1e3, 3) for fam, secs in stage_seconds.items()
        },
    }
    with _LOCK:
        _EVENTS.append(rec)
        _SERVE_RING.append(trace)


# ------------------------------------------------------------------ readers
def snapshot_events() -> list[dict]:
    """Copy of the buffered span records (seconds-domain ts/dur)."""
    with _LOCK:
        return list(_EVENTS)


def recent_serve_traces() -> list[dict]:
    """The bounded ring of recent serving traces, oldest first."""
    with _LOCK:
        return list(_SERVE_RING)


def configure_buffers(
    trace_buffer: int | None = None, serve_ring: int | None = None
) -> None:
    """Re-bound the in-process buffers (tests; production uses the
    ``TPTPU_TRACE_BUFFER`` / ``TPTPU_SERVE_TRACE_RING`` env knobs).
    Existing contents are kept up to the new bound."""
    global _EVENTS, _SERVE_RING
    with _LOCK:
        if trace_buffer is not None:
            _EVENTS = deque(_EVENTS, maxlen=max(1, trace_buffer))
        if serve_ring is not None:
            _SERVE_RING = deque(_SERVE_RING, maxlen=max(1, serve_ring))


def buffer_bounds() -> tuple[int, int]:
    return (_EVENTS.maxlen or 0, _SERVE_RING.maxlen or 0)


def reset_for_tests() -> None:
    """Clear buffers and the tid map; leaves enabled-state and clock."""
    with _LOCK:
        _EVENTS.clear()
        _SERVE_RING.clear()
        _TIDS.clear()
