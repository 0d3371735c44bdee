"""Set-up's one cold ``Workflow.train()``, by the program's own spans.

Set-up runs ONE ``Workflow.train()``: a ``train/run`` root and every record
that carries its id as ``trace``. Inside it the workflow opens a
``train/fit`` span [stage] for each estimator it fits; the readers here take
one stage's fit and the spans directly under it. The window's sweeps are
roots of their own (``selector/sweep``), so the train is found whatever the
window ran.

A program that records no such root, or a bounded buffer that may have
dropped a record of that train, gives ``None``: a reader reports a whole
sum or nothing.
"""
from __future__ import annotations

from benchmarks.lib import program_spans

ROOT = "train/run"


def cold_train() -> tuple[dict, list[dict]] | None:
    """(root record, its descendants) of set-up's one train, or None."""
    try:
        from transmogrifai_tpu.telemetry import spans
    except ImportError:
        return None
    events = spans.snapshot_events()
    roots = [
        e for e in events
        if e["name"] == ROOT and "id" in e and e.get("parent") is None
    ]
    if len(roots) != 1:
        return None
    (root,) = roots
    bound = getattr(spans, "buffer_bounds", lambda: (0, 0))()[0]
    if bound and len(events) >= bound:
        # a full buffer dropped its oldest records: none was of the train
        # only if the oldest record kept ended before the train began
        if events[0]["ts"] + events[0]["dur"] > root["ts"]:
            return None
    return root, [e for e in events if e.get("trace") == root["id"] and e is not root]


def children(stage: str, prefix: str) -> tuple[dict, list[dict], list[dict]] | None:
    """(the train's one ``train/fit`` record of ``stage``, the train's
    records, the fit's children whose name starts with ``prefix``), or None
    where there is no such fit or it has no such child."""
    train = cold_train()
    if train is None:
        return None
    _root, records = train
    fits = [
        e for e in records
        if e["name"] == "train/fit" and e.get("args", {}).get("stage") == stage
    ]
    if len(fits) != 1:
        return None
    (fit,) = fits
    kids = [
        e for e in records
        if e.get("parent") == fit["id"] and e["name"].startswith(prefix)
    ]
    return (fit, records, kids) if kids else None


def seconds_under(stage: str, name: str) -> float | None:
    """Seconds under the spans called ``name`` directly under ``stage``'s
    fit, or None where it has none."""
    found = children(stage, name)
    if found is None:
        return None
    durations = [e["dur"] for e in found[2] if e["name"] == name]
    return sum(durations) if durations else None


def unattributed(stage: str, prefix: str) -> float | None:
    """The self time of ``stage``'s fit (its duration less what its
    children cover), or None where it has no child whose name starts with
    ``prefix``: a program that does not break the fit down."""
    found = children(stage, prefix)
    if found is None:
        return None
    fit, records, _kids = found
    return program_spans.self_seconds(fit, records)
