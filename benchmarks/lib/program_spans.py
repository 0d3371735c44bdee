"""The program's own spans, for the per-layer readers that report them.

The program records a span where its work happens
(``transmogrifai_tpu/telemetry/spans.py``): each record has an ``id``, the
``parent`` that caused it and the ``trace`` (its root's ``id``) it belongs
to. A sweep is one ``selector/sweep`` root and everything that carries its
id as ``trace``, on whatever thread it ran. The window's sweeps are the last
``counters["window"]["sweeps"]`` such roots: the benchmark's clock and the
program's are never compared.

A program that records no such spans (one from before they existed, or one
with its telemetry off) gives ``None``, and so does a bounded buffer that may
have dropped a record of one of those sweeps: a reader reports a whole sum
or nothing.
"""
from __future__ import annotations

ROOT = "selector/sweep"


def window_sweeps(counters) -> list[tuple[dict, list[dict]]] | None:
    """[(root record, its descendants)] of the window's sweeps, oldest
    first, or None where they cannot all be read whole."""
    try:
        from transmogrifai_tpu.telemetry import spans
    except ImportError:
        return None
    wanted = int((counters.get("window") or {}).get("sweeps", 0))
    events = spans.snapshot_events()
    roots = [
        e for e in events
        if e["name"] == ROOT and "id" in e and e.get("parent") is None
    ]
    if wanted < 1 or len(roots) < wanted:
        return None
    roots = roots[-wanted:]
    bound = getattr(spans, "buffer_bounds", lambda: (0, 0))()[0]
    if bound and len(events) >= bound:
        # the buffer is full, so it has dropped its oldest records, which
        # are those that ended first: none was of these sweeps only if
        # the oldest record kept ended before the first of them began
        if events[0]["ts"] + events[0]["dur"] > roots[0]["ts"]:
            return None
    return [
        (r, [e for e in events if e.get("trace") == r["id"] and e is not r])
        for r in roots
    ]


def seconds_per_sweep(counters, name: str) -> float | None:
    """Seconds under spans called ``name``, per sweep of the window."""
    sweeps = window_sweeps(counters)
    if sweeps is None:
        return None
    total = sum(e["dur"] for _r, kids in sweeps for e in kids
                if e["name"] == name)
    return total / len(sweeps)


def named(counters, name: str) -> list[dict] | None:
    """The window's records called ``name``, oldest first."""
    sweeps = window_sweeps(counters)
    if sweeps is None:
        return None
    return [e for _r, kids in sweeps for e in kids if e["name"] == name]


def self_seconds(span: dict, records: list[dict]) -> float:
    """A span's duration minus the part of it that its children cover
    (children of one span may overlap: a pool runs them side by side)."""
    lo, hi = span["ts"], span["ts"] + span["dur"]
    cut = sorted(
        (max(e["ts"], lo), min(e["ts"] + e["dur"], hi))
        for e in records if e.get("parent") == span["id"]
    )
    covered, at = 0.0, lo
    for s, e in cut:
        if e > at:
            covered += e - max(s, at)
            at = e
    return span["dur"] - covered
