"""What the window's sweeps spend scoring their lanes on the host, read from
the attributes the program puts on its spans.

``selector/evaluate`` [reads, metric_seconds]: ``metric_seconds`` holds the
seconds the evaluator spent on each group of metrics it computes together,
keyed by the metric the group exists for (``AuPR``, ``AuROC``, ``Counts``,
``ThresholdCurve``; ``F1``, ``TopKAccuracy``, ``ThresholdMetrics``);
``reads`` names the one the caller reads (the selector's metric in the fold
loops), or ``all`` (the refit's train evaluation, kept whole in the
summary). ``predict_seconds`` on ``selector/evaluate`` and
``selector/refit``: the host turning a fit's outputs into predictions and
probabilities.

A program without those attributes gives ``None`` for what it lacks.
"""
from __future__ import annotations

from benchmarks.lib import program_spans


def per_sweep(counters) -> dict[str, float | None] | None:
    """{"predict", "read", "unread": seconds per sweep or None}, or None
    where the window's sweeps cannot be read whole."""
    sweeps = program_spans.window_sweeps(counters)
    if sweeps is None:
        return None
    predict = read = unread = 0.0
    timed_predict = timed_metrics = False
    for _root, records in sweeps:
        for e in records:
            args = e.get("args", {})
            if "predict_seconds" in args:
                timed_predict = True
                predict += float(args["predict_seconds"])
            groups = args.get("metric_seconds")
            if e["name"] != "selector/evaluate" or not groups:
                continue
            timed_metrics = True
            reads = args.get("reads")
            for group, secs in groups.items():
                if reads == "all" or group == reads:
                    read += float(secs)
                else:
                    unread += float(secs)
    n = len(sweeps)
    return {
        "predict": predict / n if timed_predict else None,
        "read": read / n if timed_metrics else None,
        "unread": unread / n if timed_metrics else None,
    }


def reading(counters, part: str) -> float | None:
    got = per_sweep(counters)
    return None if got is None else got[part]
