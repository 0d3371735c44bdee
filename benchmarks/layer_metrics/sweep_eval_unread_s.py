"""Seconds per sweep the fold lanes spend on metrics the selector drops:
over the sweep's ``selector/evaluate`` spans, ``metric_seconds`` of every
group but the one ``reads`` names. A program without the attributes gives
none."""
from benchmarks.lib import sweep_eval


def read(trace, spans, counters, ctx):
    return sweep_eval.reading(counters, "unread")
