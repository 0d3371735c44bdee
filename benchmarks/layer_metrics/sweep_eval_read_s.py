"""Seconds per sweep the evaluators spend on metrics their caller reads:
over the sweep's ``selector/evaluate`` spans, ``metric_seconds`` of the
group that ``reads`` names (the selector's metric, in the fold lanes), or of
every group where ``reads`` is ``all`` (the refit's train evaluation, which
goes whole into the summary). A program without the attributes gives
none."""
from benchmarks.lib import sweep_eval


def read(trace, spans, counters, ctx):
    return sweep_eval.reading(counters, "read")
