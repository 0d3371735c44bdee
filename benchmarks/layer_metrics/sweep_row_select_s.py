"""Seconds per sweep under the program's ``selector/row_select`` span: the
training rows' indices, the ``x[train_idx]`` / ``y[train_idx]`` copies, the
splitter's ``prepare`` and the refit mask."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    return program_spans.seconds_per_sweep(counters, "selector/row_select")
