"""Seconds per sweep under the program's ``tree/thresholds`` span (the
quantile bin edges, computed on the host at every cache miss)."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    return program_spans.seconds_per_sweep(counters, "tree/thresholds")
