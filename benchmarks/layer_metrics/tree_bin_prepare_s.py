"""Seconds per sweep under the program's ``tree/bin_prepare`` span
(``gbdt._TreeEstimator._binned``, cache hit or miss: thresholds, upload,
the binning program's dispatch, the 0/1-column scan)."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    return program_spans.seconds_per_sweep(counters, "tree/bin_prepare")
