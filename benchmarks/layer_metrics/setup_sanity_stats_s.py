"""Seconds of set-up's cold train under ``sanity/stats``, a child of the
SanityChecker's ``train/fit``: the column statistics and the correlation
matrix (Pearson or Spearman, the span's ``kind``). A program without the
span gives none."""
from benchmarks.lib import setup_spans


def read(trace, spans, counters, ctx):
    return setup_spans.seconds_under("SanityChecker", "sanity/stats")
