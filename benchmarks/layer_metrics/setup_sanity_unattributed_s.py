"""Seconds of set-up's cold train that the SanityChecker's ``train/fit``
holds and none of its ``sanity/*`` children explains: the fit span's self
time. A program that does not break the fit down gives none."""
from benchmarks.lib import setup_spans


def read(trace, spans, counters, ctx):
    return setup_spans.unattributed("SanityChecker", "sanity/")
