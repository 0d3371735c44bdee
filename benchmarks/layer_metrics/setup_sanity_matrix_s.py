"""Seconds of set-up's cold train under ``sanity/matrix``, a child of the
SanityChecker's ``train/fit``: the float64 copy of the plane and the seeded
row sample the checker's statistics read. A program without the span gives
none."""
from benchmarks.lib import setup_spans


def read(trace, spans, counters, ctx):
    return setup_spans.seconds_under("SanityChecker", "sanity/matrix")
