"""Seconds of set-up's cold train under ``sanity/categorical``, a child of
the SanityChecker's ``train/fit``: the contingency tables of the categorical
groups, their Cramér's V and rule confidences. A program without the span
gives none."""
from benchmarks.lib import setup_spans


def read(trace, spans, counters, ctx):
    return setup_spans.seconds_under("SanityChecker", "sanity/categorical")
