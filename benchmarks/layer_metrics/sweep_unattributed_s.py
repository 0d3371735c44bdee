"""Seconds per sweep that the sweep driver's own spans hold and no child
span explains: the self time of ``selector/sweep``, ``selector/validate``
and ``selector/family`` (duration minus what their children cover)."""
from benchmarks.lib import program_spans

DRIVER = ("selector/sweep", "selector/validate", "selector/family")


def read(trace, spans, counters, ctx):
    sweeps = program_spans.window_sweeps(counters)
    if sweeps is None:
        return None
    total = 0.0
    for root, kids in sweeps:
        for span in [root, *kids]:
            if span["name"] in DRIVER:
                total += program_spans.self_seconds(span, kids)
    return total / len(sweeps)
