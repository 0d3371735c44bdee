"""Share of the window's bin-cache look-ups that hit: the program's
``tree/bin_prepare`` spans with ``cache == "hit"`` over all of them."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    lookups = program_spans.named(counters, "tree/bin_prepare")
    if not lookups:
        return None
    hits = sum(1 for e in lookups if e.get("args", {}).get("cache") == "hit")
    return 100.0 * hits / len(lookups)
