"""Share of the histogram builds' node slots that held a live node: 100 x
the sum of ``slots_live`` over the sum of ``slots_built`` on the window's
``tree/await_outputs`` spans (per tree and level, the live compact slots
of the widest lane and the width the level's builds were made at). A fit
whose levels are all built at one fixed width reads the mean level's
share of it; one that sizes each level for its live nodes reads near 100.
A program that does not count (one from before the counter) gives none."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    waits = program_spans.named(counters, "tree/await_outputs")
    if not waits:
        return None
    args = [e.get("args", {}) for e in waits]
    built = sum(int(a.get("slots_built", 0)) for a in args)
    if built <= 0:
        return None
    return 100.0 * sum(int(a.get("slots_live", 0)) for a in args) / built
