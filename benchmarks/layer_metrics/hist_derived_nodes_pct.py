"""Share of the window's live tree nodes whose histogram no build made: 100
x the sum of ``nodes_derived`` over the sum of ``nodes_built`` +
``nodes_derived`` on the window's ``tree/await_outputs`` spans (the fit
program's own count per tree and level, summed over lanes: live nodes whose
histogram came from a build, and live nodes whose histogram is their
parent's less their sibling's). A fit that builds one child of every split
reads near 50 where its trees are bushy (every node but the roots is one of
a pair) and less where lanes hold a root and little else. A program that
does not count (one from before the counters) gives none: never 0 by
default."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    waits = program_spans.named(counters, "tree/await_outputs")
    if not waits:
        return None
    args = [e.get("args", {}) for e in waits]
    if not any("nodes_derived" in a for a in args):
        return None
    derived = sum(int(a.get("nodes_derived", 0)) for a in args)
    nodes = derived + sum(int(a.get("nodes_built", 0)) for a in args)
    if nodes <= 0:
        return None
    return 100.0 * derived / nodes
