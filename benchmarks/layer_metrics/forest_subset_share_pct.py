"""Share of the (live node, feature) pairs the forest's split search
admitted: 100 x the sum of ``subset_admitted`` over the sum of
``subset_pairs`` on the window's ``tree/await_outputs`` spans. Per-node
subsets of ceil(sqrt(F)) columns read 100 x ceil(sqrt(F)) / F (5.3 at 19 of
357); a program that searches every column at every node reads 100. A
program that does not count (one from before the counter) gives none."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    waits = program_spans.named(counters, "tree/await_outputs")
    if not waits:
        return None
    args = [e.get("args", {}) for e in waits]
    pairs = sum(int(a.get("subset_pairs", 0)) for a in args)
    if pairs <= 0:
        return None
    return 100.0 * sum(int(a.get("subset_admitted", 0)) for a in args) / pairs
