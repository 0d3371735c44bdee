"""Share of ``tree/partition``'s row-wide passes that ran: 100 x the sum of
``partition_run`` over the sum of ``partition_run`` + ``partition_skipped``
on the window's ``tree/await_outputs`` spans (the fit program's own count
per tree and level of its two passes over the rows, the compaction of
their nodes and their routing to the children; a level where no lane can
split skips them). 100 where every level of every tree splits somewhere; a
forest of stumps at depth 12 runs 3 passes of 24 a tree. A program that
does not count (one from before the counters) gives none: never 0 by
default."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    waits = program_spans.named(counters, "tree/await_outputs")
    if not waits:
        return None
    args = [e.get("args", {}) for e in waits]
    if not any("partition_run" in a for a in args):
        return None
    ran = sum(int(a.get("partition_run", 0)) for a in args)
    passes = ran + sum(int(a.get("partition_skipped", 0)) for a in args)
    if passes <= 0:
        return None
    return 100.0 * ran / passes
