"""Passes the histogram builds made over the codes, as a share of the lanes
they built: 100 x the sum of ``code_passes`` over the sum of
``lane_builds`` on the window's ``tree/await_outputs`` spans (the fit
program's own count over every build of every tree and level: the
bin-loop kernel builds the lanes that fit its stacked operand in one pass
over the codes, every other builder one lane a pass). 100 where no build
shares a pass; 25 where every build folds four lanes. A program that does
not count (one from before the counters) gives none: never 0 by
default."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    waits = program_spans.named(counters, "tree/await_outputs")
    if not waits:
        return None
    args = [e.get("args", {}) for e in waits]
    if not any("code_passes" in a for a in args):
        return None
    passes = sum(int(a.get("code_passes", 0)) for a in args)
    lanes = sum(int(a.get("lane_builds", 0)) for a in args)
    if lanes <= 0:
        return None
    return 100.0 * passes / lanes
