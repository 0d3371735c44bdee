"""Share of the GLM fit's scanned iterations in which a lane moved: 100 x
the sum of ``iters_moved`` over the sum of ``iters_scanned`` on the
window's ``glm/await_outputs`` spans (the fit program's own count: the
scan runs ``max_iter`` iterations for every lane, and a lane whose line
search finds no lower objective stays where it is). A program that does
not count (one from before the counters) gives none: never 0 by default."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    waits = program_spans.named(counters, "glm/await_outputs")
    if not waits:
        return None
    args = [e.get("args", {}) for e in waits]
    scanned = sum(int(a.get("iters_scanned", 0)) for a in args)
    if scanned <= 0:
        return None
    return 100.0 * sum(int(a.get("iters_moved", 0)) for a in args) / scanned
