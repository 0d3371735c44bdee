"""Share of the IRLS program's lane-iterations that a lane needed: 100 x
the sum of ``iters_needed`` (each lane's iterations to its stop) over the
sum of ``iters_run`` (lanes x the iterations the program ran: a stopped
lane, and a gaussian/identity lane that needs none, still count) on the
window's ``glm/await_outputs`` spans. A program that does not count them
gives none: never 0 by default."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    waits = program_spans.named(counters, "glm/await_outputs")
    args = [e.get("args", {}) for e in waits or ()]
    run = sum(int(a.get("iters_run", 0)) for a in args)
    if run <= 0:
        return None
    return 100.0 * sum(int(a.get("iters_needed", 0)) for a in args) / run
