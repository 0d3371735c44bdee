"""Share of the IRLS lanes that stopped before ``max_iter``: 100 x the sum
of ``lanes_stopped`` (by Spark's rule or the float32 floor) over the sum of
``lanes_irls`` (lanes that iterate: every pair but gaussian/identity) on
the window's ``glm/await_outputs`` spans. A program that does not count
them gives none."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    waits = program_spans.named(counters, "glm/await_outputs")
    args = [e.get("args", {}) for e in waits or ()]
    lanes = sum(int(a.get("lanes_irls", 0)) for a in args)
    if lanes <= 0:
        return None
    return 100.0 * sum(int(a.get("lanes_stopped", 0)) for a in args) / lanes
