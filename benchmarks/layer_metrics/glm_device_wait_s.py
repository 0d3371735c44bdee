"""Seconds per sweep the host waited in ``glm/await_outputs``: for the
multinomial fit program's coefficients and counts, then for the fold
lanes' validation rows and the refit lane's logits (the program's own
spans; none from a program without them)."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    if not program_spans.named(counters, "glm/await_outputs"):
        return None
    return program_spans.seconds_per_sweep(counters, "glm/await_outputs")
