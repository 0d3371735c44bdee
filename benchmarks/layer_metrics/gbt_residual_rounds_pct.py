"""Share of the window's boosting rounds whose tree was fitted to a
pseudo-residual of the margin so far: 100 x the sum of
``boost_rounds_residual`` over the sum of ``boost_rounds_label`` +
``boost_rounds_residual`` on the window's ``tree/await_outputs`` spans (the
fit program's own count, summed over lanes; Spark's boosting fits its first
tree to the labels themselves, so ``max_iter`` 2 reads 50 and 20 reads 95).
A program that does not count (one from before the counters, or one that
boosts in second order) gives none: never 0 by default."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    waits = program_spans.named(counters, "tree/await_outputs")
    if not waits:
        return None
    args = [e.get("args", {}) for e in waits]
    residual = sum(int(a.get("boost_rounds_residual", 0)) for a in args)
    rounds = residual + sum(int(a.get("boost_rounds_label", 0)) for a in args)
    if rounds <= 0:
        return None
    return 100.0 * residual / rounds
