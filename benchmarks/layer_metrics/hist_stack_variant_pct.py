"""Value variants the histogram builds' operands carried, as a share of
the (hi, lo) pair of every statistic: 100 x the sum of ``stack_variants``
over the sum of ``stack_variants_full`` on the window's
``tree/await_outputs`` spans (the fit program's own count over every build
of every tree and level: the bin-loop kernel stacks one variant of each
statistic the fit states bfloat16-exact and two of any other). 100 where
nothing is stated exact; 75 where a boosted fit's hessian of 1 is; 50 where
every statistic is (a forest's ``lowp``). A program that does not count
(one from before the counters) gives none: never 0 by default."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    waits = program_spans.named(counters, "tree/await_outputs")
    if not waits:
        return None
    args = [e.get("args", {}) for e in waits]
    if not any("stack_variants" in a for a in args):
        return None
    carried = sum(int(a.get("stack_variants", 0)) for a in args)
    full = sum(int(a.get("stack_variants_full", 0)) for a in args)
    if full <= 0:
        return None
    return 100.0 * carried / full
