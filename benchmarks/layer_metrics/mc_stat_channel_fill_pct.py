"""Share of the histogram kernel's statistic lanes that carry a statistic:
100 x the sum of ``stat_channels`` over the sum of ``stat_channels_built``
on the window's ``tree/fit_dispatch`` spans (the statistics a node of the
fit holds: K for a K-class forest; and those the kernel's stacked operand
has lanes for at the fit's narrowest build, whole 128-lane tiles). The
program reckons both from shapes whichever builder a fit takes, so a CPU
rehearsal reads it too. A program that does not state them (one from
before the statistic axis) gives none."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    fits = program_spans.named(counters, "tree/fit_dispatch")
    if not fits:
        return None
    args = [e.get("args", {}) for e in fits]
    built = sum(int(a.get("stat_channels_built", 0)) for a in args)
    if built <= 0:
        return None
    return 100.0 * sum(int(a.get("stat_channels", 0)) for a in args) / built
