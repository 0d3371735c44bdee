"""The IRLS GLM fit's share of its roofline (``lib/roofline.py``): none
from a program whose ``glm/await_outputs`` spans do not count the
iterations its lanes needed."""
from benchmarks.lib import roofline
from benchmarks.work import glr_irls_work


def read(trace, spans, counters, ctx):
    if glr_irls_work.irls_iterations(counters) is None:
        return None
    return roofline.read(trace, spans, counters, ctx)
