"""Seconds per sweep the host spent blocked on the device: the program's
``tree/await_outputs`` spans (every host read of a device result on the
sweep path). The reverse of ``sweep_prepare_s``, which is how long the
device waits for the host."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    return program_spans.seconds_per_sweep(counters, "tree/await_outputs")
