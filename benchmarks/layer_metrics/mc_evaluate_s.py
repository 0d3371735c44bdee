"""Seconds per sweep the selector spends scoring its lanes on the host,
with the device idle: the self time of the sweep's ``selector/evaluate``
spans (the fold lanes' K-wide outputs turned into probabilities and the
weighted F1, then the winner's train evaluation). A program that records
no such spans gives none."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    sweeps = program_spans.window_sweeps(counters)
    if sweeps is None:
        return None
    total, seen = 0.0, 0
    for _root, kids in sweeps:
        for span in kids:
            if span["name"] == "selector/evaluate":
                total += program_spans.self_seconds(span, kids)
                seen += 1
    return total / len(sweeps) if seen else None
