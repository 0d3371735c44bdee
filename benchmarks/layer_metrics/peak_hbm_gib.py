"""``peak_bytes_in_use`` of the fullest device after the window, in GiB."""


def read(trace, spans, counters, ctx):
    peak = counters.get("memory_peak_bytes")
    return peak / 2**30 if peak else None
