"""Share of the window's bin preparations whose column statistics (quantile
order statistics, 0/1-column flags) were computed on the device: the
program's ``tree/thresholds`` spans with ``route == "device"`` over all of
them. A program that does not say (one from before the attribute) gives
none."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    misses = program_spans.named(counters, "tree/thresholds")
    if not misses:
        return None
    routes = [e.get("args", {}).get("route") for e in misses]
    if any(r is None for r in routes):
        return None
    return 100.0 * sum(r == "device" for r in routes) / len(routes)
