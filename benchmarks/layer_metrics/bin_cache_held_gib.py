"""GiB of bin codes the program's bin cache keeps on the device after the
window's last look-up (``cache_device_bytes`` of the last
``tree/bin_prepare`` span)."""
from benchmarks.lib import program_spans


def read(trace, spans, counters, ctx):
    lookups = program_spans.named(counters, "tree/bin_prepare")
    if not lookups:
        return None
    held = lookups[-1].get("args", {}).get("cache_device_bytes")
    return None if held is None else held / 2**30
