"""A fit family's share of its roofline, over the sweep's WHOLE device-busy
time (no fit kernel has a device-visible name yet): the least seconds the
chip needs for one sweep's work, counted by the file the configuration
names under ``"work"``, over the busy seconds per sweep. Each
``layer_metrics/<family>_fit_roofline.py`` is this reader under its name."""
from benchmarks.lib import by_name, peaks


def sweep_work(ctx):
    """(flops, bytes) of one sweep, by the configuration's work count."""
    return by_name("work", ctx.cfg["work"]).sweep_work(ctx.cfg, ctx.counters)


def read(trace, spans, counters, ctx):
    if not trace or not trace["busy_s"] or not trace["steps"]:
        return None
    flops, nbytes = sweep_work(ctx)
    least, _bound = peaks.roofline_seconds(flops, nbytes, ctx.device_kind)
    return 100.0 * least / (trace["busy_s"] / trace["steps"])
