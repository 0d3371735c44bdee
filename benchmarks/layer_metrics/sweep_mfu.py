"""The whole step's share of the chip's bf16 peak: required flops of one
sweep (the configuration's work count) over the traced seconds per sweep,
host time included."""
from benchmarks.lib import peaks, roofline


def read(trace, spans, counters, ctx):
    if not trace or not trace["busy_s"] or not trace["steps"]:
        return None
    flops, _nbytes = roofline.sweep_work(ctx)
    sweep_s = trace["window_s"] / trace["steps"]
    return 100.0 * flops / (sweep_s * peaks.peaks_for(ctx.device_kind)["bf16_flops"])
