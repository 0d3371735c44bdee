"""Per sweep, seconds from the step span's start to the first device op of
that step: row selection, fold masks, thresholds, upload."""


def read(trace, spans, counters, ctx):
    prepare = trace["step_prepare_s"] if trace else []
    return sum(prepare) / len(prepare) if prepare else None
