"""Seconds of the set-up's one cold ``Workflow.train()`` (benchmark span)."""


def read(trace, spans, counters, ctx):
    found = ctx.span_seconds("cold_train")
    return found[0] if found else None
