"""Programs the set-up paid a trace and compile (or a cache load) for:
``compileStats.programsCompiled`` over set-up."""


def read(trace, spans, counters, ctx):
    return counters["setup_compiles"]["programsCompiled"]
