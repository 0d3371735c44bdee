"""``compileStats.programsCompiled`` over the window: anything but 0 means
a shape the set-up did not warm."""


def read(trace, spans, counters, ctx):
    return counters["window_compiles"]["programsCompiled"]
