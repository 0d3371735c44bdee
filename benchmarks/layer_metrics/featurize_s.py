"""Host featurize: the cold train minus the selector's ``fit_arrays`` inside
it (reader, transmogrify, SanityChecker, holdout scoring)."""


def read(trace, spans, counters, ctx):
    train, fit = ctx.span_seconds("cold_train"), ctx.span_seconds("fit_arrays")
    return train[0] - fit[0] if train and fit else None
