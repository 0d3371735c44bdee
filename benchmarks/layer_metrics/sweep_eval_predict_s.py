"""Seconds per sweep the host spends turning the fits' outputs into
predictions and probabilities before they are scored: the sum of
``predict_seconds`` on the sweep's ``selector/evaluate`` spans (the fold
lanes' validation rows sliced out of the outputs, then
``predictions_from_sweep``) and on ``selector/refit`` (the winner's refit
lane on every training row). A program without the attribute gives none."""
from benchmarks.lib import sweep_eval


def read(trace, spans, counters, ctx):
    return sweep_eval.reading(counters, "predict")
