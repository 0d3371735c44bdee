"""The flagship table with a positive amount for its label.

``flagship_table`` of ``lib/datagen.py`` makes the table (same columns, same
widths, same draws from the seed); this file replaces its 0/1 label by a
positive amount, as an order value, a claim's severity or a deal's size is,
and edits nothing else. Its mean is LINEAR in the table's dense columns and
pick-list levels as the plain reference reads them (a missing numeric is
0, a missing pick list adds nothing; the text adds nothing), and the amount
is that mean times gamma noise of mean 1::

    μ*(x) = max(MU_MIN, BASE + Σ weights · columns)
    y     = μ*(x) · G / SHAPE,   G ~ Gamma(SHAPE, 1)

so Var(y | x) = μ*(x)² / SHAPE: the spread grows with the amount, as it
does in the actuarial and pricing data a GLM is fitted to. The constants
are fixed here and drawn from nothing; ``MU_MIN`` is a floor the linear
part stays above on the cell's table (``floor_rows`` counts the rows it
lifts: 0, ``tests/bench/test_bench_glr_control.py``), so the mean is
linear where the rows are, every identity-link fit sees a positive mean,
and a poisson lane's start μ⁰ = y never meets Tweedie's δ (y > 0).

The noise is drawn from the table's own seed, so ONE table (the
configuration's ``table_seed``) gives one label: a run's ``--seed`` draws
nothing here (``configs/flagship_glr.json``, ``seed_draws``).
"""
from __future__ import annotations

import numpy as np

from benchmarks.lib import datagen

#: the mean's intercept, and the floor it is kept above
BASE, MU_MIN = 40.0, 5.0
#: gamma noise: shape (CV 1/sqrt(SHAPE) = 0.5)
SHAPE = 4.0
#: real_j, on the stored value (real_4 is log-normal: a positive weight)
REAL = (2.2, -1.8, 1.5, -1.2, 0.9, 1.6, -1.5, 1.3, -1.0, 0.8)
#: int_j, on (value - its Poisson mean) / sqrt(mean)
INTEGRAL = (1.5, -1.8, 1.2, -1.3, 1.6)
INTEGRAL_MEAN = (2.0, 5.0, 9.0, 20.0, 40.0)
#: bin_j being true
BINARY = (3.0, -2.2, 1.8)
#: pick list j: level c adds PICK[j] · ((c % 3) - 1)
PICK = (3.0, -2.2, 2.7, 1.8)


def linear_mean(table: dict) -> np.ndarray:
    """[N] float64: BASE + the weighted columns, before the floor."""
    n = len(table["label"])
    mean = np.full(n, BASE)
    for j, w in enumerate(REAL):
        mean += w * table[f"real_{j}"][0]
    for j, w in enumerate(INTEGRAL):
        values, present = table[f"int_{j}"]
        m = INTEGRAL_MEAN[j]
        mean += w * np.where(present, (values - m) / np.sqrt(m), 0.0)
    for j, w in enumerate(BINARY):
        mean += w * table[f"bin_{j}"][0].astype(np.float64)
    for j, levels in enumerate(datagen.PICK_LEVELS):
        values = table[f"pick_{j}"]
        for c in range(levels):
            mean += PICK[j] * ((c % 3) - 1) * (values == f"p{j}_{c}")
    return mean


def floor_rows(table: dict) -> int:
    """Rows whose linear mean lies under ``MU_MIN`` (the floor lifts)."""
    return int((linear_mean(table) < MU_MIN).sum())


def regression_table(n_rows: int, seed: int) -> dict:
    """``flagship_table(n_rows, seed)`` with ``label`` a positive amount
    (float64)."""
    table = datagen.flagship_table(n_rows, seed)
    mu = np.maximum(linear_mean(table), MU_MIN)
    noise = np.random.default_rng([int(seed), 5, 1]).gamma(
        SHAPE, 1.0 / SHAPE, size=len(mu))
    table["label"] = mu * noise
    return table
