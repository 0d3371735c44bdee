"""The benchmark's own table: the flagship binary-classification schema,
made column-wise from the seed.

A copy of the schema of ``transmogrifai_tpu.testkit.flagship_dataset`` (ten
Real and five Integral with missing values, three Binary, four PickList,
one free Text field that the hashing vectorizer takes, a 0/1 label that
depends on several of them). It is a copy so that the program may change
its testkit without moving the yardstick. The raw numpy columns are
what the plain reference reads; ``to_dataset`` wraps the same arrays in the
program's column types for the system under test.
"""
from __future__ import annotations

import numpy as np

REAL, INTEGRAL, BINARY = 10, 5, 3
PICK_LEVELS = (3, 5, 8, 12)
VOCAB = 400


def flagship_table(n_rows: int, seed: int) -> dict:
    """{"label": float64 [N], name: (values, present-mask) | object array}.

    Numeric columns are ``(values, present)`` pairs (missing = present
    False, value 0); PickList and Text columns are object arrays with
    ``None`` for missing."""
    rng = np.random.default_rng(seed)
    n = int(n_rows)
    table: dict = {}
    reals = rng.normal(size=(n, REAL))
    reals[:, 4] = np.exp(reals[:, 4])
    for j in range(REAL):
        present = rng.random(n) > 0.08
        table[f"real_{j}"] = (np.where(present, reals[:, j], 0.0), present)
    ints = rng.poisson(lam=[2.0, 5.0, 9.0, 20.0, 40.0], size=(n, INTEGRAL))
    for j in range(INTEGRAL):
        present = rng.random(n) > 0.05
        table[f"int_{j}"] = (
            np.where(present, ints[:, j], 0).astype(np.int64), present
        )
    bins = rng.random((n, BINARY)) < np.array([0.5, 0.2, 0.7])
    for j in range(BINARY):
        present = rng.random(n) > 0.05
        table[f"bin_{j}"] = (bins[:, j] & present, present)
    picks = []
    for j, levels in enumerate(PICK_LEVELS):
        code = rng.integers(0, levels, size=n)
        picks.append(code)
        values = np.array(
            [f"p{j}_{c}" for c in range(levels)], dtype=object
        )[code]
        values[rng.random(n) < 0.05] = None
        table[f"pick_{j}"] = values
    vocab = np.array([f"w{i:03d}" for i in range(VOCAB)], dtype="U6")
    tokens = vocab[rng.integers(0, VOCAB, size=(n, 8))]
    lengths = rng.integers(3, 9, size=n)
    urgent = rng.random(n) < 0.3
    tokens[:, 0] = np.where(urgent, "urgent", tokens[:, 0])
    text = np.array(
        [" ".join(row[:k]) for row, k in zip(tokens.tolist(), lengths.tolist())],
        dtype=object,
    )
    text[rng.random(n) < 0.05] = None
    table["text_0"] = text
    logit = (
        1.1 * reals[:, 0] - 0.8 * reals[:, 1]
        + 1.5 * (reals[:, 2] > 0.3) * (reals[:, 3] < 0.0)
        + 0.05 * (ints[:, 2] - 9.0)
        + 0.6 * bins[:, 0]
        + 0.9 * (picks[1] == 2) - 0.7 * (picks[3] >= 9)
        + 0.8 * urgent
        - 0.9
    )
    table["label"] = (logit + rng.logistic(size=n) > 0.0).astype(np.float64)
    return table


def to_dataset(table: dict):
    """The same arrays as the program's typed ``Dataset``."""
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.dataset import Dataset
    from transmogrifai_tpu.types.columns import NumericColumn, TextColumn

    kinds = {"real": T.Real, "int": T.Integral, "bin": T.Binary}
    n = len(table["label"])
    cols = {
        "label": NumericColumn(
            T.RealNN, table["label"], np.ones(n, dtype=bool)
        )
    }
    for name, col in table.items():
        if name == "label":
            continue
        prefix = name.split("_")[0]
        if prefix in kinds:
            cols[name] = NumericColumn(kinds[prefix], col[0], col[1])
        else:
            cols[name] = TextColumn(
                T.PickList if prefix == "pick" else T.Text, col
            )
    return Dataset.of(cols)
