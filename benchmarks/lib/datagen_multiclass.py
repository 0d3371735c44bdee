"""The flagship table with a label of seven classes.

``flagship_table`` of ``lib/datagen.py`` makes the table (same columns, same
widths, same draws from the seed); this file replaces its 0/1 label by a
class id in 0 … 6 and edits nothing else. Every class has a score that is
linear in the table's own columns, as the plain reference reads them
(missing numerics are 0, a missing pick list or text adds nothing), plus
standard Gumbel noise drawn from the seed; the label is the arg max (ties:
lowest class), a multinomial logit.

The class shares follow UCI Covertype's seven cover types (about 48.8,
36.5, 6.2, 3.5, 3.0, 1.6 and 0.5 %: ``SHARES``; assumed from memory, this
sandbox has no network). ``BIAS`` was fitted once, on a table of 400,000
rows, so that the shares come out within a point of those; the constants
are fixed here and are not drawn per seed, so every seed gives the same
shares to a few tenths of a point and every fold holds every class.

The signal lies in MANY columns: every real and integral column, every
binary one and every level of every pick list tells the two large classes
apart and favours one small class, and every word of the text's vocabulary
favours one class (``TOKENS_OF_CLASS``; and the planted ``urgent``). A
forest's node sees ceil(sqrt(F)) random columns of the plane. A sparse
column (a hash bucket is non-zero in 2% of the rows) cannot lower the Gini
impurity of these shares by ``min_info_gain`` 0.001 a row however strong
its word; the 46 dense columns can, each alone, and 93% of the nodes draw
at least one of them, so the trees keep splitting to the depth the grid
allows (the thin trees of the binary cell found none: PERF.md
section 6, PR 27).

How many nodes such a forest grows follows the table it is drawn from: on
fresh tables a sweep builds between six and ten 128-slot chunks, 17.8 to
19.5 s (PERF.md section 6, PR 34), more than a cell's runs may spread. So
the cell draws ONE table, from the seed its configuration states
(``table_seed``), and a run's seed draws the NAMES of the classes
(``class_order``): the class ids are permuted and nothing else moves. The
K-class Gini, the gain and the stop rule are symmetric in the classes, so
every seed grows the same nodes on the same rows and does the same work,
while the label, the kernel's class channels, the leaves' vectors, the
arg max and its ties are another run's each time.
"""
from __future__ import annotations

import numpy as np

from benchmarks.lib import datagen

CLASSES = 7
#: UCI Covertype's class shares, per cent (assumed)
SHARES = (48.8, 36.5, 6.2, 3.5, 3.0, 1.6, 0.5)
#: class intercepts, fitted once to ``SHARES`` under the weights below
BIAS = (2.0, 5.402, 5.655, 4.783, 4.197, 3.538, 2.536)
#: The dense columns tell the two large classes apart (that is where the
#: Gini impurity of these shares lies): a column adds ``+w * value`` to
#: class 0's score and ``-w * value`` to class 1's, and ``v * value`` to one
#: small class, so that every class has columns of its own. One row a
#: column: (w, small class, v).
#: real_j, on the stored value (real_4 is log-normal: smaller weights)
REAL = ((1.0, 2, 0.9), (-1.0, 3, 0.9), (1.0, 4, 0.9), (-1.0, 5, 1.0),
        (0.3, 6, 0.3), (-1.0, 2, -0.9), (1.0, 6, 1.2), (-1.0, 3, -0.9),
        (1.0, 5, -0.9), (-1.0, 4, -0.9))
#: int_j, on (value - its Poisson mean) / sqrt(mean)
INTEGRAL = ((1.0, 3, 0.8), (-1.0, 4, 0.8), (1.0, 5, 0.9), (-1.0, 6, 1.0),
            (1.0, 2, 0.7))
INTEGRAL_MEAN = (2.0, 5.0, 9.0, 20.0, 40.0)
#: bin_j being true
BINARY = ((1.6, 4, 1.1), (-1.8, 2, 1.0), (1.6, 5, 1.0))
#: pick list j: level c adds ``PICK_WEIGHT[j]`` to class 0 (even c) or class
#: 1 (odd c), and ``PICK_SMALL`` to the small class 2 + (c + j) % 5
PICK_WEIGHT = (1.6, 1.8, 2.0, 2.2)
PICK_SMALL = 1.0
#: every word of the vocabulary favours one class: words are dealt to the
#: classes in these counts (w000 … w119 favour class 0, the next 100 class
#: 1, …), so that nearly every hash bucket of the text carries class signal
TOKENS_OF_CLASS = (120, 100, 50, 40, 40, 30, 20)
TOKEN_WEIGHT = 1.0
#: the planted token of a third of the rows
URGENT = (3, 1.0)


def _text_scores(text: np.ndarray, n: int) -> np.ndarray:
    """[n, CLASSES]: what each row's words add to each class's score (term
    counts: a word twice counts twice)."""
    out = np.zeros((n, CLASSES))
    present = np.nonzero(text != None)[0]  # noqa: E711 (elementwise)
    strings = text[present].tolist()
    counts = np.fromiter((s.count(" ") + 1 for s in strings), np.int64,
                         len(strings))
    tokens = np.array(" ".join(strings).split(" "), dtype="U6")
    row_of = np.repeat(present, counts)
    # the words are ``w`` and three digits, or ``urgent``
    points = tokens.view(np.uint32).reshape(len(tokens), 6)
    word = (points[:, 0] == ord("w")) & (points[:, 4] == 0)
    ids = ((points[:, 1] - 48) * 100 + (points[:, 2] - 48) * 10
           + (points[:, 3] - 48)).astype(np.int64)
    favoured = np.repeat(np.arange(CLASSES), TOKENS_OF_CLASS)  # word -> class
    np.add.at(out, (row_of[word], favoured[ids[word]]), TOKEN_WEIGHT)
    urgent = tokens == "urgent"
    np.add.at(out, (row_of[urgent], URGENT[0]), URGENT[1])
    return out


def class_scores(table: dict) -> np.ndarray:
    """[N, CLASSES] float64: every class's score before the noise."""
    n = len(table["label"])
    score = np.tile(np.asarray(BIAS, np.float64), (n, 1))

    def dense(values, row):
        w, small, v = row
        score[:, 0] += w * values
        score[:, 1] -= w * values
        score[:, small] += v * values

    for j, row in enumerate(REAL):
        dense(table[f"real_{j}"][0], row)
    for j, row in enumerate(INTEGRAL):
        values, present = table[f"int_{j}"]
        mean = INTEGRAL_MEAN[j]
        dense(np.where(present, (values - mean) / np.sqrt(mean), 0.0), row)
    for j, row in enumerate(BINARY):
        dense(table[f"bin_{j}"][0].astype(np.float64), row)
    for j, levels in enumerate(datagen.PICK_LEVELS):
        values = table[f"pick_{j}"]
        for c in range(levels):
            at = values == f"p{j}_{c}"
            score[:, c % 2] += PICK_WEIGHT[j] * at
            score[:, 2 + (c + j) % 5] += PICK_SMALL * at
    return score + _text_scores(table["text_0"], n)


def class_order(seed: int) -> np.ndarray:
    """The names a run's seed gives the classes: ``order[c]`` is the id
    under which the class of ``SHARES[c]`` appears in the label."""
    return np.random.default_rng([int(seed), CLASSES, 1]).permutation(CLASSES)


def multiclass_table(n_rows: int, seed: int, order=None) -> dict:
    """``flagship_table(n_rows, seed)`` with ``label`` a class id in
    0 … 6 (float64, as the binary label is): the class of ``SHARES[c]`` is
    ``c``, or ``order[c]`` where an order is given."""
    table = datagen.flagship_table(n_rows, seed)
    noise = np.random.default_rng([int(seed), CLASSES]).gumbel(
        size=(len(table["label"]), CLASSES))
    label = np.argmax(class_scores(table) + noise, axis=1)
    if order is not None:
        label = np.asarray(order)[label]
    table["label"] = label.astype(np.float64)
    return table
