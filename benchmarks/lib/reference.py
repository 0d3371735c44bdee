"""The plain reference, and the comparison that decides ``correct``.

Nothing here imports the program, and nothing here is made by it: the
reference makes its own plane from the raw table, its own split of the
training rows into folds, and its own area under the precision-recall
curve. ``compare`` reads the numbers every cell has (the plane, the sweep's
grid points, the choice of winner); the numbers of one fit family (the
fold fits, their validation metrics, the winner's refit) are read by the
file the configuration names under ``"check"`` (``benchmarks/checks/``),
which brings that family's plain float32 ``jax.numpy`` reference.

``stand_in`` puts the reference in the program's place, computed at the
configuration's stated precision or one step below it: the control that
``compare`` has to fail (tests/bench/test_bench_control.py, PERF.md
section 2).
"""
from __future__ import annotations

import re

import numpy as np

HOLDOUT_FRACTION = 0.1   # Splitter.scala reserveTestFraction default
HASH_SEED = 42           # HashAlgorithm.MurMur3 seed (Spark HashingTF)
NULL = "NullIndicatorValue"


# ---------------------------------------------------------------- the plane
def train_rows(n: int, seed: int) -> np.ndarray:
    """The rows a default splitter leaves for training: a seeded
    permutation's tail, sorted (10% reserved as holdout)."""
    perm = np.random.default_rng(seed).permutation(n)
    return np.sort(perm[int(round(n * HOLDOUT_FRACTION)):])


def clean_string(raw: str) -> str:
    """TextUtils.cleanString: "p1_2" -> "P12"."""
    words = re.sub(r"[\W_]+", " ", raw.lower()).split()
    return "".join(w.capitalize() for w in words)


def murmur3_32(data: bytes, seed: int) -> int:
    """MurmurHash3 x86 32-bit, from the published algorithm."""
    mask = 0xFFFFFFFF
    h = seed & mask

    def mix(k):
        k = (k * 0xCC9E2D51) & mask
        k = ((k << 15) | (k >> 17)) & mask
        return (k * 0x1B873593) & mask

    n_full = len(data) // 4
    for i in range(n_full):
        h ^= mix(int.from_bytes(data[4 * i:4 * i + 4], "little"))
        h = ((h << 13) | (h >> 19)) & mask
        h = (h * 5 + 0xE6546B64) & mask
    tail = data[4 * n_full:]
    if tail:
        h ^= mix(int.from_bytes(tail, "little"))
    h ^= len(data)
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & mask
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & mask
    return h ^ (h >> 16)


def plane(table: dict, rows: np.ndarray, columns: list) -> np.ndarray:
    """float32 [len(rows), len(columns)]: what the Transmogrifier's default
    vectorizers give for each column the program says it kept.

    ``columns`` is (raw column, indicator value, descriptor) per plane
    column. Real: mean fill; Integral: mode fill (ties: smallest); Binary:
    false fill; each with a null indicator. PickList: one 0/1 column per
    cleaned value, null indicator. Text: term counts hashed into 512
    buckets, null indicator. Fills come from the training rows."""
    n = len(rows)
    out = np.zeros((n, len(columns)), dtype=np.float32)
    hashed: dict[str, dict[int, int]] = {}
    for j, (parent, indicator, descriptor) in enumerate(columns):
        col = table[parent]
        kind = parent.split("_")[0]
        if kind in ("real", "int", "bin"):
            values, present = col[0][rows], col[1][rows]
            if indicator == NULL:
                out[:, j] = ~present
            elif kind == "bin":
                out[:, j] = values & present
            else:
                seen = values[present].astype(np.float64)
                if kind == "real":
                    fill = seen.sum() / max(len(seen), 1)
                else:
                    vals, counts = np.unique(seen, return_counts=True)
                    fill = vals[np.argmax(counts)]
                out[:, j] = np.where(present, values, fill)
        elif kind == "pick":
            values = col[rows]
            if indicator == NULL:
                out[:, j] = values == None  # noqa: E711 (elementwise)
            else:
                levels = {
                    v for v in set(values.tolist())
                    if v is not None and clean_string(v) == indicator
                }
                out[:, j] = np.isin(values, list(levels))
        elif indicator == NULL:
            out[:, j] = col[rows] == None  # noqa: E711
        else:
            hashed.setdefault(parent, {})[int(descriptor.split("_")[1])] = j
    for parent, bucket_col in hashed.items():
        _hash_text(table[parent][rows], bucket_col, out)
    return out


def _hash_text(texts: np.ndarray, bucket_col: dict, out: np.ndarray) -> None:
    """Term counts of lower-cased tokens, murmur3 % 512. The benchmark's
    generator writes single-space-separated alphanumeric tokens, so a split
    on spaces is the tokenizer's result."""
    present = np.nonzero(texts != None)[0]  # noqa: E711
    strings = [s.lower() for s in texts[present].tolist()]
    counts = np.fromiter((s.count(" ") + 1 for s in strings), np.int64,
                         len(strings))
    tokens = " ".join(strings).split(" ")
    col_of = {
        t: bucket_col.get(murmur3_32(t.encode("utf-8"), HASH_SEED) % 512, -1)
        for t in set(tokens)
    }
    cols = np.fromiter((col_of[t] for t in tokens), np.int64, len(tokens))
    row_of = np.repeat(present, counts)
    keep = cols >= 0
    np.add.at(out, (row_of[keep], cols[keep]), 1.0)


# ------------------------------------------------- folds and the sweep metric
def fold_masks(n: int, seed: int, validator: dict) -> list:
    """[(train, validation)] boolean masks over the ``n`` training rows, as
    the stock validators state them: TrainValidationSplit draws one uniform
    number a row and trains on those under the ratio; CrossValidator deals
    a seeded permutation round-robin into the folds."""
    rng = np.random.default_rng(seed)
    if validator["kind"] == "TrainValidationSplit":
        train = rng.random(n) < float(validator["train_ratio"])
        return [(train, ~train)]
    if validator["kind"] == "CrossValidator":
        fold = rng.permutation(n) % int(validator["num_folds"])
        return [(fold != f, fold == f) for f in range(int(validator["num_folds"]))]
    raise ValueError(f"unknown validator {validator!r}")


def aupr(y: np.ndarray, score: np.ndarray) -> float:
    """Area under the precision-recall curve as mllib's
    BinaryClassificationMetrics states it: one point per distinct score,
    from the highest down, (0, precision at the first point) in front,
    trapezoids between."""
    y = np.asarray(y, np.float64)
    levels, at = np.unique(-np.asarray(score, np.float64), return_inverse=True)
    pos = np.cumsum(np.bincount(at, weights=y, minlength=len(levels)))
    seen = np.cumsum(np.bincount(at, minlength=len(levels)))
    if pos[-1] == 0:
        return 0.0
    precision, recall = pos / seen, pos / pos[-1]
    precision = np.concatenate([precision[:1], precision])
    recall = np.concatenate([[0.0], recall])
    return float(((recall[1:] - recall[:-1])
                  * (precision[1:] + precision[:-1]) / 2).sum())


def grid_points(cfg: dict) -> list[dict]:
    """The grid as it is run (the source's, with what the configuration
    cuts), one dict a point."""
    import itertools

    grid = {**cfg["default_grid"], **cfg.get("grid", {})}
    keys = sorted(grid)
    return [dict(zip(keys, values))
            for values in itertools.product(*(grid[k] for k in keys))]


def result_of(summary: dict, point: dict):
    """The sweep's result for one grid point, or None."""
    for r in summary["validationResults"]:
        if all(k in r["grid"] and float(r["grid"][k]) == float(v)
               for k, v in point.items()):
            return r
    return None


def lane_count(cfg: dict) -> int:
    """Fit lanes of one sweep: (folds + the refit lane) x grid points."""
    v = cfg["validator"]
    folds = int(v["num_folds"]) if v["kind"] == "CrossValidator" else 1
    return (folds + 1) * len(grid_points(cfg))


# ----------------------------------------------------------- the comparison
def round_bf16(a: np.ndarray) -> np.ndarray:
    """float32 rounded to bfloat16's 8 mantissa bits, to nearest even."""
    bits = np.ascontiguousarray(a, np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32)


def entry(name, value, limit):
    value = float(value)
    return {"name": name, "value": value, "limit": limit,
            "ok": bool(np.isfinite(value) and value <= limit)}


def build(cfg, table, columns, seed) -> dict:
    """What the reference makes once for a table: its plane over the
    columns the program says it kept, its labels, its folds."""
    rows = train_rows(len(table["label"]), seed)
    return {
        "x": plane(table, rows, columns),
        "y": table["label"][rows].astype(np.float32),
        "folds": fold_masks(len(rows), seed, cfg["validator"]),
        "seed": seed,
    }


def compare(cfg, ref, product) -> list[dict]:
    """Every number compared, each beside its limit (``cfg["limits"]``).
    ``product`` is what the timed path gave: ``plane`` (x, y, row_mask),
    the last sweep's ``summary``, its ``winner`` and what the program
    ``states`` of itself."""
    from benchmarks.lib import by_name

    limits, summary = cfg["limits"], product["summary"]
    out = []
    x_prog = np.asarray(product["plane"]["x"])
    gap = np.inf
    if ref["x"].shape == x_prog.shape:
        scale = np.maximum(np.abs(ref["x"]).max(axis=0), 1.0)
        gap = float((np.abs(x_prog - ref["x"]) / scale).max())
        gap = max(gap, float(
            np.abs(np.asarray(product["plane"]["y"]) - ref["y"]).max()))
    out.append(entry("plane_gap", gap, limits["plane_gap"]))
    del x_prog
    points = grid_points(cfg)
    found = [result_of(summary, p) for p in points]
    missing = sum(
        1 for r in found
        if r is None or len(r["metricValues"]) != len(ref["folds"])
        or not np.all(np.isfinite(r["metricValues"])))
    out.append(entry("grid_points_missing", missing,
                     limits["grid_points_missing"]))
    # the winner is the grid point whose mean validation metric (AuPR,
    # larger is better) is best
    means = [np.mean(r["metricValues"]) for r in found
             if r is not None and len(r["metricValues"])]
    won = result_of(summary, {
        k: v for k, v in summary["bestGrid"].items() if k in points[0]})
    wrong = not means or won is None or (
        np.mean(won["metricValues"]) != max(means))
    out.append(entry("winner_not_best", int(wrong), limits["winner_not_best"]))
    return out + by_name("checks", cfg["check"]).compare(cfg, ref, product)


def stand_in(cfg, ref, precision: dict) -> dict:
    """The reference in the program's place: a ``product`` made by the
    reference alone, its plane held at ``precision["plane"]`` and its fits
    computed at ``precision["fit"]`` ("f32" as stated, "bf16" one step
    below)."""
    from benchmarks.lib import by_name

    x = ref["x"] if precision["plane"] == "f32" else round_bf16(ref["x"])
    product = {"plane": {"x": x, "y": ref["y"],
                         "row_mask": np.ones(len(ref["y"]), np.float32)}}
    product.update(by_name("checks", cfg["check"]).stand_in(
        cfg, ref, x, precision["fit"]))
    return product
