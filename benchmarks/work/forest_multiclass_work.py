"""The least work a sweep of K-class random-forest fits requires, from
shapes alone: ``work/forest_work.py``'s count with K statistics a (lane,
row, column) and K + 1 words a row a lane, and NO binning bytes: a
re-sweep of a resident plane bins nothing (the codes are kept between
sweeps since PR 33), so counting the plane's read and the codes' write here
would count work no sweep of the window does. A roofline share divides the
least time the chip could take for this work by the device's busy time, so
counting too much here reads over 100%; the count is the minimum. A
configuration names this file under ``"work"``."""
from __future__ import annotations

import itertools


def forest_fit_work(rows: int, features: int, lanes_by_depth: dict,
                    trees: int, classes: int) -> tuple[float, float]:
    """(flops, bytes) of ``trees``-tree forests over lanes that share one
    binned matrix, ``lanes_by_depth[depth]`` lanes growing to each depth
    (one program a depth), on a label of ``classes`` classes.

    Per tree and level every lane adds K statistics (w and the indicators
    of K - 1 classes times w) into one bin per column: K adds per (lane,
    row, column). The level reads the bin codes once for all its lanes (one
    byte a code: 32 bins fit) and K + 1 4-byte words a row a lane (the K
    statistics and the node id). Split search, the subset draw and the
    bootstrap are left out: small beside the builds."""
    flops = nbytes = 0.0
    for depth, lanes in lanes_by_depth.items():
        levels = trees * int(depth)
        flops += float(classes) * lanes * rows * features * levels
        nbytes += levels * (
            rows * features * 1.0 + lanes * rows * 4.0 * (classes + 1))
    return flops, nbytes


def sweep_work(cfg: dict, counters: dict) -> tuple[float, float]:
    """(flops, bytes) of one sweep of ``cfg`` over the plane and the lanes
    the driver counted: the lanes are shared out over the grid's depths in
    proportion to their grid points."""
    grid = {**cfg["default_grid"], **cfg.get("grid", {})}
    rows, features = counters["plane_shape"]
    keys = sorted(grid)
    points = [dict(zip(keys, v))
              for v in itertools.product(*(grid[k] for k in keys))]
    per_point = counters["lanes"] / len(points)
    lanes_by_depth: dict = {}
    for p in points:
        d = int(p["max_depth"])
        lanes_by_depth[d] = lanes_by_depth.get(d, 0.0) + per_point
    return forest_fit_work(rows, features, lanes_by_depth,
                           int(grid["num_trees"][0]), int(cfg["classes"]))
