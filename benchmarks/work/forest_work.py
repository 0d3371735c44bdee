"""The least work a sweep of random-forest fits requires, from shapes alone:
what the ALGORITHM needs (Spark's forest: any node may draw any column, so
every lane's histograms run over all F columns at every level), whatever
implements it. A roofline share divides the least time the chip could take
for this work by the device's busy time, so counting too much here reads
over 100%; the count is the minimum. A configuration names this file under
``"work"``."""
from __future__ import annotations

import itertools


def forest_fit_work(rows: int, features: int, lanes_by_depth: dict,
                    trees: int) -> tuple[float, float]:
    """(flops, bytes) of ``trees``-tree forests over lanes that share one
    binned matrix, ``lanes_by_depth[depth]`` lanes growing to each depth
    (one program a depth).

    Per tree and level every lane adds two statistics (w and w*y) into one
    bin per column: 2 adds per (lane, row, column). The level reads the bin
    codes once for all its lanes (one byte a code: 32 bins fit) and three
    4-byte words a row a lane (w, w*y, node id). Binning reads the float32
    plane once and writes the codes once per sweep. Split search, the
    subset draw and the bootstrap are left out: small beside the builds."""
    flops = nbytes = 0.0
    for depth, lanes in lanes_by_depth.items():
        levels = trees * int(depth)
        flops += 2.0 * lanes * rows * features * levels
        nbytes += levels * (rows * features * 1.0 + lanes * rows * 12.0)
    nbytes += rows * features * (4.0 + 1.0)
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
                           int(grid["num_trees"][0]))
