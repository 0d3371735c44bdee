"""The least work a sweep of Spark gradient-boosted-tree fits requires, from
shapes alone: ``work/forest_work.py``'s count with a boosting ROUND where
the forest has a tree (each round grows one tree a lane over all F columns
at every level to the lane's depth, adding two statistics a row: the target
and the row count). The targets' exp, the split search and the margin update
are left out: small beside the builds. The trees may stop short of their
depth; the count is of the algorithm at full depth, the minimum for a lane
that grows to it. A configuration names this file under ``"work"``."""
from __future__ import annotations

from benchmarks.lib.reference import grid_points
from benchmarks.work.forest_work import forest_fit_work


def sweep_work(cfg: dict, counters: dict) -> tuple[float, float]:
    """(flops, bytes) of one sweep of ``cfg`` over the plane and the lanes
    the driver counted, shared out over the grid's depths in proportion to
    their grid points; rounds from ``max_iter``."""
    rows, features = counters["plane_shape"]
    points = grid_points(cfg)
    per_point = counters["lanes"] / len(points)
    lanes_by_depth: dict = {}
    for p in points:
        d = int(p["max_depth"])
        lanes_by_depth[d] = lanes_by_depth.get(d, 0.0) + per_point
    return forest_fit_work(rows, features, lanes_by_depth,
                           int(points[0]["max_iter"]))
