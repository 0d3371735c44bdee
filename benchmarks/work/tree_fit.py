"""The least work a sweep of histogram-boosted tree fits requires, from
shapes alone: what the algorithm needs, not what the program happens to do.
A roofline share divides the least time the chip could take for this work
by the device's busy time, so counting too much here reads over 100%; the
count is the minimum. A configuration names this file under ``"work"``."""
from __future__ import annotations


def tree_fit_work(rows: int, features: int, lanes: int, rounds: int,
                  depth: int) -> tuple[float, float]:
    """(flops, bytes) of histogram gradient boosting over ``lanes`` fits
    that share one binned matrix.

    Per round and level every lane adds each row's gradient and hessian
    into one bin per feature: 2 adds per (lane, row, feature). The level
    reads the bin codes once for all lanes (one byte a code: 32 bins fit)
    and each lane's gradient, hessian and node id (three 4-byte words a
    row). Binning itself reads the float32 plane once and writes the codes
    once per sweep. Split search over [nodes, features, bins] is left out:
    it is small beside the histogram build."""
    levels = rounds * depth
    flops = 2.0 * lanes * rows * features * levels
    nbytes = levels * (rows * features * 1.0 + lanes * rows * 12.0)
    nbytes += rows * features * (4.0 + 1.0)
    return flops, nbytes


def sweep_work(cfg: dict, counters: dict) -> tuple[float, float]:
    """(flops, bytes) of one sweep of ``cfg`` over the plane and the lanes
    the driver counted."""
    grid = {**cfg["default_grid"], **cfg.get("grid", {})}
    rows, features = counters["plane_shape"]
    return tree_fit_work(rows, features, counters["lanes"],
                         int(grid["num_round"][0]), int(grid["max_depth"][0]))
