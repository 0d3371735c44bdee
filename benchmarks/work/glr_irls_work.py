"""The least work a sweep of batched IRLS generalized-linear-regression fits
requires (``models/solvers.fit_glr_irls_batched`` as the configuration's
``learner`` states it), counted from the learner's own needs so that it
reads the same whatever implements it: the products over the plane at ONE
pass of the chip's bfloat16 peak. A configuration names this file under
``"work"``.

Per sweep, N rows × D columns, lanes on row masks of N_m rows:

* the unweighted Gram of each of the two row masks (the fold's training
  rows, the refit rows), 2·N_m·(D+1)², once: every lane's start and every
  gaussian/identity lane's closed form;
* every lane's start right-hand side, 2·N_m·(D+1);
* for each lane, the IRLS iterations IT needed to its stop (the program's
  ``iters_needed``, not the lane-iterations it ran): a weighted Gram
  2·N_m·(D+1)², its right-hand side 2·N_m·(D+1) and η, 2·N_m·D;
* every row's fitted mean, every lane, 2·N·D·K, once.

Bytes, the least the learner can move: the plane read once for the start,
once for every iteration the slowest lane needed (``iters_longest``) and
once for the scores, and the fitted means [K, N] written once. Masks,
labels and the [K, D, D] solves are left out: small beside the plane, so
the count is a minimum and a roofline share over it cannot pass 100%.

``iters_needed`` and ``iters_longest`` come from the window's
``glm/await_outputs`` spans; a program that does not count them gives the
count of a sweep whose lanes need no iteration (the least there is)."""
from __future__ import annotations

from benchmarks.lib import program_spans


def irls_iterations(counters) -> tuple[float, float] | None:
    """(Σ iters_needed, Σ iters_longest) per sweep of the window, or None."""
    waits = program_spans.named(counters, "glm/await_outputs")
    args = [e.get("args", {}) for e in waits or ()]
    args = [a for a in args if "iters_needed" in a]
    if not args:
        return None
    sweeps = int(counters["window"]["sweeps"])
    return (sum(int(a["iters_needed"]) for a in args) / sweeps,
            sum(int(a["iters_longest"]) for a in args) / sweeps)


def glr_fit_work(rows: int, cols: int, lanes: int, fold_rows: float,
                 refit_rows: float, needed: float,
                 longest: float) -> tuple[float, float]:
    """(flops, bytes) of one IRLS program over ``lanes`` lanes on the
    fold's rows and the refit rows; a lane's own work is counted at the
    fewer of the two."""
    n, d, k = rows, cols, lanes
    grams = 2.0 * (fold_rows + refit_rows) * (d + 1) ** 2
    lane_rows = min(fold_rows, refit_rows)
    flops = grams + k * 2.0 * lane_rows * (d + 1)
    flops += needed * (2.0 * lane_rows * (d + 1) ** 2
                       + 2.0 * lane_rows * (d + 1) + 2.0 * lane_rows * d)
    flops += 2.0 * n * d * k
    plane = 4.0 * n * d
    nbytes = (2 + longest) * plane + 4.0 * k * n
    return flops, nbytes


def sweep_work(cfg: dict, counters: dict) -> tuple[float, float]:
    """(flops, bytes) of one sweep of ``cfg``: one program over every lane
    the driver counted, on the plane it ran on."""
    rows, cols = counters["plane_shape"]
    got = irls_iterations(counters) or (0.0, 0.0)
    fold = float(cfg["validator"]["train_ratio"]) * rows
    refit = min(rows, int(cfg["splitter"]["max_training_sample"]))
    return glr_fit_work(rows, cols, int(counters["lanes"]), fold, refit,
                        *got)
