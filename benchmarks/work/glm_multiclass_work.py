"""The least work a sweep of batched multinomial logistic-regression fits
requires, from shapes alone (``models/solvers.fit_logistic_multinomial_batched``
as the configuration's ``learner`` states it): the products over the
plane, at ONE pass of the chip's bfloat16 peak, for every iteration the
scan runs (``max_iter``: it runs them all, a frozen lane too). A
configuration names this file under ``"work"``.

Per sweep, N rows × D columns, K lanes, C classes, T step candidates, I
iterations:

* moments: two [K, N] × [N, D] products (the lanes' sums and squares) and
  the class counts, once;
* per iteration: the line search's ONE [N, D] × [D, T·K·C] product, the
  logits [N, D] × [D, K·C] and the gradient [D, N] × [N, K·C] at the point
  taken;
* the start's logits and gradient, and the scores (the logits at the fit)
  once.

Bytes, the least a product can move: the plane read once a product
(float32), the candidate logits [N, T·K·C] written once and read once a
line search, and the logits [N, K·C] written and read for the gradient.
Moments' mask reads, the two-loop recursion on [K, D·C] and the masked
min/max scans are left out: small beside the products, so the count is a
minimum and a roofline share over it cannot pass 100%."""
from __future__ import annotations


def glm_fit_work(rows: int, cols: int, lanes: int, classes: int,
                 iters: int, candidates: int) -> tuple[float, float]:
    """(flops, bytes) of one multinomial fit program."""
    n, d, k, c, t = rows, cols, lanes, classes, candidates
    plane = 4.0 * n * d
    flops = 2.0 * 2 * k * n * d + 2.0 * k * n * c          # moments, counts
    nbytes = 2 * plane
    per_iter_flops = 2.0 * n * d * (t * k * c) + 2 * 2.0 * n * d * (k * c)
    per_iter_bytes = 3 * plane + 2 * 4.0 * n * t * k * c + 2 * 4.0 * n * k * c
    flops += iters * per_iter_flops
    nbytes += iters * per_iter_bytes
    # the start's logits and gradient, the scores at the fit
    flops += 3 * 2.0 * n * d * k * c
    nbytes += 3 * plane + 3 * 4.0 * n * k * c
    return flops, nbytes


def sweep_work(cfg: dict, counters: dict) -> tuple[float, float]:
    """(flops, bytes) of one sweep of ``cfg``: one program over every lane
    the driver counted, on the plane it ran on."""
    grid = {**cfg["default_grid"], **cfg.get("grid", {})}
    rows, cols = counters["plane_shape"]
    return glm_fit_work(
        rows, cols, int(counters["lanes"]), int(cfg["classes"]),
        int(grid["max_iter"][0]), int(cfg["line_search_candidates"]))
