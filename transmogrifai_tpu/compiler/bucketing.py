"""Shape buckets for batched candidate sweeps (cross-candidate dedup).

A GLM sweep's compiled program is keyed by its LANE COUNT K (folds ×
same-static grid points + refit lanes): a 24-lane and a 28-lane sweep are
different XLA programs even though every lane runs identical math. Each
extra program costs an acquisition (trace, compile, load), so near-miss
lane counts are padded up to a small set of buckets — the padded sweep
replays lane 0 in the inert lanes and the caller slices the real lanes
back out.

Lanes in the batched GLM solvers are independent GEMM columns, so padding
changes no real lane's math; any residual difference is at the level of
XLA's per-shape GEMM tiling (measured bit-identical on XLA:CPU, documented
as <=1e-6 relative tolerance in docs/tpu.md for other backends). Tree
sweeps do NOT bucket: split decisions are discrete, and a reassociated
histogram sum can flip a borderline split — there the lane count already
equals the static-group size, which the dedup ledger records instead.

Buckets: powers of two up to 64, then multiples of 32 (<=2x compute
blowup, bounded program count). ``TPTPU_LANE_BUCKETS=0`` disables padding.
"""
from __future__ import annotations

import os

import numpy as np

_POW2_CAP = 64
_STEP = 32


def enabled() -> bool:
    return os.environ.get("TPTPU_LANE_BUCKETS", "1") != "0"


def lane_bucket(k: int) -> int:
    """Smallest bucket >= k (identity when padding is disabled or k<=1)."""
    if k <= 1 or not enabled():
        return k
    if k <= _POW2_CAP:
        b = 1
        while b < k:
            b *= 2
        return b
    return -(-k // _STEP) * _STEP


def mesh_lane_bucket(k: int, multiple: int = 1) -> int:
    """Smallest lane bucket >= k that ``multiple`` divides evenly — the
    sharded sweep's variant of :func:`lane_bucket`: lanes shard over the
    mesh's model axis, so the padded lane count must split into equal
    per-device blocks. With padding disabled the bucket degrades to the
    plain ceiling multiple (divisibility is a correctness requirement of
    the sharded dispatch, not an optimization)."""
    multiple = max(1, int(multiple))
    b = max(lane_bucket(k), multiple)
    while b % multiple:
        nb = lane_bucket(b + 1)
        b = nb if nb > b else b + 1
    return b


def bucket_sweep_lanes(
    *arrays: np.ndarray, multiple: int = 1
) -> tuple[int, tuple]:
    """The whole per-sweep sequence in one place (shared by the logistic
    and linear batched-masks sweeps, so the pad/record semantics cannot
    drift between them): bucket the lane count of axis 0 (rounded up to
    ``multiple`` when the lanes shard over a model axis of that size),
    pad every array onto it by replicating lane 0, and record
    (lanes, padded) in the compileStats ledger. Returns
    ``(k, padded_arrays)`` — callers slice program outputs back with
    ``[:k]``."""
    from . import stats

    arrays = tuple(np.asarray(a) for a in arrays)
    k = arrays[0].shape[0]
    bucket = (
        mesh_lane_bucket(k, multiple) if multiple > 1 else lane_bucket(k)
    )
    stats.stats().record_sweep(lanes=k, padded=max(0, bucket - k))
    return k, pad_lane_arrays(bucket, *arrays)


def pad_lane_arrays(bucket: int, *arrays: np.ndarray) -> tuple:
    """Pad each array's axis 0 from K to ``bucket`` by replicating entry 0
    (a real lane, so the padded program computes nothing undefined).
    Returns the arrays unchanged when no padding is needed."""
    if not arrays:
        return arrays
    k = arrays[0].shape[0]
    if bucket <= k:
        return arrays
    out = []
    for a in arrays:
        reps = np.repeat(a[:1], bucket - k, axis=0)
        out.append(np.concatenate([a, reps], axis=0))
    return tuple(out)
