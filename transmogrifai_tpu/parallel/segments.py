"""Device-side segment aggregation — event streams reduced per key on the
mesh.

Reference: the Aggregate/Conditional readers fold per-key event sequences
host-side (readers/.../DataReader.scala:206-360, Spark groupBy shuffle).
SURVEY.md §5.7 names long event-sequence aggregation as this framework's
"long axis": the TPU-native equivalent is ``jax.ops.segment_sum``-style
reductions over sorted keys, sharded over the data axis — each shard
reduces its local slice and a ``psum`` combines the per-key partials, so
the whole monoid fold rides ICI instead of a shuffle.

Supported monoids map to the aggregator registry (features/aggregators.py):
sum / max / min / mean / count / logical-or. Keys must be dense ints in
[0, num_segments) (factorize host-side once).
"""
from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

from .mesh import DATA_AXIS

_NEUTRAL = {
    "sum": 0.0,
    "mean": 0.0,
    "count": 0.0,
    "or": 0.0,
    "max": -np.inf,
    "min": np.inf,
}


@lru_cache(maxsize=None)
def _segment_kernels(mesh, num_segments: int, op: str):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    neutral = _NEUTRAL[op]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(),
    )
    def reduce_shard(values, seg_ids):
        # local segment reduction on this shard
        if op in ("sum", "mean", "count", "or"):
            local = jax.ops.segment_sum(
                values, seg_ids, num_segments=num_segments
            )
            total = jax.lax.psum(local, DATA_AXIS)
        elif op == "max":
            local = jax.ops.segment_max(
                values, seg_ids, num_segments=num_segments
            )
            total = jax.lax.pmax(local, DATA_AXIS)
        else:  # min
            local = jax.ops.segment_min(
                values, seg_ids, num_segments=num_segments
            )
            total = jax.lax.pmin(local, DATA_AXIS)
        return total

    return jax.jit(reduce_shard)


def program_trace_specs():
    """Register the segment-reduce kernels (sum + max — the psum and the
    pmax lowering families) with the program auditor."""
    import jax
    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((8, 1), (DATA_AXIS, "model"))
    total = 1
    for name in mesh.axis_names:
        total *= int(mesh.shape[name])

    def build(b):
        n = b * total
        return (
            (jax.ShapeDtypeStruct((n,), np.float32),
             jax.ShapeDtypeStruct((n,), np.int32)),
            {},
        )

    return [
        dict(
            name="psegment_sum", fn=_segment_kernels(mesh, 16, "sum"),
            buckets=(8, 16), build=build,
        ),
        dict(
            name="psegment_max", fn=_segment_kernels(mesh, 16, "max"),
            buckets=(8, 16), build=build,
        ),
    ]


def psegment_reduce(
    values: np.ndarray,
    seg_ids: np.ndarray,
    num_segments: int,
    mesh,
    op: str = "sum",
) -> np.ndarray:
    """Per-segment reduction of ``values`` by dense int keys over the mesh.

    op: 'sum' | 'mean' | 'max' | 'min' | 'count' | 'or'. Rows added as
    padding carry the op's neutral element and segment id 0 with zero
    weight, so results are shard- and padding-invariant.
    """
    import jax.numpy as jnp

    if op not in _NEUTRAL:
        raise ValueError(f"unknown segment op {op!r}")
    values = np.asarray(values, dtype=np.float32)
    seg_ids = np.asarray(seg_ids, dtype=np.int32)
    if op == "count":
        values = np.ones_like(values, dtype=np.float32)
    if op == "or":
        values = (values != 0).astype(np.float32)
    if op == "mean":
        # one kernel dispatch: sums land in segments [0, S), counts in
        # [S, 2S) by offsetting a ones copy's segment ids
        s = int(num_segments)
        both = psegment_reduce(
            np.concatenate([values, np.ones_like(values)]),
            np.concatenate([seg_ids, seg_ids + s]),
            2 * s,
            mesh,
            op="sum",
        )
        sums, counts = both[:s], both[s:]
        with np.errstate(invalid="ignore"):
            return np.where(counts > 0, sums / np.maximum(counts, 1), np.nan)

    n = len(values)
    shards = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    pad = (-n) % shards
    if pad:
        values = np.concatenate(
            [values, np.full(pad, _NEUTRAL[op], dtype=np.float32)]
        )
        # padded rows: segment 0 with neutral value — for sum/count/or the
        # neutral is 0 (no effect); for max/min the neutral is ∓inf
        seg_ids = np.concatenate([seg_ids, np.zeros(pad, dtype=np.int32)])

    # compile-cache discipline: num_segments is data-dependent (the unique
    # key count), so pad it to the next power of two — the jitted kernel set
    # stays O(log max-segments) instead of one program per distinct count
    padded_segments = 1 << max(int(num_segments) - 1, 0).bit_length()
    from .guarded import guarded_collective

    kernel = _segment_kernels(
        mesh, padded_segments, "sum" if op in ("count", "or") else op
    )
    out = np.asarray(
        guarded_collective(
            "psegment_reduce", kernel, jnp.asarray(values),
            jnp.asarray(seg_ids),
        )
    )
    out = out[:num_segments]

    if op == "or":
        out = (out > 0).astype(np.float32)
    return out


def factorize_keys(keys) -> tuple[np.ndarray, list]:
    """Host-side key densification: (dense int ids, sorted unique keys)."""
    uniq = sorted(set(keys))
    index = {k: i for i, k in enumerate(uniq)}
    return np.asarray([index[k] for k in keys], dtype=np.int32), uniq


def aggregate_events_on_device(
    keys,
    values: np.ndarray,
    mesh,
    op: str = "sum",
) -> dict:
    """Convenience: group ``values`` by arbitrary ``keys`` with the given
    monoid on the mesh; returns {key: reduced value}."""
    seg_ids, uniq = factorize_keys(keys)
    out = psegment_reduce(values, seg_ids, len(uniq), mesh, op=op)
    return {k: float(out[i]) for i, k in enumerate(uniq)}
