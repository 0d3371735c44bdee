"""Sharded monoid reductions: the map-reduce plane of every estimator.

The reference expresses all statistics as commutative-monoid map-reduce
(SequenceAggregators, Statistics.colStats, reduceByKey in
SanityChecker.scala:252-348) so results are partition-order-invariant. Here
each reduction is a `shard_map` whose per-shard body computes the local
summary and `lax.psum`s it over the data axis — the direct ICI analog of
Spark's treeAggregate, with the same order-invariance guarantee.

All kernels take rows padded to the shard multiple (parallel.mesh.pad_rows);
padding is either monoid-neutral (zeros for sums) or masked via ``n_valid``.
"""
from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

from .guarded import guarded_collective
from .mesh import DATA_AXIS, pad_rows, shard_rows


def _data_spec(*trailing):
    from jax.sharding import PartitionSpec as P

    return P(DATA_AXIS, *trailing)


#: the canonical guarded-collective seam now lives in parallel/guarded.py
#: (one module for the resilience guard, the SPMD analyzer and the
#: collective tracer to instrument); the old private name stays importable
#: for callers that grew around it
_guarded = guarded_collective


# Jitted shard_map kernels are built once per mesh (jax.sharding.Mesh is
# hashable) and reused — a fresh closure + jax.jit per call would retrace and
# recompile on every reduction, costing SanityChecker/RawFeatureFilter
# hundreds of ms per stats call. jit's own cache handles per-shape variants.
@lru_cache(maxsize=None)
def _stats_kernels(mesh):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(_data_spec(None),),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    def pass1(xs):
        v = xs[:, -1:]
        data = xs[:, :-1]
        cnt = jax.lax.psum(v.sum(), DATA_AXIS)
        s = jax.lax.psum((data * v).sum(axis=0), DATA_AXIS)
        big = jnp.finfo(jnp.float32).max
        mn = jax.lax.pmin(
            jnp.where(v > 0, data, big).min(axis=0), DATA_AXIS
        )
        mx = jax.lax.pmax(
            jnp.where(v > 0, data, -big).max(axis=0), DATA_AXIS
        )
        return cnt, s, mn, mx

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(_data_spec(None), P()),
        out_specs=P(),
        check_vma=False,
    )
    def pass2(xs, mean):
        v = xs[:, -1:]
        c = (xs[:, :-1] - mean[None, :]) * v
        return jax.lax.psum((c * c).sum(axis=0), DATA_AXIS)

    return jax.jit(pass1), jax.jit(pass2)


def pcolumn_stats(x: np.ndarray, mesh) -> dict[str, np.ndarray]:
    """Per-column count/mean/centered-M2/min/max over a row-sharded matrix.

    Mirrors Statistics.colStats (used by SanityChecker.scala:464) as a
    psum/pmin/pmax tree over the mesh's data axis. Two passes — sums first,
    then CENTERED squared deviations — because device arithmetic is float32
    and raw-moment variance (sumsq - n·mean²) catastrophically cancels for
    columns with |mean| >> std. Padding rows are excluded via the
    row-validity weight column appended internally. Runs behind the active
    CollectiveGuard when a FailoverController is installed.
    """
    return _guarded("pcolumn_stats", _pcolumn_stats, x, mesh)


def _pcolumn_stats(x: np.ndarray, mesh) -> dict[str, np.ndarray]:
    n_shards = mesh.shape[DATA_AXIS]
    xp, n = pad_rows(np.asarray(x, dtype=np.float32), n_shards)
    valid = np.zeros((xp.shape[0], 1), dtype=np.float32)
    valid[:n] = 1.0
    xp = np.concatenate([xp, valid], axis=1)

    pass1, pass2 = _stats_kernels(mesh)
    xs = shard_rows(mesh, xp)
    cnt, s, mn, mx = pass1(xs)
    cnt_f = float(np.asarray(cnt))
    mean = np.asarray(s, dtype=np.float64) / max(cnt_f, 1.0)
    m2 = pass2(xs, mean.astype(np.float32))
    return {
        "count": np.asarray(cnt),
        "mean": mean,
        "m2": np.asarray(m2, dtype=np.float64),
        "min": np.asarray(mn),
        "max": np.asarray(mx),
    }


def pcentered_gram(x: np.ndarray, mesh) -> tuple[np.ndarray, np.ndarray, float]:
    """(centered XᵀX, column means, n) over row-sharded X.

    The covariance/correlation building block: per-shard mean-subtraction
    (mask-aware for padding) keeps float32 matmuls numerically safe where a
    raw-moment XᵀX would cancel (see pcolumn_stats). One MXU matmul + psum
    per pass over ICI. Runs behind the active CollectiveGuard when a
    FailoverController is installed.
    """
    return guarded_collective("pcentered_gram", _pcentered_gram, x, mesh)


def _pcentered_gram(x: np.ndarray, mesh) -> tuple[np.ndarray, np.ndarray, float]:
    n_shards = mesh.shape[DATA_AXIS]
    xp, n = pad_rows(np.asarray(x, dtype=np.float32), n_shards)
    valid = np.zeros((xp.shape[0], 1), dtype=np.float32)
    valid[:n] = 1.0
    xp = np.concatenate([xp, valid], axis=1)

    sums, gram = _gram_kernels(mesh)
    xs = shard_rows(mesh, xp)
    s = np.asarray(sums(xs), dtype=np.float64)
    mean = s / max(n, 1)
    g = np.asarray(gram(xs, mean.astype(np.float32)), dtype=np.float64)
    return g, mean, float(n)


@lru_cache(maxsize=None)
def _gram_kernels(mesh):
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(_data_spec(None),),
        out_specs=P(),
        check_vma=False,
    )
    def sums(xs):
        v = xs[:, -1:]
        return jax.lax.psum((xs[:, :-1] * v).sum(axis=0), DATA_AXIS)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(_data_spec(None), P()),
        out_specs=P(),
        check_vma=False,
    )
    def gram(xs, mean):
        v = xs[:, -1:]
        c = (xs[:, :-1] - mean[None, :]) * v
        return jax.lax.psum(c.T @ c, DATA_AXIS)

    return jax.jit(sums), jax.jit(gram)


def pxtx(x: np.ndarray, mesh) -> np.ndarray:
    """XᵀX over row-sharded X: per-shard MXU matmul + psum over ICI.

    The correlation/covariance building block (SanityChecker's feature-label
    and feature-feature correlation matrix, SanityChecker.scala:464-470).
    Zero padding rows are monoid-neutral. Runs behind the active
    CollectiveGuard when a FailoverController is installed.
    """
    return _guarded("pxtx", _pxtx, x, mesh)


def _pxtx(x: np.ndarray, mesh) -> np.ndarray:
    n_shards = mesh.shape[DATA_AXIS]
    xp, _ = pad_rows(np.asarray(x, dtype=np.float32), n_shards)
    return np.asarray(_xtx_kernel(mesh)(shard_rows(mesh, xp)), dtype=np.float64)


@lru_cache(maxsize=None)
def _xtx_kernel(mesh):
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(_data_spec(None),),
        out_specs=P(),
        check_vma=False,
    )
    def body(xs):
        return jax.lax.psum(xs.T @ xs, DATA_AXIS)

    return jax.jit(body)


def phistogram(
    codes: np.ndarray, num_bins: int, mesh, weights: np.ndarray | None = None
) -> np.ndarray:
    """Per-column histograms of integer codes: one-hot matmul per shard +
    psum (RawFeatureFilter's FeatureDistribution bins, the GBDT histogram
    primitive). codes [N, F] int32 in [0, num_bins); rows with code < 0 are
    skipped (doubles as the padding mask). Runs behind the active
    CollectiveGuard when a FailoverController is installed."""
    return _guarded("phistogram", _phistogram, codes, num_bins, mesh, weights)


def _phistogram(
    codes: np.ndarray, num_bins: int, mesh, weights: np.ndarray | None
) -> np.ndarray:
    n_shards = mesh.shape[DATA_AXIS]
    codes = np.asarray(codes, dtype=np.int32)
    cp, n = pad_rows(codes + 1, n_shards)  # padding rows become code 0 = skip
    cp = cp - 1
    if weights is None:
        w = np.ones(codes.shape[0], dtype=np.float32)
    else:
        w = np.asarray(weights, dtype=np.float32)
    wp, _ = pad_rows(w, n_shards)
    body = _hist_kernel(mesh, num_bins)
    return np.asarray(body(shard_rows(mesh, cp), shard_rows(mesh, wp)))


@lru_cache(maxsize=None)
def _hist_kernel(mesh, num_bins: int):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(_data_spec(None), _data_spec()),
        out_specs=P(),
        check_vma=False,
    )
    def body(cs, ws):
        valid = (cs >= 0).astype(jnp.float32) * ws[:, None]
        onehot = jax.nn.one_hot(jnp.maximum(cs, 0), num_bins, dtype=jnp.float32)
        hist = jnp.einsum("nf,nfb->fb", valid, onehot)
        return jax.lax.psum(hist, DATA_AXIS)

    return jax.jit(body)


#: rows per device round for pcontingency: float32 cell counts are exact up
#: to 2^24, so bounding each round's per-shard rows keeps every per-shard
#: partial integral (the psum across shards can round above 2^24, bounded by
#: f32 eps ~1e-7 relative — not the +1-increment saturation of an unchunked
#: accumulate); rounds accumulate in float64 on host.
_CONTINGENCY_CHUNK_ROWS = 1 << 23


def pcontingency(
    group_onehot: np.ndarray, label_onehot: np.ndarray, mesh
) -> np.ndarray:
    """Contingency tables group×label via sharded matmul + psum
    (SanityChecker's Cramér's V contingency build, :252-348).

    Counts within one device round stay below float32's 2^24 integer limit;
    rounds are summed in float64 host-side, so large-N tables are exact.
    Runs behind the active CollectiveGuard when a FailoverController is
    installed.
    """
    return guarded_collective(
        "pcontingency", _pcontingency, group_onehot, label_onehot, mesh
    )


def _pcontingency(
    group_onehot: np.ndarray, label_onehot: np.ndarray, mesh
) -> np.ndarray:
    n_shards = mesh.shape[DATA_AXIS]
    fn = _contingency_kernel(mesh)
    total = np.zeros(
        (group_onehot.shape[1], label_onehot.shape[1]), dtype=np.float64
    )
    step = _CONTINGENCY_CHUNK_ROWS * n_shards
    for i in range(0, group_onehot.shape[0], step):
        gp, _ = pad_rows(
            np.asarray(group_onehot[i:i + step], dtype=np.float32), n_shards
        )
        lp, _ = pad_rows(
            np.asarray(label_onehot[i:i + step], dtype=np.float32), n_shards
        )
        total += np.asarray(fn(shard_rows(mesh, gp), shard_rows(mesh, lp)))
    return total


@lru_cache(maxsize=None)
def _contingency_kernel(mesh):
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(_data_spec(None), _data_spec(None)),
        out_specs=P(),
        check_vma=False,
    )
    def body(gs, ls):
        return jax.lax.psum(gs.T @ ls, DATA_AXIS)

    return jax.jit(body)


# --------------------------------------------------------------------------
# trace-spec registration (analysis/program.py TPJ + analysis/spmd.py TPS)
# --------------------------------------------------------------------------
def _spec_trace_mesh():
    """The auditors' 8-way data mesh: a device-free AbstractMesh, so the
    kernels trace on any host. The lru_cached kernel factories accept it
    like a real mesh — both are hashable and shard_map traces over both."""
    from jax.sharding import AbstractMesh

    return AbstractMesh((8, 1), (DATA_AXIS, "model"))


def program_trace_specs():
    """Register the sharded-reduction kernels with the program auditor
    (same contract as models/gbdt.py etc.): each entry traces the jitted
    shard_map kernel over representative row buckets, so the TPJ IR
    lints AND the TPS static collective census see exactly the programs
    the stats plane dispatches."""
    import jax
    import numpy as np

    mesh = _spec_trace_mesh()
    n_shards = int(mesh.shape[DATA_AXIS])
    f = 4  # representative column count (+1 validity appended by callers)

    def rows(b):
        return b * n_shards

    def mat(b, cols, dtype=np.float32):
        return jax.ShapeDtypeStruct((rows(b), cols), dtype)

    pass1, pass2 = _stats_kernels(mesh)
    sums, gram = _gram_kernels(mesh)
    mean = jax.ShapeDtypeStruct((f,), np.float32)
    return [
        dict(
            name="pstats_pass1", fn=pass1, buckets=(8, 16),
            build=lambda b: ((mat(b, f + 1),), {}),
        ),
        dict(
            name="pstats_pass2", fn=pass2, buckets=(8, 16),
            build=lambda b: ((mat(b, f + 1), mean), {}),
        ),
        dict(
            name="pgram_sums", fn=sums, buckets=(8, 16),
            build=lambda b: ((mat(b, f + 1),), {}),
        ),
        dict(
            name="pgram_centered", fn=gram, buckets=(8, 16),
            build=lambda b: ((mat(b, f + 1), mean), {}),
        ),
        dict(
            name="pxtx", fn=_xtx_kernel(mesh), buckets=(8, 16),
            build=lambda b: ((mat(b, f),), {}),
        ),
        dict(
            name="phistogram", fn=_hist_kernel(mesh, 16), buckets=(8, 16),
            build=lambda b: (
                (mat(b, f, np.int32),
                 jax.ShapeDtypeStruct((rows(b),), np.float32)),
                {},
            ),
        ),
        dict(
            name="pcontingency", fn=_contingency_kernel(mesh),
            buckets=(8, 16),
            build=lambda b: ((mat(b, 3), mat(b, 2)), {}),
        ),
    ]
