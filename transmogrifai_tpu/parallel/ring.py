"""Feature-axis (column) sharding with ring collectives — the wide-axis
analog of ring attention / sequence parallelism (SURVEY.md §5.7).

The reference has no sequence models; its honest "long axis" is the feature
axis — hashing vectorizers go up to MaxNumOfFeatures = 2^17 columns
(core/.../stages/impl/feature/Transmogrifier.scala:56), and SanityChecker
needs the F×F feature-feature gram (SanityChecker.scala:464-470). At that
width a replicated gram build no longer fits next to the data in one chip's
HBM. The ring layout fixes it with exactly the ring-attention communication
pattern:

  * every device holds one column block X_k of shape [N, F/d];
  * the gram is built in d ring steps — at step s each device multiplies its
    resident block against a rotating block and passes the rotating block to
    its ring neighbor (`lax.ppermute` over ICI), overlapping the MXU matmul
    of step s with the neighbor exchange for step s+1;
  * device k ends holding the row block G_k = X_kᵀ·X, i.e. the gram sharded
    over its first axis — X is never all-gathered, and peak per-device
    memory is O(N·F/d + F·F/d).
"""
from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

from .mesh import DATA_AXIS


def pad_cols(x: np.ndarray, multiple: int) -> tuple[np.ndarray, int]:
    """Zero-pad axis 1 to a multiple of ``multiple``; zero columns are
    monoid-neutral for gram/sum reductions. Returns (padded, original_f)."""
    f = x.shape[1]
    rem = f % multiple
    if rem == 0:
        return x, f
    pad = multiple - rem
    padded = np.concatenate(
        [x, np.zeros((x.shape[0], pad), dtype=x.dtype)], axis=1
    )
    return padded, f


def shard_cols(mesh, x):
    """Place ``x`` column-sharded over the ring (data) axis."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(x, NamedSharding(mesh, P(None, DATA_AXIS)))


@lru_cache(maxsize=None)
def _ring_gram_kernel(mesh):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    d = mesh.shape[DATA_AXIS]
    perm = [(i, (i + 1) % d) for i in range(d)]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, DATA_AXIS),),
        out_specs=P(DATA_AXIS, None),
        check_vma=False,
    )
    def body(xl):
        # xl: this device's resident column block [N, Fl]
        fl = xl.shape[1]
        idx = lax.axis_index(DATA_AXIS)

        def step(s, carry):
            rot, out = carry
            # after s neighbor passes the rotating block originated on ring
            # position (idx - s) mod d — that's the gram column block it fills
            j = (idx - s) % d
            blk = xl.T @ rot  # MXU matmul, overlapped with the ppermute below
            out = lax.dynamic_update_slice(out, blk, (0, j * fl))
            rot = lax.ppermute(rot, DATA_AXIS, perm)
            return rot, out

        out0 = jnp.zeros((fl, fl * d), dtype=xl.dtype)
        _, out = lax.fori_loop(0, d, step, (xl, out0))
        return out

    return jax.jit(body)


def program_trace_specs():
    """Register the ring-gram kernel with the program auditor: the one
    ppermute-based program in the plane — tracing it keeps the TPS
    collective census honest about permute collectives, not just
    psum-family reductions. AbstractMesh traces device-free; the ring
    step count is mesh-static so the kernel traces at any column width."""
    import jax
    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((8, 1), (DATA_AXIS, "model"))
    d = int(mesh.shape[DATA_AXIS])
    return [
        dict(
            name="ring_gram", fn=_ring_gram_kernel(mesh), buckets=(1, 2),
            bucket_axis="cols",
            build=lambda b: (
                (jax.ShapeDtypeStruct((32, b * d), np.float32),), {},
            ),
        ),
    ]


def ring_gram(x: np.ndarray, mesh) -> np.ndarray:
    """XᵀX [F, F] of a column-sharded matrix via ring passes over ICI.

    Drop-in alternative to parallel.reductions.pxtx for matrices whose
    feature axis, not row axis, is the long one (hashed text planes); rows
    stay resident, columns ride the ring.
    """
    from .guarded import guarded_collective

    d = mesh.shape[DATA_AXIS]
    xp, f = pad_cols(np.asarray(x, dtype=np.float32), d)
    xs = shard_cols(mesh, xp)
    g = np.asarray(
        guarded_collective("ring_gram", _ring_gram_kernel(mesh), xs),
        dtype=np.float64,
    )
    return g[:f, :f]


def ring_corr(x: np.ndarray, mesh) -> np.ndarray:
    """Pearson correlation matrix [F, F] with the gram built over the ring.

    Centering/normalization uses per-column moments (cheap, O(N·F/d) per
    device); only the quadratic F×F term rides the ring. Constant columns
    get correlation 0 (the reference's NaN-corr columns are treated as
    uninformative, SanityChecker.scala:464-470).
    """
    x = np.asarray(x, dtype=np.float64)
    n = x.shape[0]
    mean = x.mean(axis=0)
    g = ring_gram(x - mean, mesh)  # centered gram: covariance * n
    var = np.clip(np.diag(g), 0.0, None)
    denom = np.sqrt(np.outer(var, var))
    with np.errstate(invalid="ignore", divide="ignore"):
        corr = np.where(denom > 0, g / np.where(denom > 0, denom, 1.0), 0.0)
    np.fill_diagonal(corr, np.where(var > n * 1e-18, 1.0, 0.0))
    return corr
