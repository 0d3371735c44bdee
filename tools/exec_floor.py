"""Measure warm device-exec time of the flagship's main programs on the
real chip (Titanic shapes): forest_scan per depth group, boost_chunk,
logistic sweep, sweep predict programs."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402  — enables compile cache

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from transmogrifai_tpu.models import trees as TR  # noqa: E402
from transmogrifai_tpu.models.gbdt import _feature_bin_groups  # noqa: E402
from transmogrifai_tpu.models.solvers import fit_logistic_binary_batched  # noqa: E402

rng = np.random.default_rng(0)
N, F = 891, 120  # post-sanity Titanic-ish width: mostly indicator columns
x = np.zeros((N, F), dtype=np.float32)
x[:, :8] = rng.normal(size=(N, 8))
x[:, 8:] = (rng.random((N, F - 8)) < 0.2).astype(np.float32)
y = (rng.random(N) < 0.4).astype(np.float32)

thr = TR.quantile_thresholds(x, 32)
binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
fgroups = _feature_bin_groups(x)
fg = tuple(jnp.asarray(a) for a in fgroups) if fgroups else None

masks = np.stack([(rng.random(N) < 0.67).astype(np.float32) for _ in range(3)])


def _sync(out):
    """Pull one leaf to host to force completion."""
    leaf = jax.tree.leaves(out)[0]
    np.asarray(leaf)
    return out


def timeit(label, fn, reps=3):
    out = _sync(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = _sync(fn())
        ts.append(time.perf_counter() - t0)
    print(f"{label:44s} {min(ts)*1e3:9.1f} ms (best of {reps})")
    return out


for depth, npts in ((3, 6), (6, 6), (12, 6)):
    K = npts * 3
    rm = jnp.asarray(np.repeat(masks, npts, axis=0))
    mi = jnp.asarray(rng.choice([10.0, 100.0], K).astype(np.float32))
    mg = jnp.asarray(rng.choice([0.001, 0.01, 0.1], K).astype(np.float32))
    sub = jnp.ones(K)
    col = jnp.ones(K)
    tkeys = jax.random.split(jax.random.PRNGKey(42), 50)
    trees = timeit(
        f"forest_scan depth={depth} K={K} T=50",
        lambda: TR._forest_trees_scan(
            binned, jnp.asarray(-y), rm, tkeys, sub, col, mi, mg, fg,
            max_depth=depth, num_bins=32, bootstrap=True, lowp=True,
            hist_impl=TR._resolved_impl(),
        ),
    )
    timeit(
        f"sweep_forest_outputs depth={depth} K={K}",
        lambda: TR.sweep_forest_outputs(
            jnp.asarray(x), jnp.asarray(thr), trees,
            jnp.ones(K), jnp.zeros(K),
        ),
    )

K = 6
rm = jnp.asarray(np.repeat(masks, 2, axis=0))
eta = jnp.full(K, 0.02)
lam = jnp.ones(K)
gam = jnp.full(K, 0.8)
mcw = jnp.asarray([1.0, 10.0] * 3, dtype=jnp.float32)
mig = jnp.zeros(K)
m0 = jnp.zeros((K, N), dtype=jnp.float32)
timeit(
    "boost_chunk K=6 R=200 depth=10",
    lambda: TR._boost_rounds_batched(
        binned, jnp.asarray(y), rm, m0, eta, lam, gam, mcw, mig, fg,
        num_rounds=200, max_depth=10, num_bins=32,
        objective="binary:logistic", hist_impl=TR._resolved_impl(),
    ),
)

K = 24
rm24 = jnp.asarray(np.repeat(masks, 8, axis=0))
regs = jnp.asarray(np.tile([0.001, 0.01, 0.1, 0.2], 6).astype(np.float32))
ens = jnp.asarray(np.tile([0.1, 0.5], 12).astype(np.float32))
timeit(
    "logistic_binary_batched K=24 iters=50",
    lambda: fit_logistic_binary_batched(
        jnp.asarray(x), jnp.asarray(y), rm24, regs, ens,
        num_iters=50, fit_intercept=True, standardization=True,
    ),
)
