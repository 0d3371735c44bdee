"""Depth/chunk scaling probes for the forest_scan exec floor (real chip)."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from transmogrifai_tpu.models import trees as TR  # noqa: E402
from transmogrifai_tpu.models.gbdt import _feature_bin_groups  # noqa: E402

rng = np.random.default_rng(0)
N, F = 891, 120
x = np.zeros((N, F), dtype=np.float32)
x[:, :8] = rng.normal(size=(N, 8))
x[:, 8:] = (rng.random((N, F - 8)) < 0.2).astype(np.float32)
y = (rng.random(N) < 0.4).astype(np.float32)
thr = TR.quantile_thresholds(x, 32)
binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
fg = tuple(jnp.asarray(a) for a in _feature_bin_groups(x))
masks = np.stack([(rng.random(N) < 0.67).astype(np.float32) for _ in range(3)])


def sync(out):
    # fence on a SCALAR reduction: pulling a full leaf measures the
    # download of the tree stack (176 MB at depth 12), not execution
    for leaf in jax.tree.leaves(out):
        np.asarray(jnp.sum(leaf))
    return out


def run(depth, K=18, T=50):
    npts = K // 3
    rm = jnp.asarray(np.repeat(masks, npts, axis=0))
    if os.environ.get("TPTPU_PROBE_NOSPLIT"):
        mi = jnp.full(K, 1e6, dtype=jnp.float32)  # nothing ever splits
    else:
        mi = jnp.asarray(np.tile([10.0, 100.0], K // 2).astype(np.float32))
    mg = jnp.asarray(np.tile([0.001, 0.01, 0.1], K // 3).astype(np.float32))
    tkeys = jax.random.split(jax.random.PRNGKey(42), T)
    f = lambda: TR._forest_trees_scan(  # noqa: E731
        binned, jnp.asarray(-y), rm, tkeys, jnp.ones(K), jnp.ones(K), mi, mg,
        fg, max_depth=depth, num_bins=32, bootstrap=True, lowp=True,
        hist_impl=TR._resolved_impl(),
    )
    sync(f())
    ts = []
    for _ in range(2):
        t0 = time.perf_counter()
        sync(f())
        ts.append(time.perf_counter() - t0)
    print(f"depth={depth:2d} K={K} T={T} mcap={os.environ.get('TPTPU_GEMM_MCAP', '128')}"
          f"  {min(ts)*1e3:9.1f} ms")


for d in (int(a) for a in sys.argv[1:] or ["8", "10", "12"]):
    run(d)
