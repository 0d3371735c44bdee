"""Trace the 1M x 64 boost chunk on the real chip — attribute the 20 s."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402  (enables the compile cache)
import numpy as np, jax, jax.numpy as jnp
from transmogrifai_tpu.models import trees as TR

k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
N, F, ROUNDS, DEPTH, BINS = 1_000_000, 64, 20, 6, 32
x = jax.random.normal(k1, (N, F), dtype=jnp.float32)
w = jax.random.normal(k2, (F,), dtype=jnp.float32)
y = (x @ w + jax.random.normal(k3, (N,)) > 0).astype(jnp.float32)
thr = TR.quantile_thresholds(np.asarray(x[:100_000]), max_bins=BINS)
binned = TR.bin_data(x, jnp.asarray(thr))
mask = jnp.ones((1, N), dtype=jnp.float32)
np.asarray(jnp.sum(binned))  # fence

def sync(out):
    for leaf in jax.tree.leaves(out):
        np.asarray(jnp.sum(leaf))

print("rounds:", ROUNDS, "hist impl:", TR._resolved_impl())
margin = jnp.zeros((1, N), dtype=jnp.float32)
args = (binned, y, mask, margin, jnp.ones(1), jnp.float32(1.0),
        jnp.float32(0.0), jnp.float32(1.0), jnp.float32(0.0), None)
statics = dict(num_rounds=ROUNDS, max_depth=DEPTH, num_bins=BINS,
               objective="binary:logistic", hist_impl=TR._resolved_impl())
t0 = time.perf_counter(); out = TR._boost_rounds_batched(*args, **statics); sync(out)
print(f"chunk first call (trace+compile+exec): {time.perf_counter()-t0:.2f}s")
for i in range(3):
    t0 = time.perf_counter(); out = TR._boost_rounds_batched(*args, **statics); sync(out)
    print(f"chunk warm exec {i}: {time.perf_counter()-t0:.2f}s  ({ROUNDS} rounds)")

jax.profiler.start_trace("/tmp/jaxtrace_scale")
out = TR._boost_rounds_batched(*args, **statics); sync(out)
jax.profiler.stop_trace()
print("trace done")
