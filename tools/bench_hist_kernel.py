"""Standalone timing of BOTH Pallas histogram kernels at a scale shape
(default 1M x 500 x 32).

Usage: python tools/bench_hist_kernel.py [N] [F] [M] [B]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: F401,E402  (side effect: enables the persistent
#                                  XLA compile cache — do not remove)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from transmogrifai_tpu.models.hist_pallas import (  # noqa: E402
    build_histogram_pallas_batched,
    build_histogram_pallas_binloop,
)

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
F = int(sys.argv[2]) if len(sys.argv) > 2 else 500
M = int(sys.argv[3]) if len(sys.argv) > 3 else 64
B = int(sys.argv[4]) if len(sys.argv) > 4 else 32

k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
binned = jax.random.randint(k1, (N, F), 0, B, dtype=jnp.int32)
node = jax.random.randint(k2, (1, N), 0, M, dtype=jnp.int32)
g = jax.random.normal(k3, (1, N), dtype=jnp.float32)
h = jnp.ones((1, N), dtype=jnp.float32)
np.asarray(jnp.sum(binned))  # force inputs

outs = {}
for name, fn in (
    ("packed", build_histogram_pallas_batched),
    ("binloop", build_histogram_pallas_binloop),
):
    out = fn(binned, node, g, h, M, B)
    outs[name] = float(np.asarray(jnp.sum(jnp.abs(out))))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = fn(binned, node, g, h, M, B)
        np.asarray(jnp.sum(out))
        times.append(time.perf_counter() - t0)
    print(f"{name:8s}: best {min(times)*1e3:7.1f} ms")
match = abs(outs["packed"] - outs["binloop"]) < 1e-3 * abs(outs["packed"])
print(f"parity (sum |hist|): {match}")
