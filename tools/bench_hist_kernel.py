"""Seconds per call of the bin-loop Pallas histogram kernel, by node-slot
width, at one table shape (default: the flagship cell's wide group,
1,002,701 x 302 x 32 bins, 4 lanes).

    python tools/bench_hist_kernel.py [--rows N] [--cols F] [--bins B]
        [--lanes K] [--widths 32,64,128,256] [--row-tile T] [--feat-tile FT]
        [--packed]

Prints, per width, the tiles ``hist_pallas.binloop_tiles`` picks and the
best and median of ``--repeat`` timed calls: the width -> time curve of
PERF.md. ``--packed`` times the lane-packed kernel beside it (the default
above 64 bins) and compares the two histograms. Times are device times
only on a chip; on CPU the kernels run interpreted and the numbers say
nothing.
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=1_002_701)
    ap.add_argument("--cols", type=int, default=302)
    ap.add_argument("--bins", type=int, default=32)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--widths", default="32,64,128,256")
    ap.add_argument("--row-tile", type=int, default=None)
    ap.add_argument("--feat-tile", type=int, default=None)
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--packed", action="store_true")
    args = ap.parse_args(argv)

    from transmogrifai_tpu.compiler.cache import enable_persistent_cache

    enable_persistent_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from transmogrifai_tpu.models import hist_pallas as HP

    interpret = jax.default_backend() != "tpu"
    n, f, b, k = args.rows, args.cols, args.bins, args.lanes
    widths = [int(w) for w in args.widths.split(",")]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    binned = jax.random.randint(k1, (n, f), 0, b, dtype=jnp.int32)
    g = jax.random.normal(k3, (k, n), dtype=jnp.float32)
    h = jnp.ones((k, n), dtype=jnp.float32)
    np.asarray(jnp.sum(binned))  # force inputs
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}; {n} x {f} x {b} bins, "
          f"{k} lanes", flush=True)

    def timed(fn, node, m, **kw):
        out = fn(binned, node, g, h, m, b, interpret=interpret, **kw)
        total = float(np.asarray(jnp.sum(jnp.abs(out))))  # compile + run
        times = []
        for _ in range(args.repeat):
            t0 = time.perf_counter()
            out = fn(binned, node, g, h, m, b, interpret=interpret, **kw)
            np.asarray(jnp.sum(out))
            times.append(time.perf_counter() - t0)
        return total, min(times), statistics.median(times)

    for m in widths:
        node = jax.random.randint(k2, (k, n), 0, m, dtype=jnp.int32)
        rt, ft = HP.binloop_tiles(
            f, m, b, row_tile=args.row_tile, feat_tile=args.feat_tile
        )
        total, best, med = timed(
            HP.build_histogram_pallas_binloop, node, m,
            row_tile=args.row_tile, feat_tile=args.feat_tile,
        )
        print(f"binloop width {m:4d} row_tile {rt:5d} feat_tile {ft:4d}: "
              f"best {best:8.4f} s median {med:8.4f} s", flush=True)
        if args.packed:
            ptotal, best, med = timed(
                HP.build_histogram_pallas_batched, node, m
            )
            same = abs(ptotal - total) < 1e-3 * abs(total)
            print(f"packed  width {m:4d}: best {best:8.4f} s median "
                  f"{med:8.4f} s; sum |hist| agrees: {same}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
