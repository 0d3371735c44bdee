"""Seconds of the tree fit's bin preparation at one plane shape (default: the
flagship cells', 1,002,701 x 357 x 32 bins), by route and by step.

    python tools/bench_bin_prepare.py [--rows N] [--cols F] [--bins B]
        [--col-chunks 32,64] [--repeat 3] [--no-host] [--check]

The device route (``trees.bin_column_stats``) alone, per column-chunk size:
the upload of the float32 plane, the program (sort, 0/1 scan, NaN scan) on
the resident plane, the read-back of its small result and the host's
float64 interpolation, each the median of ``--repeat`` calls after one that
compiles; then the host route (``trees.quantile_thresholds`` and
``gbdt._feature_bin_groups``) once beside it. ``--check`` compares the two
routes' thresholds bit for bit and their column flags, and exits 1 on any
difference. Times are device times only on a chip; on CPU they say nothing.
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _plane(rows: int, cols: int, seed: int):
    """A plane shaped like a transmogrified table: one column in six an
    indicator, one a small count with many ties, the rest continuous."""
    import numpy as np

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, cols), dtype=np.float32)
    x[:, 0::6] = rng.integers(0, 2, (rows, len(range(0, cols, 6))))
    x[:, 3::6] = rng.poisson(0.3, (rows, len(range(3, cols, 6))))
    return x


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=1_002_701)
    ap.add_argument("--cols", type=int, default=357)
    ap.add_argument("--bins", type=int, default=32)
    ap.add_argument("--col-chunks", default="32,64")
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-host", action="store_true")
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args(argv)

    from transmogrifai_tpu.compiler.cache import enable_persistent_cache

    enable_persistent_cache()
    import jax
    import numpy as np

    from transmogrifai_tpu.compiler.dispatch import device_f32
    from transmogrifai_tpu.models import gbdt
    from transmogrifai_tpu.models import trees as TR

    n, f, b = args.rows, args.cols, args.bins
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}; {n} x {f} x {b} bins",
          flush=True)
    x = _plane(n, f, args.seed)
    med = statistics.median

    uploads = []
    for _ in range(args.repeat):
        t0 = time.perf_counter()
        xj = device_f32(x)
        xj.block_until_ready()
        uploads.append(time.perf_counter() - t0)
    print(f"upload {x.nbytes / 1e9:.3f} GB: median {med(uploads):.4f} s "
          f"({x.nbytes / 1e9 / med(uploads):.2f} GB/s)", flush=True)

    device = None
    for chunk in [int(c) for c in args.col_chunks.split(",")]:
        TR._STATS_COL_CHUNK = chunk
        TR.bin_column_stats.clear_cache()
        t0 = time.perf_counter()
        out = TR.bin_column_stats(xj, max_bins=b)
        jax.block_until_ready(out)
        first = time.perf_counter() - t0
        program, readback, interpolate = [], [], []
        for _ in range(args.repeat):
            t0 = time.perf_counter()
            out = TR.bin_column_stats(xj, max_bins=b)
            jax.block_until_ready(out)
            t1 = time.perf_counter()
            stats, binary, any_nan = jax.device_get(out)
            t2 = time.perf_counter()
            thr = TR.thresholds_from_order_stats(stats, n)
            t3 = time.perf_counter()
            program.append(t1 - t0)
            readback.append(t2 - t1)
            interpolate.append(t3 - t2)
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"device route, {chunk:4d} columns a sort: first call "
              f"{first:.3f} s (compile); program {med(program):.4f} s, "
              f"read-back {med(readback):.5f} s, interpolation "
              f"{med(interpolate):.5f} s; device peak {mem}", flush=True)
        device = (thr, np.asarray(binary), bool(any_nan))

    if args.no_host and not args.check:
        return 0
    t0 = time.perf_counter()
    host_thr = TR.quantile_thresholds(x, b)
    t1 = time.perf_counter()
    groups = gbdt._feature_bin_groups(x)
    t2 = time.perf_counter()
    print(f"host route: quantile_thresholds {t1 - t0:.3f} s, "
          f"_feature_bin_groups {t2 - t1:.3f} s", flush=True)
    if not args.check:
        return 0
    thr, binary, any_nan = device
    narrow = np.zeros(f, bool)
    if groups is not None:
        narrow[np.asarray(groups[0])] = True
    differing = int((thr.view(np.uint32) != host_thr.view(np.uint32)).sum())
    flags = int((binary != narrow).sum())
    print(f"check: {differing} differing thresholds of {thr.size}, {flags} "
          f"differing column flags of {f}, any NaN {any_nan}", flush=True)
    return 1 if differing or flags or any_nan else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
