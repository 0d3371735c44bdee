"""Seconds per call of the bin-loop Pallas histogram kernel, by node-slot
width, at one table shape (default: the flagship cell's wide group,
1,002,701 x 302 x 32 bins, 4 lanes).

    python tools/bench_hist_kernel.py [--rows N] [--cols F] [--bins B]
        [--lanes K] [--widths 32,64,128,256] [--lowp | --exact-hess]
        [--row-tile T] [--feat-tile FT] [--lane-group G] [--packed]
        [--grid [--row-tiles 128,...] [--feat-tiles 8,...]]
        [--vmem-limit-mb MB]

Prints, per width, the tiles ``hist_pallas.binloop_tiles`` picks and the
best and median of ``--repeat`` timed calls: the width -> time curve of
PERF.md. ``--lane-group`` sets how many of the ``--lanes`` one grid step
builds together (default: ``binloop_lane_group``'s; 1 times the kernel
as it was before lanes shared a pass). ``--lowp`` times the two-variant kernel (the forest's: values
already bf16-exact). ``--exact-hess`` times the three-variant kernel (a
first-order boosted fit's: real g, h = 1 carried as one variant) at the
tiles of the four-variant one, then builds the four-variant histograms
and says whether they are the same bit for bit. ``--packed`` times the lane-packed kernel beside it
(the default above 64 bins) and compares the two histograms. ``--grid``
forces every (row_tile, feat_tile) pair of the two lists in turn and
prints one table a width, best seconds a call, ``-`` where Mosaic refused
the pair, ``*`` at the pair ``binloop_tiles`` picks: the sweep its table
was made from. ``--vmem-limit-mb`` replaces the scoped-VMEM limit the
kernel states to Mosaic (0: state none, Mosaic's default of 16 MB). Times
are device times only on a chip; on CPU the kernels run interpreted and
the numbers say nothing.
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ints(text: str) -> list[int]:
    return [int(w) for w in text.split(",")]


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=1_002_701)
    ap.add_argument("--cols", type=int, default=302)
    ap.add_argument("--bins", type=int, default=32)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--widths", type=_ints, default=[32, 64, 128, 256])
    ap.add_argument("--lowp", action="store_true")
    ap.add_argument("--exact-hess", action="store_true")
    ap.add_argument(
        "--channels", type=int, default=2,
        help="statistic channels (2: grad and hess; K: a K-class forest's)",
    )
    ap.add_argument("--row-tile", type=int, default=None)
    ap.add_argument("--lane-group", type=int, default=None)
    ap.add_argument("--feat-tile", type=int, default=None)
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--packed", action="store_true")
    ap.add_argument("--grid", action="store_true")
    ap.add_argument(
        "--row-tiles", type=_ints, default=[128, 256, 512, 1024, 2048]
    )
    ap.add_argument(
        "--feat-tiles", type=_ints, default=[8, 16, 32, 56, 64, 80, 104, 128]
    )
    ap.add_argument("--vmem-limit-mb", type=int, default=None)
    args = ap.parse_args(argv)

    from transmogrifai_tpu.compiler.cache import enable_persistent_cache

    enable_persistent_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from transmogrifai_tpu.models import hist_pallas as HP

    if args.vmem_limit_mb is not None:
        # read when the kernel is traced: set before the first call
        HP._BINLOOP_VMEM_LIMIT = (args.vmem_limit_mb << 20) or None
    interpret = jax.default_backend() != "tpu"
    n, f, b, k = args.rows, args.cols, args.bins, args.lanes
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    binned = jax.random.randint(k1, (n, f), 0, b, dtype=jnp.int32)
    g = jax.random.normal(k3, (k, n), dtype=jnp.float32)
    if args.lowp:
        g = jnp.sign(g)  # bf16-exact values, as the forest's indicators
    h = jnp.ones((k, n), dtype=jnp.float32)
    ch = args.channels
    if ch > 2:
        # a K-class forest's value channels: the indicators of classes
        # 1 … K - 1 (bf16-exact), or real numbers without --lowp
        cls = jax.random.randint(k3, (n,), 0, ch, dtype=jnp.int32)
        g = jnp.stack(
            [-(cls == c).astype(jnp.float32) for c in range(1, ch)]
        )[None] * jnp.ones((k, 1, 1), jnp.float32)
        if not args.lowp:
            g = g * jax.random.normal(k3, (k, ch - 1, n), dtype=jnp.float32)
    np.asarray(jnp.sum(binned))  # force inputs
    dev = jax.devices()[0]
    limit = HP._BINLOOP_VMEM_LIMIT
    print(f"device {dev.platform} {dev.device_kind}; {n} x {f} x {b} bins, "
          f"{k} lanes, lowp {args.lowp}, exact_hess {args.exact_hess}, "
          f"{ch} channels, vmem limit "
          f"{'default' if limit is None else limit >> 20} MB", flush=True)
    stated = dict(lowp=args.lowp, exact_hess=args.exact_hess)

    def lane_group(m):
        return args.lane_group or HP.binloop_lane_group(
            k, ch, args.lowp, m, args.exact_hess
        )

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

    def grid(node, m):
        """One table: rows row_tile, columns feat_tile, best s a call."""
        lanes = lane_group(m)
        picked = HP.binloop_tiles(
            f, m, b, lowp=args.lowp, stat_channels=ch, lanes=lanes
        )
        print(f"width {m}: best s a call; row_tile down, feat_tile across; "
              f"* = binloop_tiles {picked}", flush=True)
        print("       " + "".join(f"{ft:>9d}" for ft in args.feat_tiles))
        for rt in args.row_tiles:
            cells = []
            for ft in args.feat_tiles:
                try:
                    _, best, _ = timed(
                        HP.build_histogram_pallas_binloop, node, m,
                        row_tile=rt, feat_tile=ft, lane_group=lanes,
                        **stated,
                    )
                    cell = f"{best:.4f}"
                except Exception as e:  # Mosaic refused the pair
                    print(f"  ({rt}, {ft}) refused: "
                          f"{str(e).splitlines()[0][:160]}", file=sys.stderr)
                    cell = "-"
                cells.append(cell + ("*" if (rt, ft) == picked else " "))
            print(f"{rt:6d} " + "".join(f"{c:>9s}" for c in cells),
                  flush=True)

    for m in args.widths:
        node = jax.random.randint(k2, (k, n), 0, m, dtype=jnp.int32)
        if args.grid:
            grid(node, m)
            continue
        lanes = lane_group(m)
        rt, ft = HP.binloop_tiles(
            f, m, b, lowp=args.lowp, stat_channels=ch, lanes=lanes
        )
        rt, ft = args.row_tile or rt, args.feat_tile or ft
        total, best, med = timed(
            HP.build_histogram_pallas_binloop, node, m,
            row_tile=rt, feat_tile=ft, lane_group=lanes, **stated,
        )
        print(f"binloop width {m:4d} lane_group {lanes} row_tile {rt:5d} "
              f"feat_tile {ft:4d}: best {best:8.4f} s median {med:8.4f} s",
              flush=True)
        if args.exact_hess:
            kw = dict(row_tile=rt, feat_tile=ft, interpret=interpret)
            three = HP.build_histogram_pallas_binloop(
                binned, node, g, h, m, b, lane_group=lanes, exact_hess=True,
                **kw,
            )
            four = HP.build_histogram_pallas_binloop(
                binned, node, g, h, m, b, **kw
            )
            same = bool(jnp.array_equal(three, four))
            print(f"exact_hess width {m:4d}: the four-variant build's "
                  f"histograms bit for bit: {same}", flush=True)
        if args.packed:
            ptotal, best, med = timed(
                HP.build_histogram_pallas_batched, node, m, lowp=args.lowp
            )
            same = abs(ptotal - total) < 1e-3 * abs(total)
            print(f"packed  width {m:4d}: best {best:8.4f} s median "
                  f"{med:8.4f} s; sum |hist| agrees: {same}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
