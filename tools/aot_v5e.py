"""AOT-compile the main path's Pallas kernels for a TPU v5e — without a chip.

libtpu ships the real v5e compiler, Mosaic included, and
``jax.experimental.topologies`` hands out device descriptions for a
``v5e:2x2`` host under ``JAX_PLATFORMS=cpu``. Lowering a jitted function
over ``ShapeDtypeStruct``s placed on one of those devices and calling
``.compile()`` runs exactly what the chip's first dispatch would run, so a
kernel Mosaic refuses is found here, in seconds, instead of on the first
chip run (PR 20 shipped a serve kernel that had only ever run in interpret
mode; Mosaic refused it at every shape).

    JAX_PLATFORMS=cpu python tools/aot_v5e.py            # main-path set
    JAX_PLATFORMS=cpu python tools/aot_v5e.py --serve-matrix

Compiled at main-path shapes: the bin-loop histogram (32 bins) and the
lane-packed histogram (256 bins) in both precisions, the bin-loop kernel
at the benchmark cells' shape and full widths (1,002,701 x 302, the tiles
``binloop_tiles`` picks under the VMEM limit it states) and at the narrow
widths where four lanes share a grid step, and with a first-order boosted
fit's hessian stated exact (three variants), the serve-side
traversal kernel at more than one tree tile (one-byte and split codes), one
whole ``boost_chunk`` program (65,536 x 64, depth 10) with the Pallas
histogram inside, and the binning's column statistics (the chunked sort).
``--serve-matrix`` adds 8/50/200/1000 trees x depth 3/6/10/12. Exit codes:
0 all compiled, 1 a compile failed, 77 no TPU topology available here (the
tier-1 test skips on it).
"""
from __future__ import annotations

import itertools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or one that cannot describe a v5e
        print(f"no v5e topology available: {type(e).__name__}: {e}")
        return 77
    on_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)

    from transmogrifai_tpu.models import hist_pallas as HP
    from transmogrifai_tpu.models import serve_pallas as SP
    from transmogrifai_tpu.models import trees as TR

    i32, f32 = jnp.int32, jnp.float32
    n, f, k, m = 65536, 64, 2, 64
    jobs = []
    for lowp in (False, True):
        hist_args = (sds((n, f), i32), sds((k, n), i32), sds((k, n), f32),
                     sds((k, n), f32))
        jobs.append((
            f"hist binloop 32 bins lowp={lowp}",
            lambda a=hist_args, lp=lowp: HP.build_histogram_pallas_binloop
            .lower(*a, num_nodes=m, num_bins=32, lowp=lp),
        ))
        jobs.append((
            f"hist lane-packed 256 bins lowp={lowp}",
            lambda a=hist_args, lp=lowp: HP.build_histogram_pallas_batched
            .lower(*a, num_nodes=m, num_bins=256, lowp=lp),
        ))
    # the benchmark cells' wide group at the widths whose tiles ask for
    # more scoped VMEM than Mosaic's default (``binloop_tiles``), and at
    # the narrow rungs where the 4 lanes share a grid step
    # (binloop_lane_group: g lanes in one [T, g·variants·slots] operand)
    cn, cf, ck = 1_002_701, 302, 4
    cell_args = (sds((cn, cf), i32), sds((ck, cn), i32), sds((ck, cn), f32),
                 sds((ck, cn), f32))
    for slots, lowp in (
        (256, False), (256, True), (128, False), (64, False), (32, False),
        (32, True), (8, True),
    ):
        g = HP.binloop_lane_group(ck, 2, lowp, slots)
        rt, ft = HP.binloop_tiles(cf, slots, 32, lowp=lowp, lanes=g)
        jobs.append((
            f"hist binloop cell shape {slots} slots x {g} lanes a pass "
            f"lowp={lowp} (tiles {rt}/{ft})",
            lambda s_=slots, lp=lowp: HP.build_histogram_pallas_binloop
            .lower(*cell_args, num_nodes=s_, num_bins=32, lowp=lp),
        ))
    # a first-order boosted fit's statistics with the hessian stated exact
    # (g hi, g lo, h): the folded 128-slot rung and the widest operands
    for slots in (32, 128, 256):
        g = HP.binloop_lane_group(ck, 2, False, slots, exact_hess=True)
        rt, ft = HP.binloop_tiles(cf, slots, 32, lanes=g)
        jobs.append((
            f"hist binloop cell shape {slots} slots x {g} lanes a pass "
            f"exact_hess (tiles {rt}/{ft})",
            lambda s_=slots: HP.build_histogram_pallas_binloop
            .lower(*cell_args, num_nodes=s_, num_bins=32, exact_hess=True),
        ))
    # a seven-class forest's statistic axis (w and six class indicators,
    # one bfloat16 variant each) at the widths its plan allows
    class_args = (sds((cn, cf), i32), sds((ck, cn), i32),
                  sds((ck, 6, cn), f32), sds((ck, cn), f32))
    for slots in (8, 32, 64, 128):
        g = HP.binloop_lane_group(ck, 7, True, slots)
        rt, ft = HP.binloop_tiles(
            cf, slots, 32, lowp=True, stat_channels=7, lanes=g
        )
        jobs.append((
            f"hist binloop cell shape {slots} slots x {g} lanes a pass, "
            f"7 channels lowp (tiles {rt}/{ft})",
            lambda s_=slots: HP.build_histogram_pallas_binloop
            .lower(*class_args, num_nodes=s_, num_bins=32, lowp=True),
        ))
    serve = [(200, 10), (50, 12)]
    if "--serve-matrix" in argv:
        serve = list(itertools.product((8, 50, 200, 1000), (3, 6, 10, 12)))
    for (t, depth), bins in itertools.product(serve, (32, None)):
        w = 1 << depth
        tiles = -(-t // SP._plan_tiles(t, depth, None, None)[1])
        jobs.append((
            f"serve_trees T={t} depth={depth} num_bins={bins} "
            f"({tiles} tree tiles)",
            lambda t=t, depth=depth, w=w, bins=bins:
            SP.serve_trees_pallas.lower(
                sds((32768, f), i32), sds((t, depth, w), i32),
                sds((t, depth, w), i32), sds((t, w), f32), num_bins=bins,
            ),
        ))
    jobs.append((
        "boost_chunk 65536x64 K=2 depth 10, 200 rounds, pallas histograms",
        lambda: TR._boost_rounds_batched.lower(
            sds((n, f), i32), sds((n,), f32), sds((k, n), f32),
            sds((k, n), f32), sds((k,), f32), sds((), f32), sds((), f32),
            sds((), f32), sds((), f32), None,
            num_rounds=200, max_depth=10, num_bins=32,
            objective="binary:logistic", hist_impl="pallas",
        ),
    ))
    jobs.append((
        "boost_chunk 65536x64 K=4 depth 12, 2 rounds, spark:logloss "
        "(first-order targets, per-round weights, 8 chunks a level, the "
        "hessian stated exact)",
        lambda: TR._boost_rounds_batched.lower(
            sds((n, f), i32), sds((n,), f32), sds((4, n), f32),
            sds((4, n), f32), sds((4,), f32), sds((), f32), sds((), f32),
            sds((4,), f32), sds((4,), f32), None,
            num_rounds=2, max_depth=12, num_bins=32,
            objective="spark:logloss", hist_impl="pallas",
            info_gain_norm=2.0, binary_weights=True,
        ),
    ))
    jobs.append((
        "bin_column_stats 65536x70, 32 bins (3 sorts of 32 columns)",
        lambda: TR.bin_column_stats.lower(sds((n, 70), f32), max_bins=32),
    ))

    failed = 0
    for name, lower in jobs:
        t0 = time.monotonic()
        try:
            lower().compile()
        except Exception as e:
            failed += 1
            print(f"FAIL {name}: {type(e).__name__}: {str(e)[:1200]}",
                  flush=True)
        else:
            print(f"ok   {name} ({time.monotonic() - t0:.1f} s)", flush=True)
    print(f"{len(jobs) - failed} of {len(jobs)} compiled for "
          f"{topo.devices[0].device_kind}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
