"""Pallas TPU kernel: per-node gradient histograms for tree growth.

The histogram build is THE hot op of histogram GBDT (the reference runs it
in libxgboost's C++ core, SURVEY.md §2.5 item 1). The XLA scatter-add in
models/trees.py lowers to a serialized sort/scatter on TPU; this kernel
reformulates the build as matmuls so it runs on the MXU:

    hist[m, f, b] = Σ_r 1[node_r = m] · 1[binned_{r,f} = b] · v_r
                  = (NodeOneHot · v)ᵀ @ BinOneHot_f        per feature f

i.e. for every feature an [M, T] x [T, B] matmul over row tiles T — dense
systolic-array work instead of scattered memory traffic. Grad and hess are
two value columns of the same one-hot product.

Grid: (F, N/T). The output block for feature f is revisited across row
tiles (accumulation pattern: init at j==0, add afterwards). Padded rows
carry node = -1 → their one-hot row is all zero → no contribution.

The module holds every histogram builder a tree fit can take (the two
kernels, the one-hot GEMM pair, the scatter reference) and the one function
that picks among them, ``histogram_plan``.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


FEAT_TILE = 8  # features per program (TPU sublane granule)


def _hist_pack(num_bins: int) -> tuple[int, int]:
    """(pack, sub_lanes): features sharing one 128-lane bin axis. Lane
    sub·S + bin holds feature-sub's bin count, so one dot builds ``pack``
    features' histograms — a pack× FLOP cut over one-feature-per-dot."""
    if num_bins <= 32:
        return 4, 32
    if num_bins <= 64:
        return 2, 64
    if num_bins <= 128:
        return 1, 128
    return 1, _round_up(num_bins, 128)


def _hist_kernel(binned_ref, node_ref, g_ref, h_ref, outg_ref, outh_ref,
                 *, m_pad, b_pad, pack, sub_lanes, lowp, feat_tile):
    """One (fit, feature-tile, row-tile) step: accumulate grad/hess
    histograms for one batched fit (separate outputs — a trailing dim of 2
    would be tile-padded to 128 and blow VMEM). Output lanes are PACKED:
    lane sub·S + bin of group q is (feature q·pack+sub, bin) — the wrapper
    unpacks with one reshape/transpose.

    Precision: the one-hots are bf16-exact; the value operand splits into
    hi/lo bf16 halves (wg == hi + lo to ~2^-17 relative) so the dots run
    single-pass at the full bf16 MXU rate with f32 accumulation instead of
    the 6-pass f32 HIGHEST schedule — measured 6-8x on the 1M-row build.
    ``lowp`` callers assert values are ALREADY bf16-exact (RF indicators)
    and skip the lo half.

    The batch (fit) axis is a GRID dimension, not a vmap: Mosaic custom
    calls crash this TPU runtime under vmap, and a grid axis reuses the same
    VMEM working set per step anyway."""
    import jax.lax as lax
    from jax.experimental import pallas as pl

    j = pl.program_id(2)

    nodes = node_ref[0, 0, :]    # [T] int32 (-1 = padded/dead row)
    g = g_ref[0, 0, :]           # [T] f32
    h = h_ref[0, 0, :]           # [T] f32
    t = nodes.shape[0]

    # stack built DIRECTLY in the [T, nvar·M] lane space — a bf16 concat of
    # M-lane pieces costs lane-shift relayouts per step; here one compare
    # against (iota mod M) plus variant-selects assembles the same operand
    nvar = 2 if lowp else 4
    iota_s = lax.broadcasted_iota(jnp.int32, (t, nvar * m_pad), 1)
    m_lane = iota_s % m_pad
    variant = iota_s // m_pad
    oh = nodes[:, None] == m_lane                         # [T, nvar·M]
    if lowp:
        val = jnp.where(variant == 0, g[:, None], h[:, None])
    else:
        g_hi = g.astype(jnp.bfloat16).astype(jnp.float32)
        g_lo = g - g_hi
        h_hi = h.astype(jnp.bfloat16).astype(jnp.float32)
        h_lo = h - h_hi
        val = jnp.where(
            variant == 0, g_hi[:, None],
            jnp.where(
                variant == 1, g_lo[:, None],
                jnp.where(variant == 2, h_hi[:, None], h_lo[:, None]),
            ),
        )
    stack = jnp.where(oh, val, 0.0).astype(jnp.bfloat16)
    iota_b = lax.broadcasted_iota(jnp.int32, (t, b_pad), 1)
    contract = (((0,), (0,)), ((), ()))  # contract the row-tile axis

    for q in range(feat_tile // pack):
        # ONE compare per group: broadcast each sub-feature's codes onto its
        # own lane segment with nested selects, then a single 128-lane
        # equality — the per-sub compare+convert+add loop was the VPU cost
        # that dominated the whole build (trace: 18.0 of 18.6 s at 1M x 500).
        # The select chain itself measured 333 of 408 ms per 1M×500×32
        # build (round 5), which motivated the bin-loop kernel below (the
        # builder at ≤64 bins: ``histogram_plan``).
        code_b = binned_ref[q * pack + 0, :][:, None]
        for sub in range(1, pack):
            seg = binned_ref[q * pack + sub, :][:, None] + sub * sub_lanes
            code_b = jnp.where(iota_b < sub * sub_lanes, code_b, seg)
        comb_oh = (code_b == iota_b).astype(jnp.bfloat16)
        out = lax.dot_general(
            stack, comb_oh, contract,
            preferred_element_type=jnp.float32,
            precision=lax.Precision.DEFAULT,
        )  # [nvar·M, b_pad]
        if lowp:
            hg = out[:m_pad]
            hh = out[m_pad:]
        else:
            hg = out[:m_pad] + out[m_pad:2 * m_pad]
            hh = out[2 * m_pad:3 * m_pad] + out[3 * m_pad:]

        @pl.when(j == 0)
        def _(q=q, hg=hg, hh=hh):
            outg_ref[0, q, :, :] = hg
            outh_ref[0, q, :, :] = hh

        @pl.when(j > 0)
        def _(q=q, hg=hg, hh=hh):
            outg_ref[0, q, :, :] = outg_ref[0, q, :, :] + hg
            outh_ref[0, q, :, :] = outh_ref[0, q, :, :] + hh


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "num_bins", "row_tile", "lowp", "interpret"),
)
def build_histogram_pallas_batched(
    binned: jax.Array,   # [N, F] int32 codes in [0, num_bins), SHARED
    node: jax.Array,     # [K, N] int32 node slot per row per fit (-1 = dead)
    grad: jax.Array,     # [K, N] f32 (pre-masked)
    hess: jax.Array,     # [K, N] f32
    num_nodes: int,
    num_bins: int,
    row_tile: int | None = None,
    lowp: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """hist [K, num_nodes, F, num_bins, 2] via the MXU one-hot formulation
    (bin-axis packing + hi/lo bf16 value split — see _hist_kernel).

    K batched fits (grid points × CV folds) share one binned matrix; the fit
    axis rides the kernel grid, so the whole hyperparameter sweep's
    histograms build in one custom call."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k_fits, n = node.shape
    f = binned.shape[1]
    m_pad = _round_up(max(num_nodes, 8), 8)
    pack, sub_lanes = _hist_pack(num_bins)
    b_pad = pack * sub_lanes
    nvar = 2 if lowp else 4
    if row_tile is None:
        # the kernel's big VMEM temporaries are the [T, M] node one-hot,
        # its value-weighted copies, and the [T, nvar·M] stacked operand —
        # shrink the row tile as the node axis grows so T·nvar·M stays
        # bounded; lane-align to 128 (Mosaic trailing-block constraint)
        row_tile = max(
            128, min(4096, ((1 << 20) // (nvar * m_pad)) // 128 * 128)
        )

    def vmem_bytes(ft: int) -> int:
        # binned block + two output accumulators + the stacked bf16 value
        # operand + node one-hot / weighted copies / comb one-hot
        return (
            ft * row_tile * 4
            + 2 * (ft // pack) * m_pad * b_pad * 4
            + row_tile * nvar * m_pad * 2
            + row_tile * (3 * m_pad * 4 + 2 * b_pad * 2)
        )

    # feature tile: as many features per grid step as scoped VMEM (~16 MB,
    # budget 12 MB for headroom) allows — small tiles multiply grid steps,
    # and every step rebuilds the [T, nvar·M] value stack (measured
    # 74 -> 16 ms/level at 1M x 64 going from 8-feature steps to 64)
    feat_tile = FEAT_TILE
    while (
        feat_tile * 2 <= _round_up(f, FEAT_TILE)
        and vmem_bytes(feat_tile * 2) <= (12 << 20)
    ):
        feat_tile *= 2
    while vmem_bytes(feat_tile) > (12 << 20) and row_tile > 512:
        row_tile //= 2
    n_pad = _round_up(max(n, row_tile), row_tile)
    f_pad = _round_up(f, feat_tile)
    groups = f_pad // pack

    binned_t = jnp.zeros((f_pad, n_pad), dtype=jnp.int32)
    binned_t = binned_t.at[:f, :n].set(binned.T)
    # per-fit row vectors get a singleton sublane axis [K, 1, n_pad] so the
    # (1, row_tile) trailing block dims satisfy Mosaic's tiling constraint
    node_p = jnp.full((k_fits, 1, n_pad), -1, dtype=jnp.int32).at[:, 0, :n].set(node)
    g_p = jnp.zeros((k_fits, 1, n_pad), dtype=jnp.float32).at[:, 0, :n].set(grad)
    h_p = jnp.zeros((k_fits, 1, n_pad), dtype=jnp.float32).at[:, 0, :n].set(hess)

    num_row_tiles = n_pad // row_tile
    grid = (k_fits, f_pad // feat_tile, num_row_tiles)
    groups_per_tile = feat_tile // pack

    out_g, out_h = pl.pallas_call(
        functools.partial(
            _hist_kernel, m_pad=m_pad, b_pad=b_pad, pack=pack,
            sub_lanes=sub_lanes, lowp=lowp, feat_tile=feat_tile,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((k_fits, groups, m_pad, b_pad), jnp.float32),
            jax.ShapeDtypeStruct((k_fits, groups, m_pad, b_pad), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (feat_tile, row_tile), lambda k, i, j: (i, j),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, row_tile), lambda k, i, j: (k, 0, j),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, row_tile), lambda k, i, j: (k, 0, j),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, row_tile), lambda k, i, j: (k, 0, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=(
            pl.BlockSpec(
                (1, groups_per_tile, m_pad, b_pad),
                lambda k, i, j: (k, i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, groups_per_tile, m_pad, b_pad),
                lambda k, i, j: (k, i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ),
        interpret=interpret,
    )(binned_t, node_p, g_p, h_p)

    # unpack lanes: [K, G, M, pack·S] -> [K, G, M, pack, S] -> [K, F, M, B]
    def unpack(a):
        a = a.reshape(k_fits, groups, m_pad, pack, sub_lanes)
        a = jnp.transpose(a, (0, 1, 3, 2, 4))
        return a.reshape(k_fits, f_pad, m_pad, sub_lanes)

    out = jnp.stack([unpack(out_g), unpack(out_h)], axis=-1)
    return jnp.transpose(out[:, :f, :num_nodes, :num_bins, :], (0, 2, 1, 3, 4))


def _stat_rows(grad, hess) -> list:
    """The statistic channels as ``[K, N]`` rows, the hessian last."""
    if grad.ndim == 2:
        return [grad, hess]
    return [grad[:, v] for v in range(grad.shape[1])] + [hess]


def _hist_binloop_kernel(binned_ref, node_ref, *refs, m_pad, num_bins, lowp):
    """Bin-loop histogram step: one whole-block compare per bin instead of
    the per-group select-chain assembly. The comb construction drops from
    ~5 VPU ops per one-hot element to 2 (compare + convert) — the
    select-chain was measured at 333 ms of the 408 ms level cost at
    1M×500×32. Layout: binned block [feat_tile, T]
    (features on sublanes), stack [T, nvar·M]; per bin b the dot
    [feat_tile, T] @ [T, nvar·M] emits that bin's [feat_tile, nvar·M]
    plane, written at a static outermost index.

    ``refs``: the S statistic rows of the fit (its value channels, then
    the hessian / weight: ``(g, h)`` of a boosted or a two-class fit, the
    K - 1 class indicators and w of a K-class forest), then their S
    output accumulators. The stack holds one variant a statistic under
    ``lowp`` and two (hi, lo) without, statistic-major."""
    import jax.lax as lax
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    nstat = len(refs) // 2
    val_refs, out_refs = refs[:nstat], refs[nstat:]

    nodes = node_ref[0, 0, :]
    vals = [r[0, 0, :] for r in val_refs]
    t = nodes.shape[0]

    nvar = stack_variants(nstat, lowp)
    iota_s = lax.broadcasted_iota(jnp.int32, (t, nvar * m_pad), 1)
    m_lane = iota_s % m_pad
    variant = iota_s // m_pad
    oh = nodes[:, None] == m_lane
    if lowp:
        pieces = vals
    else:
        pieces = []
        for v in vals:
            hi = v.astype(jnp.bfloat16).astype(jnp.float32)
            pieces += [hi, v - hi]

    def select(i):
        """Variant i's values on its lanes, the later variants' on theirs
        (traced in the order the two-statistic kernel always had: its
        compiled form is part of what the cells measure)."""
        if i == nvar - 1:
            return pieces[i][:, None]
        return jnp.where(variant == i, pieces[i][:, None], select(i + 1))

    stack = jnp.where(oh, select(0), 0.0).astype(jnp.bfloat16)
    codes = binned_ref[...]  # [feat_tile, T] int32
    contract = (((1,), (0,)), ((), ()))  # contract the row-tile axis

    for b in range(num_bins):
        comb = (codes == b).astype(jnp.bfloat16)  # [feat_tile, T]
        out = lax.dot_general(
            comb, stack, contract,
            preferred_element_type=jnp.float32,
            precision=lax.Precision.DEFAULT,
        )  # [feat_tile, nvar·M]
        if lowp:
            sums = [out[:, s * m_pad:(s + 1) * m_pad] for s in range(nstat)]
        else:
            sums = [
                out[:, 2 * s * m_pad:(2 * s + 1) * m_pad]
                + out[:, (2 * s + 1) * m_pad:(2 * s + 2) * m_pad]
                for s in range(nstat)
            ]

        @pl.when(j == 0)
        def _(b=b, sums=sums):
            for ref, hs in zip(out_refs, sums):
                ref[0, b, :, :] = hs

        @pl.when(j > 0)
        def _(b=b, sums=sums):
            for ref, hs in zip(out_refs, sums):
                ref[0, b, :, :] = ref[0, b, :, :] + hs


# Scoped VMEM the bin-loop kernel states to Mosaic (``vmem_limit_bytes``; a
# v5e core has 128 MiB, Mosaic's default scope is 16 MiB, the serve kernel
# states 64 MiB too), and what ``binloop_tiles`` may plan for under it by
# ``binloop_vmem_bytes``. The limit itself costs nothing: the pairs that fit
# the default ran the same seconds with and without it (PR 30).
_BINLOOP_VMEM_LIMIT = 64 << 20
_BINLOOP_VMEM_BUDGET = 48 << 20
# Elements of the [T, nvar·M] stacked one-hot operand one grid step builds.
# Measured, not a memory bound: at 1,024 lanes (256 slots x 4 variants) a
# 1,024-row tile beat 2,048 rows at every feature tile (0.5295 against
# 0.5511 s at 104), at 512 lanes and under 2,048 rows won.
_BINLOOP_STACK_ELEMS = 1 << 20
# Lanes of that operand one build may have: 1,024 is the widest measured
# (256 slots x 4 variants), so a fit of more variants takes fewer slots a
# chunk (``histogram_plan``): 7 statistics at ``lowp`` 128, two 256.
_BINLOOP_STACK_LANES = 1024


def stack_variants(stat_channels: int, lowp: bool) -> int:
    """Value variants of the bin-loop kernel's stacked operand: one a
    statistic where the values are bfloat16-exact (``lowp``), else a
    (hi, lo) pair."""
    return stat_channels * (1 if lowp else 2)


def stat_channels_built(stat_channels: int, lowp: bool, slots: int) -> int:
    """Statistic channels the ``[T, nvar·M]`` operand of a ``slots``-slot
    build has lanes for: the operand pads to whole 128-lane tiles, so 7
    statistics at 32 slots (224 lanes) are built as 8, two at ``lowp`` (64
    lanes) as 4."""
    m_pad = _round_up(max(slots, 8), 8)
    per = stack_variants(stat_channels, lowp) // stat_channels
    return _round_up(per * stat_channels * m_pad, 128) // (per * m_pad)


def binloop_vmem_bytes(
    row_tile: int, feat_tile: int, num_nodes: int, num_bins: int,
    lowp: bool = False, stat_channels: int = 2,
) -> int:
    """Scoped VMEM one grid step of the bin-loop kernel takes, fitted from
    above to what Mosaic asked for at 240 (slots, bins, variants, tiles)
    points compiled for a v5e (PR 30: 0.5% to 27% over it at 128
    features a tile, 16% to 5x at 8). Mosaic double-buffers every block
    the grid pipelines: the two ``[bins, feat_tile, M]`` float32 output
    accumulators (M pads to 128 lanes: under 128 slots they cost what 128
    cost), the ``[feat_tile, T]`` codes and the node / grad / hess rows
    (8 sublanes each). On top, once, the step's temporaries per row of the
    tile: the ``[T, nvar·M]`` stack and what it is selected from (3.5
    bytes a lane), the per-bin compare of the codes (8 bytes a feature),
    and 1 KB of row vectors. ``stat_channels`` (S: two up to PR 33) scales
    what there is one of a statistic: S accumulators, S + 1 rows, the
    stack's variants (derived from the two-channel fit, not re-fitted:
    ``binloop_tiles``)."""
    m_pad = _round_up(max(num_nodes, 8), 8)
    stack_lanes = _round_up(stack_variants(stat_channels, lowp) * m_pad, 128)
    blocks = (
        stat_channels * num_bins * feat_tile * _round_up(m_pad, 128) * 4
        + feat_tile * row_tile * 4
        + (stat_channels + 1) * 8 * row_tile * 4
    )
    temps = row_tile * (stack_lanes * 7 // 2 + 8 * feat_tile + 1024)
    return 2 * blocks + temps


def binloop_tiles(
    f: int, num_nodes: int, num_bins: int, lowp: bool = False,
    stat_channels: int = 2,
) -> tuple[int, int]:
    """(row_tile, feat_tile) of the bin-loop kernel at ``num_nodes`` node
    slots, from shapes alone, chosen together. The kernel's dot is
    ``[feat_tile, T] @ [T, nvar·M]``: the feature tile is the MXU's row
    dimension, and the ``[T, nvar·M]`` operand (the VPU's work) is rebuilt
    once per feature tile. So the feature tile comes first: as few tiles
    as 128 features a tile allow, evenly filled (302 columns as 3 x 104,
    padded to 312; 128 + 128 + 46 would pad them to 384, a quarter more
    dots). Then the largest row tile the stacked operand's element cap and
    the VMEM budget leave; only if none fits, one more feature tile (no
    shape a fit can reach today: at most 256 slots and 64 bins need 42 of
    the 48 MB).

    Measured on a v5e at 1,002,701 rows x 32 bins, 4 lanes
    (``tools/bench_hist_kernel.py --grid``, PR 30; seconds a call; ``was``:
    the pair and seconds of the two-step choice this replaced, which fixed
    the row tile first and had 6 MB for the rest):

        302 columns  4 variants                lowp (2 variants)
        slots  tiles     s       was             tiles     s       was
          256  1024/104  0.5295  1024/8  2.4061  2048/104  0.2706  1024/8
          128  2048/104  0.2676  2048/16 0.6486  2048/104  0.1524  2048/32
           64  2048/104  0.1755  the same        2048/104  0.1126  the same
           32  2048/104  0.1339  the same        2048/104  0.1128  the same

        55 columns x 2 bins: 2048/56 (1024/56 at 256 slots x 4 variants)
        0.0151 s at 256 slots, 0.0102 at 32 (was 32 features: 0.0248,
        0.0181)

    At 256 slots the best pair under Mosaic's default 16 MiB scope is
    512/104, 0.5853 s (1024/104 asks for 18.0 MiB): the stated limit buys
    a tenth. 128 features a tile lost to 104 at every width (0.5981 at
    1024/128): the padding to 384 columns costs more than the fuller MXU
    gains. Every pair of {128..2048} x {8..128} compiled under the stated
    limit.

    ``stat_channels`` (S; PR 34). MEASURED at S = 7 (a seven-class
    forest: 7 one-variant channels at ``lowp``), same shape, same tool
    with ``--channels 7``, at the pairs this function gives:

        302 columns  7 channels, lowp
        slots  tiles     s        lanes of the stacked operand
          128  1024/104  0.4933   896
           64  2048/104  0.3623   448 (in 512)
           32  2048/104  0.2055   224 (in 256); two channels: 0.1128

    DERIVED, not measured: the pairs themselves. The VMEM model scales
    what there is one of a statistic (S accumulators, S + 1 rows, the
    stack's variants) from the two-channel fit and was not re-fitted;
    the row cap is the same element cap over ``S x slots`` lanes, floored
    to a power of two (7 x 128 = 896 lanes: 1,024 rows, as 4 x 256); no
    grid of pairs was timed at S = 7, and the v5e compiler took every
    pair of the table (32 MB at most of the 48 MB budget:
    ``tools/aot_v5e.py``). ``histogram_plan`` holds such a fit to
    128-slot chunks: no operand wider than the 1,024 lanes measured."""
    m_pad = _round_up(max(num_nodes, 8), 8)
    nvar = stack_variants(stat_channels, lowp)
    # a power of two, so that its halvings stay multiples of 128 lanes
    row_cap = max(
        128, min(2048, _pow2_floor(_BINLOOP_STACK_ELEMS // (nvar * m_pad)))
    )
    f8 = _round_up(f, FEAT_TILE)
    for tiles in range(-(-f8 // 128), f8 // FEAT_TILE + 1):
        feat_tile = _round_up(-(-f8 // tiles), FEAT_TILE)
        row_tile = row_cap
        while row_tile >= 128:
            if (
                binloop_vmem_bytes(
                    row_tile, feat_tile, num_nodes, num_bins, lowp,
                    stat_channels,
                ) <= _BINLOOP_VMEM_BUDGET
            ):
                return row_tile, feat_tile
            row_tile //= 2
    return 128, FEAT_TILE


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_nodes", "num_bins", "row_tile", "lowp", "interpret", "feat_tile",
    ),
)
def build_histogram_pallas_binloop(
    binned: jax.Array,   # [N, F] int32 codes in [0, num_bins), SHARED
    node: jax.Array,     # [K, N] int32 node slot per row per fit (-1 = dead)
    grad: jax.Array,     # [K, N] f32 (pre-masked), or [K, V, N]: V channels
    hess: jax.Array,     # [K, N] f32
    num_nodes: int,
    num_bins: int,
    row_tile: int | None = None,
    lowp: bool = False,
    interpret: bool = False,
    feat_tile: int | None = None,
) -> jax.Array:
    """hist [K, num_nodes, F, num_bins, S] via the bin-loop kernel (see
    _hist_binloop_kernel): S = 2 (grad, hess) from a ``[K, N]`` grad, V + 1
    (the value channels, then hess) from ``[K, V, N]``. Same contract as
    build_histogram_pallas_batched; ``row_tile`` / ``feat_tile`` override
    ``binloop_tiles`` (timing probes: tools/bench_hist_kernel.py)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k_fits, n = node.shape
    f = binned.shape[1]
    stats = _stat_rows(grad, hess)
    m_pad = _round_up(max(num_nodes, 8), 8)
    tiles = binloop_tiles(
        f, num_nodes, num_bins, lowp=lowp, stat_channels=len(stats)
    )
    row_tile, feat_tile = row_tile or tiles[0], feat_tile or tiles[1]
    n_pad = _round_up(max(n, row_tile), row_tile)
    f_pad = _round_up(f, feat_tile)

    binned_t = jnp.full((f_pad, n_pad), -1, dtype=jnp.int32)
    binned_t = binned_t.at[:f, :n].set(binned.T)
    node_p = jnp.full((k_fits, 1, n_pad), -1, dtype=jnp.int32).at[:, 0, :n].set(node)
    stats_p = [
        jnp.zeros((k_fits, 1, n_pad), dtype=jnp.float32).at[:, 0, :n].set(v)
        for v in stats
    ]

    grid = (k_fits, f_pad // feat_tile, n_pad // row_tile)
    row_spec = pl.BlockSpec(
        (1, 1, row_tile), lambda k, i, j: (k, 0, j), memory_space=pltpu.VMEM,
    )

    outs = pl.pallas_call(
        functools.partial(
            _hist_binloop_kernel, m_pad=m_pad, num_bins=num_bins, lowp=lowp,
        ),
        out_shape=tuple(
            jax.ShapeDtypeStruct(
                (k_fits, num_bins, f_pad, m_pad), jnp.float32
            )
            for _ in stats
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (feat_tile, row_tile), lambda k, i, j: (i, j),
                memory_space=pltpu.VMEM,
            ),
            row_spec,
        ] + [row_spec] * len(stats),
        out_specs=tuple(
            pl.BlockSpec(
                (1, num_bins, feat_tile, m_pad),
                lambda k, i, j: (k, 0, i, 0),
                memory_space=pltpu.VMEM,
            )
            for _ in stats
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_BINLOOP_VMEM_LIMIT
        ),
        interpret=interpret,
    )(binned_t, node_p, *stats_p)

    # S x [K, B, F, M] -> [K, M, F, B, S]
    out = jnp.stack(outs, axis=-1)
    out = jnp.transpose(out, (0, 3, 2, 1, 4))
    return out[:, :num_nodes, :f, :, :]


def build_histogram_pallas(
    binned: jax.Array,   # [N, F] int32 codes in [0, num_bins)
    node: jax.Array,     # [N] int32 node slot per row (-1 = dead)
    grad: jax.Array,     # [N] f32 (pre-masked)
    hess: jax.Array,     # [N] f32
    num_nodes: int,
    num_bins: int,
    row_tile: int | None = None,
    lowp: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """hist [num_nodes, F, num_bins, 2] — the K=1 case of the batched build."""
    return build_histogram_pallas_batched(
        binned, node[None, :], grad[None, :], hess[None, :],
        num_nodes, num_bins, row_tile=row_tile, lowp=lowp,
        interpret=interpret,
    )[0]


def build_histogram_scatter(
    binned: jax.Array,
    node: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    num_nodes: int,
    num_bins: int,
) -> jax.Array:
    """XLA scatter-add reference implementation (CPU / correctness):
    [num_nodes, F, num_bins, S] from ``grad`` [N] (S = 2) or [V, N]
    (S = V + 1, hess last).

    Every statistic scatters as its own flat [N·F] vector — a trailing
    length-S axis would be tile-padded 64× on TPU (catastrophic under the
    forest vmap)."""
    n, f = binned.shape
    col_ids = jnp.arange(f, dtype=jnp.int32)[None, :]
    safe_node = jnp.maximum(node, 0)
    alive = (node >= 0).astype(jnp.float32)
    flat = ((safe_node[:, None] * f + col_ids) * num_bins + binned).reshape(-1)
    size = num_nodes * f * num_bins
    stats = ([grad] if grad.ndim == 1 else list(grad)) + [hess]
    return jnp.stack(
        [
            jnp.zeros(size, dtype=jnp.float32).at[flat].add(
                jnp.repeat(v * alive, f)
            ).reshape(num_nodes, f, num_bins)
            for v in stats
        ],
        axis=-1,
    )


def build_histogram_scatter_batched(
    binned: jax.Array,   # [N, F] shared
    node: jax.Array,     # [K, N]
    grad: jax.Array,     # [K, N] or [K, V, N]
    hess: jax.Array,     # [K, N]
    num_nodes: int,
    num_bins: int,
) -> jax.Array:
    """[K, num_nodes, F, num_bins, S] scatter-add fallback (CPU / non-TPU)."""
    return jax.vmap(
        lambda nd, g, h: build_histogram_scatter(
            binned, nd, g, h, num_nodes, num_bins
        )
    )(node, grad, hess)


def one_hot_codes(binned: jax.Array, num_bins: int, lowp: bool) -> jax.Array:
    """[N, F·B] one-hot of the bin codes: the GEMM builder's operand. It is
    the same at every level of every tree of a fit, so it is made once a
    fit, outside the level scan (XLA's loop-invariant code motion is not
    reliable through scan+cond+fori nesting, and the temporary is small at
    GEMM row counts)."""
    dt = jnp.bfloat16 if lowp else jnp.float32
    return jax.nn.one_hot(binned, num_bins, dtype=dt).reshape(
        binned.shape[0], -1
    )


def build_histogram_gemm(
    codes1h: jax.Array,  # [N, F·B] from one_hot_codes, SHARED
    node: jax.Array,     # [K, N] int32 node slot per row per fit (-1 = dead)
    grad: jax.Array,     # [K, N] f32 (pre-masked), or [K, V, N]
    hess: jax.Array,     # [K, N] f32
    num_nodes: int,
    num_bins: int,
    lowp: bool = False,
) -> jax.Array:
    """[K, num_nodes, F, num_bins, S] histogram as one one-hot GEMM a
    statistic — the MXU-native formulation for small row counts. The
    pallas kernels' grid
    economics only win at large N; at AutoML-tabular sizes (≤4k rows) the
    whole per-level histogram is a [K·M, N] @ [N, F·B] matmul pair that XLA
    fuses into the surrounding program (measured: the depth-12 RF group
    fell from ~25 s of kernel passes to GEMM noise). Plain jnp, so it also
    serves a sharded body: the caller's psum reduces the shards'
    histograms."""
    dt = jnp.bfloat16 if lowp else jnp.float32
    node1h = jax.nn.one_hot(node, num_nodes, dtype=jnp.float32)  # [K, N, M]
    sums = [
        jnp.einsum(
            "knm,nw->kmw", (node1h * v[:, :, None]).astype(dt), codes1h,
            preferred_element_type=jnp.float32,
        )
        for v in _stat_rows(grad, hess)
    ]
    return jnp.stack(sums, axis=-1).reshape(
        node.shape[0], num_nodes, codes1h.shape[1] // num_bins, num_bins,
        len(sums),
    )


# --------------------------------------------------------------------------
# which builder a fit takes, and how many node slots one build may hold
# --------------------------------------------------------------------------
class Builder(NamedTuple):
    """One way to build a feature group's [K, M, F, B, S] histograms.
    ``prepare(binned [N, F], num_bins, lowp)`` makes the operand that is the
    same at every level (once a fit, outside the level scan);
    ``build(operand, node, grad, hess, num_nodes, num_bins, lowp=...)``
    builds one level's or one chunk's histograms from it."""

    prepare: Callable
    build: Callable


def _codes(binned, num_bins, lowp):
    return binned


BUILDERS: dict[str, Builder] = {
    # XLA scatter-add: the CPU builder and the reference (f32 whatever lowp)
    "scatter": Builder(
        _codes,
        lambda *operands, lowp=False: build_histogram_scatter_batched(
            *operands
        ),
    ),
    "gemm": Builder(one_hot_codes, build_histogram_gemm),
    "binloop": Builder(_codes, build_histogram_pallas_binloop),
    "lanepacked": Builder(_codes, build_histogram_pallas_batched),
}

# ``"pallas"`` means "whatever is fastest on the TPU": up to this many local
# rows the GEMM builder beats the kernels outright (a level's work is two
# matmuls that fuse into the program, while a kernel grid carries per-pass
# costs that dominate at small N)
_GEMM_MAX_ROWS = 4096
# The bin-loop kernel's cost is linear in the bin count (one whole-block
# compare and one dot per bin), so wide sketches keep the lane-packed
# kernel; up to 64 bins the bin-loop builds the same histograms bit for bit
# without the select chain (seconds a build by width: ``binloop_tiles``)
_BINLOOP_MAX_BINS = 64
# [K, chunk, Σ F·B, 2] histogram elements one build may hold in HBM (the
# Spark maxMemoryInMB node-group equivalent): 2^25 over the lanes, and no
# lane count shrinks it under 2^20
_HIST_BUDGET_ELEMS = 1 << 25
_HIST_BUDGET_FLOOR = 1 << 20
# GEMM: the [K, N, M] weighted node one-hots bound the chunk; the ceiling
# keeps deep levels multi-chunk, so the occupancy skip can drop the (mostly
# dead) tail of the slot range instead of paying one [K·cap, N] GEMM a level
_GEMM_ONEHOT_ELEMS = 1 << 24
_GEMM_CHUNK_CEIL = 128
# Kernels: VMEM a grid step is the [FEAT_TILE, M, b_pad] x 2 output block
# (the feature axis is gridded, F does not multiply in) plus the [T, M]
# one-hot temporaries (the kernels shrink their row tile as M grows)
_KERNEL_BLOCK_ELEMS = 1 << 19
_KERNEL_CHUNK_CEIL = 256
# [K, parents, Σ F·B, S] histogram elements a fit may KEEP from one level to
# the next so that a split's two children share one build (the lighter child
# is built, the heavier is parent − sibling: ``trees._grow_tree_impl``). Its
# own literal, not ``_HIST_BUDGET_ELEMS``: that one bounds a single build's
# output (128 MB), this one a buffer that lives across the level scan, twice
# while a level writes its own beside its parents'. Sized on the cells'
# depth-12 programs at 4 lanes x 1,024 parents x 9,774 cells: two channels
# 0.8e8 elements (317 MB), the seven-class forest's seven 2.8e8 (1.11 GB,
# beside 4.5 GiB of temporaries on a 16 GB chip); 2^29 (2 GiB of float32)
# leaves that one twice its size and refuses 8 such lanes.
_PARENT_HIST_BUDGET_ELEMS = 1 << 29


class HistogramPlan(NamedTuple):
    builders: tuple[str, ...]  # key of BUILDERS, one per feature group
    chunk_cap: int             # most node slots one build may hold (2^j)
    # nodes whose histograms the fit keeps for the next level's subtraction
    # (0: none, every node is built directly)
    parent_slots: int = 0


def _pow2_floor(x: int) -> int:
    return 1 << (x.bit_length() - 1)


def histogram_plan(
    impl: str, n: int, k_fits: int, groups: Sequence[tuple[int, int]],
    max_slots: int, stat_channels: int = 2, lowp: bool = False,
    max_parents: int = 0,
) -> HistogramPlan:
    """How a fit builds its histograms, from what its trace can see:
    ``impl`` ('pallas' = choose for the TPU; 'gemm' / 'scatter' force one
    builder), the LOCAL rows ``n`` and lanes ``k_fits`` of one build, the
    feature groups' ``(columns, bins)``, ``max_slots``, the most compact
    node slots a level can have live, and the fit's statistic channels
    (two: grad and hess, or w*y and w; K for a K-class forest) with
    whether their values are bfloat16-exact (``lowp``). This is the one
    place that decides; it runs while a program is traced, never per
    call.

    Every builder takes the statistic axis but the lane-packed kernel,
    whose two accumulators are its layout: a fit of more channels is
    planned onto the bin-loop kernel whatever its bins. The kernels' chunk
    also holds the stacked operand to ``_BINLOOP_STACK_LANES`` (two
    channels: 256 slots as before; 7 at ``lowp``: 128).

    ``max_parents``: the most nodes a level that is not the last can hold
    (0 from a fit with no such level, or one that may not subtract: the
    sharded path). The fit keeps that many histograms a lane from level to
    level (``parent_slots``) if they fit ``_PARENT_HIST_BUDGET_ELEMS``,
    whichever builder it takes; else none, and it builds every node."""
    cells = sum(f * b for f, b in groups)
    kept = k_fits * max_parents * cells * stat_channels
    parent_slots = max_parents if kept <= _PARENT_HIST_BUDGET_ELEMS else 0
    hist_width = cells * stat_channels // 2
    budget = max(_HIST_BUDGET_ELEMS // k_fits, _HIST_BUDGET_FLOOR)
    cap = min(_pow2_floor(max(1, budget // max(hist_width, 1))), max_slots)
    if impl == "gemm" or (impl == "pallas" and n <= _GEMM_MAX_ROWS):
        builders = ("gemm",) * len(groups)
        ceil = min(_GEMM_CHUNK_CEIL, _GEMM_ONEHOT_ELEMS // max(k_fits * n, 1))
    elif impl == "pallas":
        builders = tuple(
            "binloop" if b <= _BINLOOP_MAX_BINS or stat_channels > 2
            else "lanepacked"
            for _, b in groups
        )
        b_pad = _round_up(max(b for _, b in groups), 128)
        ceil = min(
            _KERNEL_CHUNK_CEIL, _KERNEL_BLOCK_ELEMS // (8 * b_pad),
            _BINLOOP_STACK_LANES // stack_variants(stat_channels, lowp),
        )
    else:
        return HistogramPlan(("scatter",) * len(groups), cap, parent_slots)
    return HistogramPlan(
        builders, min(cap, _pow2_floor(max(8, ceil))), parent_slots
    )


def default_impl() -> str:
    """'pallas' on real TPU backends, 'scatter' elsewhere (CPU tests run the
    kernels via interpret mode in the dedicated unit tests only)."""
    return "pallas" if jax.default_backend() == "tpu" else "scatter"
