"""Pallas TPU kernel: serve-side vectorized multi-tree traversal.

Serving a fitted ensemble is a traversal, not a matmul: ``predict_tree``
walks the level arrays with per-row gathers (``lax.scan`` over levels of
small-table lookups), which lowers to serialized dynamic-gathers on TPU —
fine at fit time where the histogram build dominates, but at serve time
the traversal IS the program. This kernel reformulates the whole
ensemble's traversal as level-synchronous one-hot linear algebra over the
quantized/binned plane, the same trick the fit-side histogram kernel
(``models/hist_pallas.py``) uses for its scatter:

    code[r, t, m]  = Σ_f binned[r, f] · 1[split_feat[t, l, m] = f]   (MXU)
    right[r, t, m] = 1[code > split_bin] · 1[split_feat ≥ 0]          (VPU)
    p_{l+1}[r, t, 2m + right] = p_l[r, t, m] · selector               (VPU)

i.e. per level one [R, F] x [F, Tt·2^l] matmul routes every (row, tree)
pair one level down, and the last level picks each row's left or right
leaf value without materializing the leaf one-hot. All arithmetic is exact
(one-hots and integer bin codes in bf16 operands, f32 accumulation; codes
past one byte are split into bytes), so per-tree values are BIT-IDENTICAL
to the gather traversal — pinned by the interpret-mode CPU twin in the
unit tests and against the gather programs on the chip
(``tests/test_hist_pallas_tpu.py``).

What Mosaic accepts shapes the layout. Everything in the kernel is 2-D
[rows, lanes] with lane = node' · tree_tile + tree (``_level_layout``): a
level's lane span doubles by an aligned copy of the right children behind
the left ones, so no lane-splitting reshape, lane interleave or sub-128
lane slice is ever needed (the first version of this kernel had all three
and was refused at every shape). ``tools/aot_v5e.py`` compiles it for a
v5e without a chip; tier-1 runs that.

Grid: (tree tiles, row tiles), tree tiles outermost so a tile's level
arrays stay resident while the row tiles stream past. Each step holds the
[row_tile, tree_tile · 2^(depth-1)] node one-hot in a VMEM scratch
(budgeted to 8 MB, ``_plan_tiles``) and walks each level in 512-lane
chunks. Padded rows produce garbage sliced off by the wrapper; padded
trees carry ``split_feat = -1`` and a zero leaf table so they contribute
exactly 0 to every sum. The output is [T, N] with rows on the lanes — the
layout of ``vmap(predict_tree)`` — and both wrappers reduce it with the
gather path's own ``trees.sum_trees``.

``serve_impl()`` picks the implementation (env ``TPTPU_SERVE_TREES``
overrides; Pallas on real TPU backends, the gather scan elsewhere), and
``program_trace_specs()`` registers the kernel with the TPJ program bank
gate so admissions get bucket-stable fingerprints like every other
serving program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .trees import sum_trees, weighted_tree_sum


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _bit_reverse(v: np.ndarray, bits: int) -> np.ndarray:
    out = np.zeros_like(v)
    for b in range(bits):
        out |= ((v >> b) & 1) << (bits - 1 - b)
    return out


#: lanes of one level processed per inner step (bounds the [R, chunk]
#: temporaries; a multiple of the 128-lane vreg width)
_CHUNK = 512
#: f32 bytes the node one-hot scratch may take (it is not double-buffered)
_NOH_BYTES = 8 << 20
#: scoped-VMEM ceiling asked of Mosaic: scratch + double-buffered level
#: blocks + chunk temporaries stay under half of it at every planned tile
_VMEM_LIMIT = 64 << 20


def _plan_tiles(t: int, depth: int, row_tile: int | None,
                tree_tile: int | None) -> tuple[int, int]:
    """(row_tile, tree_tile) from the ensemble's shape. The tree tile is a
    power of two no wider than 128 lanes or than the ensemble needs, and
    small enough that the [row_tile, tree_tile * 2^(depth-1)] node one-hot
    fits its VMEM budget; deep trees shrink the row tile before they
    shrink the tree tile below 8. Row tiles are multiples of 128: rows
    are the lanes of the output block."""
    half = (1 << depth) // 2
    auto_rows = row_tile is None
    if auto_rows:
        row_tile = 256
    if tree_tile is None:
        def fits(tt: int) -> bool:
            return row_tile * tt * half * 4 <= _NOH_BYTES

        tree_tile = min(128, 1 << max(t - 1, 0).bit_length())
        if auto_rows and not fits(min(tree_tile, 8)):
            row_tile = 128
        while tree_tile > 1 and not fits(tree_tile):
            tree_tile //= 2
    if tree_tile & (tree_tile - 1) or not 1 <= tree_tile <= 128:
        raise ValueError(
            f"tree_tile must be a power of two in [1, 128], got {tree_tile}"
        )
    if row_tile % 128:
        raise ValueError(f"row_tile must be a multiple of 128, got {row_tile}")
    return row_tile, tree_tile


def _level_layout(depth: int, tree_tile: int):
    """Static lane layout of one tree tile's level arrays.

    A lane is ``node' * tree_tile + tree``: node-major, so a level's lane
    span doubles by appending the right children after the left ones (an
    aligned copy, never a lane interleave). ``node'`` is the node's path
    with the root decision in bit 0 — the bit reversal of the gather
    traversal's ``node * 2 + go_right`` index. ``k`` is the level at which
    ``2^k * tree_tile`` fills the 128 lanes of one vreg; levels above it
    (and the leaves of a tree shallower than ``k``) are stored replicated
    at that width, lane ``node'`` carrying the node its low bits name.

    Returns (k, per-level gather index into the standard node order,
    per-level lane offset, total level lanes, leaf gather index, leaf
    keep-mask — replicas beyond the first hold no leaf value)."""
    k = (128 // tree_tile).bit_length() - 1
    idx, offs, off = [], [], 0
    for lvl in range(depth):
        m = np.arange(1 << max(lvl, k), dtype=np.int64)
        idx.append(_bit_reverse(m & ((1 << lvl) - 1), lvl))
        offs.append(off)
        off += len(m) * tree_tile
    m = np.arange(1 << max(depth, k), dtype=np.int64)
    leaf_idx = _bit_reverse(m & ((1 << depth) - 1), depth)
    return k, idx, tuple(offs), off, leaf_idx, m < (1 << depth)


def _serve_kernel(codes_ref, p_ref, lv_ref, out_ref, noh_ref, *, depth,
                  tree_tile, k, offs, f_pad, wide):
    """One (tree-tile, row-tile) step: route the block's rows through the
    tile's trees level by level and emit per-(row, tree) leaf values.
    Every array is 2-D [rows, lanes] with lane = node' * tree_tile + tree
    (see ``_level_layout``)."""
    import jax.lax as lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    codes = codes_ref[...]                      # [R, Kp] bf16 (exact ints)
    r, kp = codes.shape
    tt = tree_tile

    def go_right(off, width):
        """1.0 where the row goes right at the node in P lane off+j."""
        sf = p_ref[0:1, pl.ds(off, width)]      # [1, width] int32, -1 leaf
        sb = p_ref[1:2, pl.ds(off, width)]
        row = lax.broadcasted_iota(jnp.int32, (kp, width), 0)
        if wide:
            # codes arrive as [hi | lo] bytes; 256·hi + lo is exact in the
            # f32 accumulator where a single bf16 code is not
            g = jnp.where(
                row == sf, 256.0, jnp.where(row - f_pad == sf, 1.0, 0.0)
            )
        else:
            g = jnp.where(row == sf, 1.0, 0.0)
        # routed code per (row, node, tree) — ONE MXU dot; sf = -1 selects
        # nothing
        c = jnp.dot(
            codes, g.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        return jnp.where((c > sb.astype(jnp.float32)) & (sf >= 0), 1.0, 0.0)

    def emit(x):
        """[R, 128] lanes -> per-tree sums, stored [tt, R] (rows on the
        lanes: the output block is lane-dense at any tree tile)."""
        shift = 64
        while shift >= tt:
            # every lane ends up holding the sum of the lanes congruent
            # to it mod tt; all but one addend are exact zeros
            x = x + pltpu.roll(x, shift, 1)
            shift //= 2
        out_ref[...] = x.T[:tt, :]

    # levels [0, k): one vreg column wide; lane node' keeps its mass while
    # the decision at each level matches that level's bit of node'
    noh = jnp.ones((r, 128), jnp.float32)
    node = lax.broadcasted_iota(jnp.int32, (r, 128), 1) // tt
    for lvl in range(min(k, depth)):
        gr = go_right(offs[lvl], 128)
        noh = noh * jnp.where(((node >> lvl) & 1) == 1, gr, 1.0 - gr)
    if depth <= k:
        emit(noh * lv_ref[0:1, :])
        return
    noh_ref[:, 0:128] = noh

    # levels [k, depth): the lane span doubles — left children stay in
    # place, right children land one span further on
    for lvl in range(k, depth):
        w_l = tt << lvl
        chunk = min(w_l, _CHUNK)
        last = lvl == depth - 1

        def step(j, acc, lvl=lvl, w_l=w_l, chunk=chunk, last=last):
            at = pl.multiple_of(j * chunk, 128)
            here = noh_ref[:, pl.ds(at, chunk)]
            gr = go_right(pl.multiple_of(offs[lvl] + j * chunk, 128), chunk)
            if not last:
                right = here * gr
                noh_ref[:, pl.ds(at, chunk)] = here - right
                noh_ref[:, pl.ds(w_l + at, chunk)] = right
                return acc
            # the leaf level is never materialized: pick each lane's
            # left or right leaf value and reduce
            leaf = here * jnp.where(
                gr > 0.0,
                lv_ref[0:1, pl.ds(w_l + at, chunk)],
                lv_ref[0:1, pl.ds(at, chunk)],
            )
            for s in range(chunk // 128):
                acc = acc + leaf[:, s * 128:(s + 1) * 128]
            return acc

        acc = lax.fori_loop(
            0, w_l // chunk, step, jnp.zeros((r, 128), jnp.float32)
        )
    emit(acc)


@functools.partial(
    jax.jit,
    static_argnames=("row_tile", "tree_tile", "interpret", "num_bins"),
)
def serve_trees_pallas(
    binned: jax.Array,      # [N, F] int32 bin codes (bin_data output)
    split_feat: jax.Array,  # [T, depth, 2^depth] int32, -1 = leaf
    split_bin: jax.Array,   # [T, depth, 2^depth] int32
    leaf_value: jax.Array,  # [T, 2^depth] f32
    row_tile: int | None = None,
    tree_tile: int | None = None,
    interpret: bool = False,
    num_bins: int | None = None,
) -> jax.Array:
    """Per-tree leaf value for every row -> [T, N] f32, the layout and the
    bits of ``vmap(predict_tree)``. Callers reduce over axis 0 (sum for
    boosting, mean for forests) outside, with the gather path's own
    expression — the reduction is where the families differ.
    ``num_bins`` (static, from the threshold table) lets codes below 256
    ride one bf16 operand; unknown or wider codes are split into bytes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, f = binned.shape
    t, depth, w = split_feat.shape
    if w != 1 << depth or leaf_value.shape != (t, w):
        raise ValueError(
            f"expected perfect-tree arrays [T, depth, 2^depth] / "
            f"[T, 2^depth], got {split_feat.shape} / {leaf_value.shape}"
        )
    row_tile, tree_tile = _plan_tiles(t, depth, row_tile, tree_tile)
    k, idx, offs, p_lanes, leaf_idx, leaf_keep = _level_layout(
        depth, tree_tile
    )
    wide = num_bins is None or num_bins > 256
    f_pad = _round_up(f, 16)
    n_pad = _round_up(max(n, row_tile), row_tile)
    t_pad = _round_up(t, tree_tile)
    tiles = t_pad // tree_tile

    codes = jnp.zeros((n_pad, f_pad), jnp.int32).at[:n, :f].set(binned)
    if wide:
        codes = jnp.concatenate([codes >> 8, codes & 255], axis=1)
    codes = codes.astype(jnp.bfloat16)

    def lanes(a):
        """[t_pad, nodes] -> [tiles, nodes * tree_tile], node-major."""
        a = a.reshape(tiles, tree_tile, a.shape[1])
        return a.transpose(0, 2, 1).reshape(tiles, -1)

    sf = jnp.full((t_pad, depth, w), -1, jnp.int32).at[:t].set(split_feat)
    sb = jnp.zeros((t_pad, depth, w), jnp.int32).at[:t].set(split_bin)
    lv = jnp.zeros((t_pad, w), jnp.float32).at[:t].set(leaf_value)
    p = jnp.stack(
        [
            jnp.concatenate(
                [lanes(a[:, lvl, idx[lvl]]) for lvl in range(depth)], axis=1
            )
            for a in (sf, sb)
        ],
        axis=1,
    )                                            # [tiles, 2, p_lanes]
    lv = lanes(jnp.where(leaf_keep, lv[:, leaf_idx], 0.0))[:, None, :]

    kp = codes.shape[1]
    noh_lanes = max(128, tree_tile * (w // 2))
    out = pl.pallas_call(
        functools.partial(
            _serve_kernel, depth=depth, tree_tile=tree_tile, k=k,
            offs=offs, f_pad=f_pad, wide=wide,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (tiles, tree_tile, n_pad), jnp.float32
        ),
        # tree tiles outermost: a tile's level arrays stay resident in
        # VMEM while the row tiles stream past
        grid=(tiles, n_pad // row_tile),
        in_specs=[
            pl.BlockSpec((row_tile, kp), lambda j, i: (i, 0)),
            pl.BlockSpec((None, 2, p_lanes), lambda j, i: (j, 0, 0)),
            pl.BlockSpec((None, 1, lv.shape[2]), lambda j, i: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, tree_tile, row_tile), lambda j, i: (j, 0, i)
        ),
        scratch_shapes=[pltpu.VMEM((row_tile, noh_lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        name="serve_trees",
        interpret=interpret,
    )(codes, p, lv)
    return out.reshape(t_pad, n_pad)[:t, :n]


def predict_forest_pallas(binned, trees, interpret: bool = False,
                          num_bins: int | None = None):
    """Mean leaf value across the stacked forest -> [N] (the
    ``predict_forest`` contract over the Pallas traversal)."""
    per_tree = serve_trees_pallas(
        binned, trees.split_feat, trees.split_bin, trees.leaf_value,
        interpret=interpret, num_bins=num_bins,
    )
    return sum_trees(per_tree) / per_tree.shape[0]


def predict_boosted_pallas(binned, trees, eta, base_score,
                           interpret: bool = False,
                           num_bins: int | None = None):
    """base + the weighted sum of the rounds -> [N] (the
    ``predict_boosted`` contract: ``eta`` a scalar or per-tree weights)."""
    per_tree = serve_trees_pallas(
        binned, trees.split_feat, trees.split_bin, trees.leaf_value,
        interpret=interpret, num_bins=num_bins,
    )
    return base_score + weighted_tree_sum(per_tree, eta)


def serve_impl() -> str:
    """'pallas' on real TPU backends, 'gather' (the lax.scan traversal)
    elsewhere; env ``TPTPU_SERVE_TREES`` forces either. CPU callers that
    force 'pallas' run the kernel in interpret mode — the CPU twin the
    unit tests pin parity with."""
    import os

    forced = os.environ.get("TPTPU_SERVE_TREES")
    if forced:
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "gather"


def serve_interpret() -> bool:
    """Interpret-mode flag for the current backend (True off-TPU)."""
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# compiled-program contract audit (analysis/program.py, TPJ0xx)
# --------------------------------------------------------------------------
def program_trace_specs():
    """The serve-side traversal kernel over a representative small
    ensemble, bucketed on the BATCH axis like the fused serving programs
    (TPJ bank gate + TPJ005 bucket-fingerprint stability)."""
    i32, f32 = "int32", "float32"
    depth, w, t, f = 3, 8, 5, 6

    def _build(n: int):
        return (
            (
                jax.ShapeDtypeStruct((n, f), i32),
                jax.ShapeDtypeStruct((t, depth, w), i32),
                jax.ShapeDtypeStruct((t, depth, w), i32),
                jax.ShapeDtypeStruct((t, w), f32),
            ),
            dict(row_tile=128, tree_tile=8, interpret=True),
        )

    return [
        dict(
            name="serve_trees",
            fn=serve_trees_pallas,
            build=_build,
            buckets=(8, 16),
            static_argnames=("row_tile", "tree_tile", "interpret"),
            scoring=True,
        ),
    ]
