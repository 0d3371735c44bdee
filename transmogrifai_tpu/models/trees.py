"""Histogram-based decision-tree machinery — the XLA-native replacement for
libxgboost (JNI/C++) and Spark MLlib's JVM tree ensembles (SURVEY.md §2.5
item 1, the largest native-parity item).

Design (TPU-first, static shapes throughout — SURVEY.md §7 hard-part 1):
  * features are quantile-binned once into int32 codes [N, F] (host-side
    thresholds, in-graph binning);
  * a tree grows LEVEL-WISE to a fixed ``max_depth``: level d has exactly
    2^d node slots; nodes that stop splitting carry split_feat = -1 and
    route every row left, so shapes never depend on data;
  * per-level histograms hist[node, feature, bin] of (grad, hess) are ONE
    scatter-add over flattened keys — the XLA analog of XGBoost's C++
    histogram build, and the reduction is a psum when rows are sharded
    over the mesh 'data' axis;
  * split gain is the XGBoost second-order formula
    0.5*(GL²/(HL+λ) + GR²/(HR+λ) − G²/(H+λ)) − γ, with
    min_child_weight / min_info_gain masks; with h ≡ 1 and λ=0 this is
    exactly CART variance reduction, so the same learner serves
    RandomForest/GBT (Spark semantics) and XGBoost;
  * whole forests train under ``vmap`` over bootstrap/feature masks; boosting
    runs as ``lax.scan`` over rounds.

Leaf values are -G/(H+λ) (Newton step). For plain mean-target trees (random
forest leaves) pass g = -target, h = 1: the leaf value becomes mean(target).

The statistic a node holds has S channels: V value channels and the hessian
/ weight. V is 1 for every boosted fit, every regression forest and the
two-class forest (g, h); a K-class forest has V = K - 1, the (negated)
indicators of classes 1 … K - 1, so that a node holds its K class counts
(class 0's is W less the others), its impurity is the K-class Gini and its
leaf the class distribution C_k / W, k = 0 … K - 1 (``GINI``). K = 2 is the
(w·y, w) pair the forest always had, its leaf the class-1 share: one code
for every K.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import metrics as _tm
from ..telemetry import runlog as _runlog
from ..telemetry import spans as _tspans


class Tree(NamedTuple):
    """Dense perfect-binary-tree arrays. Level d uses slots [0, 2^d)."""

    split_feat: jax.Array  # [depth, 2^depth] int32, -1 = leaf (route left)
    split_bin: jax.Array   # [depth, 2^depth] int32, go right when bin > split_bin
    leaf_value: jax.Array  # [2^depth] float32; [2^depth, V] of V values a leaf


class HistSlots(NamedTuple):
    """What a fit counted of its own work, per tree and level (``[...,
    depth]`` int32). ``live``: the slots of the level's histogram builds
    that held a node (the widest lane's): its live compact nodes where
    every node is built, its sibling PAIRS at a level that builds one child
    of each and subtracts for the other; ``built``: the slots those builds
    were made at (the rung of ``_width_ladder`` taken, or ``chunk_nodes``
    per chunk that ran; 0 for a level the early exit skipped), so ``live /
    built`` is the occupancy of the histogram kernel's node axis.
    ``chunks_run`` / ``chunks_skipped``: the builds made, and the chunks
    the occupancy branch left out. ``nodes_built`` / ``nodes_derived``:
    over every lane, the live nodes whose histogram came from a build, and
    from ``parent - sibling`` (their sum is the level's live nodes).
    ``subset_admitted`` / ``subset_pairs``: over every
    lane's live nodes, the (node, feature) pairs the split search admitted
    and all there were (both 0 from a fit that draws no node subsets:
    boosting). ``rounds_label`` / ``rounds_residual`` (per round, not per
    level): the lanes whose tree of that round was fitted to the labels
    themselves, and to a pseudo-residual of the margin so far — counted by
    ``_boost_chunk_body`` under a ``spark:*`` objective, 0 from every other
    fit (a Newton round is neither; a forest has no rounds)."""

    live: jax.Array
    built: jax.Array
    chunks_run: jax.Array
    chunks_skipped: jax.Array
    subset_admitted: jax.Array
    subset_pairs: jax.Array
    rounds_label: jax.Array
    rounds_residual: jax.Array
    nodes_built: jax.Array
    nodes_derived: jax.Array


class _Siblings(NamedTuple):
    """What a level of ``_grow_tree_impl`` hands the next for its sibling
    subtraction. The next level's pair j is the j-th node (in compact slot
    order) that split here; its children get the compact slots 2j, 2j + 1."""

    takes_part: jax.Array   # [K, N] bool: the row is in the child to build
    n_pairs: jax.Array      # [K] int32: nodes that split, a lane
    parent_slot: jax.Array  # [K, pairs] int32: pair -> its parent's slot here
    built_right: jax.Array  # [K, pairs] bool: the child to build is the right
    hists: tuple            # per feature group [K·keep, Fg·Bg·S] float32


def sibling_rows(built, parent, built_right, slot_live):
    """The histograms of a level's nodes from those of ONE child of each
    sibling pair. Rows are (lane, slot), a row one node's histogram:
    ``built`` [K·P, X] the P pairs' built children, ``parent`` [K·P, X]
    their parents, ``built_right`` [K, P] whether the built child is the
    right one, ``slot_live`` [K, 2P] the compact slots a node lives in.
    Pair j's children are the compact slots 2j and 2j + 1: [K·2P, X], the
    other child its parent less the one built, in float32. A slot no node
    lives in (the root's sibling; past a lane's last pair, whose parent row
    is whatever slot 0 held) is empty."""
    other = parent - built
    right = built_right.reshape(-1, 1)
    rows = jnp.stack(
        [jnp.where(right, other, built), jnp.where(right, built, other)],
        axis=1,
    ).reshape(2 * built.shape[0], -1)
    return jnp.where(slot_live.reshape(-1, 1), rows, 0.0)


# Bit of the routing table's threshold entries that says which child of the
# split the next level builds (a bin code stays far under it)
_BUILT_BIT = 16


#: ``info_gain_norm`` of the Spark families (``_grow_tree_impl``): the
#: variance of a real target, and the Gini impurity of a class label whose
#: indicators are the fit's value channels
VARIANCE = 2.0
GINI = 4.0

# The narrowest width a level's histograms are built at. At 32 slots the
# bin-loop kernel's [T, nvar·M] operand is exactly one 128-lane tile
# (nvar 4); below it the lanes only pad (on a v5e at 1M x 302 x 32 bins a
# build takes 0.134 s at 32 slots and at 8, 0.176 s at 64: PR 26).
_HIST_WIDTH_FLOOR = 32
_HIST_WIDTH_RUNGS = 4


def _width_ladder(chunk_nodes: int) -> tuple[int, ...]:
    """The widths (node slots, ascending) a level's histograms may be built
    at: ``chunk_nodes`` and up to three halvings of it, none under the
    floor. Each rung is one more copy of the level body in the executable,
    so there are at most four; a ``chunk_nodes`` at or under the floor has
    the one rung."""
    rungs = [chunk_nodes]
    while (
        len(rungs) < _HIST_WIDTH_RUNGS
        and rungs[0] // 2 >= _HIST_WIDTH_FLOOR
    ):
        rungs.insert(0, rungs[0] // 2)
    return tuple(rungs)


def _slot_layout(
    impl: str, n: int, k_fits: int, groups, max_depth: int,
    axis_size: int = 1, sharded: bool = False, stat_channels: int = 2,
    lowp: bool = False,
):
    """(cap, plan, ladder) of one tree fit, from what its trace can see:
    ``cap`` compact node slots a level can have live (``2^max_depth``, or
    the power of two that holds the GLOBAL rows where there are fewer),
    ``hist_pallas.histogram_plan``'s builders and chunk width for the
    LOCAL rows ``n`` and the fit's ``stat_channels`` (bfloat16-exact where
    ``lowp``), and the widths a level may be built at; ``plan.parent_slots``
    are the nodes a lane whose histograms a level keeps for the next one's
    sibling subtraction. The sharded path keeps the full width and builds
    every node: its psums may not sit under a data-dependent branch."""
    from .hist_pallas import histogram_plan

    max_nodes = 1 << max_depth
    n_global = n * axis_size
    cap = max_nodes
    if cap > n_global:
        cap = 1
        while cap < n_global:
            cap <<= 1
        cap = min(cap, max_nodes)
    # the most nodes a level that is not the last can hold: what the next
    # level's sibling subtraction can ask for (the sharded path builds every
    # node: its psums stay at one width, outside every branch)
    max_parents = 0 if sharded or max_depth < 2 else min(cap, max_nodes // 4)
    plan = histogram_plan(
        impl, n, k_fits, groups, cap, stat_channels=stat_channels, lowp=lowp,
        max_parents=max_parents,
    )
    ladder = (plan.chunk_cap,) if sharded else _width_ladder(plan.chunk_cap)
    return cap, plan, ladder


def _dispatch_layout(n, k_fits, groups, max_depth, lowp, impl, shards,
                     stat_channels):
    """(plan, ladder) of a fit as its program will be traced, from what a
    ``tree/fit_dispatch`` span knows: the rows of the whole fit and the
    data-axis size of the mesh it runs on (``shards``; None: one device)."""
    axis_size = shards or 1
    _, plan, ladder = _slot_layout(
        impl or _resolved_impl(), -(-n // axis_size), k_fits, groups,
        max_depth, axis_size, sharded=shards is not None,
        stat_channels=stat_channels, lowp=lowp,
    )
    return plan, ladder


def hist_tiles(
    n: int, k_fits: int, groups, max_depth: int, lowp: bool,
    impl: str | None = None, shards: int | None = None,
    stat_channels: int = 2,
) -> str:
    """The ``hist_tiles`` attribute of ``tree/fit_dispatch``: for each rung
    of the fit's width ladder, ``slots:row_tile/feat_tile`` of the bin-loop
    kernel over the widest feature group (the one with the most histogram
    cells), so a trace reader can tell which table of
    ``hist_pallas.binloop_tiles`` a run used. ``groups``: the fit's
    ``(columns, bins)``; ``n``: the rows of the whole fit; ``shards``: the
    data-axis size of the mesh a sharded fit runs on (None: one device);
    ``stat_channels``: the fit's statistic channels (K for a K-class
    forest). ``none`` where that group takes another builder."""
    from .hist_pallas import binloop_tiles

    plan, ladder = _dispatch_layout(
        n, k_fits, groups, max_depth, lowp, impl, shards, stat_channels
    )
    (f, b), builder = max(
        zip(groups, plan.builders), key=lambda gb: gb[0][0] * gb[0][1]
    )
    if builder != "binloop":
        return "none"
    return " ".join(
        "{}:{}/{}".format(
            w, *binloop_tiles(f, w, b, lowp=lowp, stat_channels=stat_channels)
        )
        for w in ladder
    )


def stat_channels_built(
    n: int, k_fits: int, groups, max_depth: int, lowp: bool,
    stat_channels: int, impl: str | None = None, shards: int | None = None,
) -> int:
    """The ``stat_channels_built`` attribute of ``tree/fit_dispatch``: the
    statistic channels the bin-loop kernel's stacked operand has lanes for
    at the narrowest rung of the fit's width ladder
    (``hist_pallas.stat_channels_built``), from the shapes ``hist_tiles``
    takes. Reckoned whichever builder the fit takes: it is what the
    channel count costs on the TPU's kernel."""
    from .hist_pallas import stat_channels_built as built

    _, ladder = _dispatch_layout(
        n, k_fits, groups, max_depth, lowp, impl, shards, stat_channels
    )
    return built(stat_channels, lowp, ladder[0])


class HistSlotStats(_tm.LedgerCore):
    """Cumulative sums of the ``HistSlots`` of the fits whose outputs
    ``await_outputs`` landed (part of the ``tree`` ledger: ``models/gbdt.py``
    registers it beside the bin cache's counters)."""

    #: ledger key of each ``HistSlots`` field
    KEYS = {
        "live": "histSlotsLive", "built": "histSlotsBuilt",
        "chunks_run": "chunksRun", "chunks_skipped": "chunksSkipped",
        "subset_admitted": "nodeSubsetAdmitted",
        "subset_pairs": "nodeSubsetPairs",
        "rounds_label": "boostRoundsLabel",
        "rounds_residual": "boostRoundsResidual",
        "nodes_built": "histNodesBuilt", "nodes_derived": "histNodesDerived",
    }

    #: per ``tree/fit_dispatch``: the fit's statistic channels and those
    #: its kernel operand has lanes for (``stat_channels_built``)
    CHANNEL_KEYS = ("statChannels", "statChannelsBuilt")

    def __init__(self) -> None:
        super().__init__(tuple(self.KEYS.values()) + self.CHANNEL_KEYS)

    def record_channels(self, channels: int, built: int) -> None:
        with self._lock:
            self._counts["statChannels"] += channels
            self._counts["statChannelsBuilt"] += built

    def record(self, sums: dict) -> None:
        """``sums``: field of ``HistSlots`` -> its sum over one fit."""
        with self._lock:
            for field, value in sums.items():
                self._counts[self.KEYS[field]] += value

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._counts)


_HIST_SLOT_STATS = HistSlotStats()


def hist_slot_stats() -> HistSlotStats:
    return _HIST_SLOT_STATS


def await_outputs(value, hist_slots: HistSlots | None = None):
    """``value`` (an array or a pytree of arrays) on the host. A read of a
    device result blocks until the program that makes it has run, so it is
    a ``tree/await_outputs`` span — how long the host waited for the
    device — and one download on the run ledger's transfer census. What is
    on the host already passes through. ``hist_slots`` are the counts the
    same fit program returned: once its outputs have landed they are there
    too, and their sums go onto the span (``slots_live``, ``slots_built``,
    ``chunks_run``, ``chunks_skipped``, ``nodes_built``, ``nodes_derived``;
    from a fit that counts node
    subsets ``subset_admitted``, ``subset_pairs``; from one that boosts in
    first order ``boost_rounds_label``, ``boost_rounds_residual``) and the
    ``tree`` ledger."""
    leaves = jax.tree.leaves(value)
    if all(isinstance(a, np.ndarray) for a in leaves):
        return value
    with _tspans.span("tree/await_outputs") as sp:
        t0 = _tspans.clock()
        out = jax.tree.map(np.asarray, value)
        nbytes = sum(int(a.nbytes) for a in jax.tree.leaves(out))
        sp.attrs["bytes"] = nbytes
        _runlog.record_download(nbytes, _tspans.clock() - t0)
        if hist_slots is not None and _tspans.enabled():
            sums = {
                field: int(np.asarray(a, dtype=np.int64).sum())
                for field, a in hist_slots._asdict().items()
            }
            sp.attrs.update(
                slots_live=sums["live"], slots_built=sums["built"],
                chunks_run=sums["chunks_run"],
                chunks_skipped=sums["chunks_skipped"],
                nodes_built=sums["nodes_built"],
                nodes_derived=sums["nodes_derived"],
            )
            if sums["subset_pairs"]:
                sp.attrs.update(
                    subset_admitted=sums["subset_admitted"],
                    subset_pairs=sums["subset_pairs"],
                )
            if sums["rounds_label"] or sums["rounds_residual"]:
                sp.attrs.update(
                    boost_rounds_label=sums["rounds_label"],
                    boost_rounds_residual=sums["rounds_residual"],
                )
            _HIST_SLOT_STATS.record(sums)
    return out


def quantile_thresholds(x: np.ndarray, max_bins: int = 32) -> np.ndarray:
    """Per-feature quantile bin edges [F, max_bins-1] (XGBoost 'hist' sketch
    equivalent; computed host-side once per dataset). NaN-free input takes
    the plain-quantile path (np.nanquantile walks a per-column masked slow
    path — ~45× slower on a 891×957 matrix)."""
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    xd = np.asarray(x, dtype=np.float64)
    qf = np.quantile if not np.isnan(xd).any() else np.nanquantile
    thr = qf(xd, qs, axis=0).T
    # make strictly non-decreasing; duplicate edges simply yield empty bins
    return _positive_zeros(np.ascontiguousarray(thr, dtype=np.float32))


def _positive_zeros(thr: np.ndarray) -> np.ndarray:
    """A zero edge is +0.0 (``x > -0.0`` is ``x > 0.0``): the sign
    ``np.quantile`` leaves on one depends on where its partition put the
    column's -0.0s, which no other route to the same edges can repeat."""
    return thr + np.float32(0.0)


# Columns per sort of ``bin_column_stats``. At 1,002,701 x 357 on a v5e the
# program takes 0.67 s at 32 and at 64 columns a sort and 0.92 s at 128
# (tools/bench_bin_prepare.py, PR 28); its temporaries are 0.26 GB at 32,
# 0.77 GB at 64 and 1.03 GB at 128 whatever F is (the v5e compiler's
# memory analysis), so the narrowest of the fast ones.
_STATS_COL_CHUNK = 32


def quantile_rows(n: int, max_bins: int):
    """(lo, hi, gamma) of ``np.quantile``'s 'linear' method at the
    ``max_bins - 1`` inner quantiles of ``n`` values: the two sorted rows
    each quantile lies between and its weight, in numpy's own float64
    arithmetic (``_quantile``: a virtual index at or past the last row
    reads the last row twice and keeps its gamma)."""
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    virtual = (n - 1) * qs
    lo = np.floor(virtual)
    hi = lo + 1
    last = virtual >= n - 1
    lo[last] = hi[last] = -1
    lo, hi = lo.astype(np.intp), hi.astype(np.intp)
    return lo % n, hi % n, virtual - lo


def _f32_total_order(bits: jax.Array) -> jax.Array:
    """int32 bit patterns of float32 values -> int32 keys in the values'
    total order (-0.0 below +0.0, subnormals apart), and back: the map is
    its own inverse. Integer compares are exact on every backend; float
    ones flush subnormals to zero on the TPU and in XLA:CPU's sort."""
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


@partial(jax.jit, static_argnames=("max_bins",))
def bin_column_stats(x: jax.Array, max_bins: int):
    """What binning needs to know of each column of the float32 plane
    ``x`` [N, F], from the device that already holds it:

    - ``[F, max_bins - 1, 2]`` float32: the order statistics at
      ``quantile_rows``' (lo, hi), exact values of the column, from a sort
      along the rows in chunks of ``_STATS_COL_CHUNK`` columns;
    - ``[F]`` bool: ``gbdt._feature_bin_groups``' predicate (the column
      holds only 0, 1 or non-finite values);
    - ``[]`` bool: whether any value is NaN (the caller then takes
      ``np.nanquantile`` on the host: its ``n`` differs by column).

    Every compare is on the values' bit patterns, and nothing here
    interpolates: ``thresholds_from_order_stats`` does, on the host in
    float64, so the thresholds equal ``quantile_thresholds``'."""
    n, f = x.shape
    lo, hi, _gamma = quantile_rows(n, max_bins)
    rows = np.stack([lo, hi], axis=1).reshape(-1).astype(np.int32)
    c = min(f, _STATS_COL_CHUNK)
    n_chunks = -(-f // c)
    # the last chunk is clamped into the plane (it overlaps its
    # predecessor where F is no multiple of the chunk): no padded copy
    starts = np.minimum(np.arange(n_chunks) * c, f - c).astype(np.int32)
    col = np.arange(f)
    chunk_of = np.minimum(col // c, n_chunks - 1)

    def chunk_stats(start):
        cols = jax.lax.dynamic_slice_in_dim(x, start, c, axis=1)
        keys = _f32_total_order(jax.lax.bitcast_convert_type(cols, jnp.int32))
        return jnp.sort(keys, axis=0)[rows]  # [2(B-1), c]

    with jax.named_scope("tree/thresholds"):
        picked = jax.lax.map(chunk_stats, jnp.asarray(starts))
        picked = picked[chunk_of, :, col - starts[chunk_of]]  # [F, 2(B-1)]
        stats = jax.lax.bitcast_convert_type(
            _f32_total_order(picked), jnp.float32
        )
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        magnitude = bits & jnp.int32(0x7FFFFFFF)
        inf = jnp.int32(0x7F800000)
        one = jnp.int32(0x3F800000)
        binary = jnp.all(
            (magnitude == 0) | (bits == one) | (magnitude >= inf), axis=0
        )
        any_nan = jnp.any(magnitude > inf)
    return stats.reshape(f, max_bins - 1, 2), binary, any_nan


def thresholds_from_order_stats(stats: np.ndarray, n_rows: int):
    """``quantile_thresholds``' [F, max_bins-1] float32 from
    ``bin_column_stats``' order statistics of an ``n_rows``-row plane:
    ``np.quantile``'s linear interpolation (``_lerp``), operation for
    operation, in float64."""
    a = np.asarray(stats[..., 0], dtype=np.float64)
    b = np.asarray(stats[..., 1], dtype=np.float64)
    t = quantile_rows(n_rows, stats.shape[1] + 1)[2][None, :]
    with np.errstate(invalid="ignore"):  # inf - inf is nan, as in numpy
        diff = b - a
        thr = a + diff * t
        np.subtract(b, diff * (1 - t), out=thr, where=t >= 0.5)
    return _positive_zeros(np.ascontiguousarray(thr, dtype=np.float32))


def bin_data(x: jax.Array, thresholds: jax.Array) -> jax.Array:
    """int32 bin codes [N, F]: number of thresholds strictly below x.

    Accumulated one threshold column at a time: the broadcast form
    materializes an [N, F, B-1] temporary — 15.5 GB at 1M×500×32, the OOM
    cliff for wide scale runs — while the scan keeps peak memory at one
    [N, F] int32."""
    def step(acc, thr_col):  # thr_col [F]
        return acc + (x > thr_col[None, :]).astype(jnp.int32), None

    with jax.named_scope("tree/bin"):
        acc0 = jnp.zeros(x.shape, dtype=jnp.int32)
        codes, _ = jax.lax.scan(step, acc0, jnp.swapaxes(thresholds, 0, 1))
    return codes


# --------------------------------------------------------------------------
# small-table primitives — TPU scatters serialize per index and per-element
# gathers from small tables lower to slow dynamic-gathers; the one-hot
# compare/select forms are plain VPU reductions that XLA fuses (measured at
# [1M] rows, 64-entry tables, in-program: gather 3.3 ms vs 2.4; per-row
# feature select 14 ms vs ~2; occupancy scatter 10.2 ms vs 2.3).
# --------------------------------------------------------------------------
_ONEHOT_MAX_WIDTH = 512
# beyond the always-on width, the fused compare/select form is still the
# winner as long as the TOTAL lane-op count (index count × table width)
# stays around a millisecond of VPU time — deep AutoML trees at sub-4k row
# counts sit far under this (24 lanes × 891 rows × 4096 node ids ≈ 87M),
# while the 1M-row scale paths fall back to scatter/gather exactly as
# before (measured: the flagship depth-12 RF program 2.05 → 1.72 s and the
# 200-round XGB sweep 1.64 → 1.12 s from this alone).
_ONEHOT_OPS_BUDGET = 1 << 28


def _use_onehot(n_idx: int, width: int) -> bool:
    return width <= _ONEHOT_MAX_WIDTH or n_idx * width <= _ONEHOT_OPS_BUDGET


def _small_table_lookup(table: jax.Array, idx: jax.Array) -> jax.Array:
    """out[k, r] = table[k, idx[k, r]] — one-hot select for small tables,
    take_along_axis beyond the fused-form ops budget. idx must be in
    [0, M)."""
    m = table.shape[-1]
    if not _use_onehot(idx.size, m):
        return jnp.take_along_axis(table, idx, axis=-1)
    iot = jnp.arange(m, dtype=jnp.int32)
    zero = jnp.zeros((), dtype=table.dtype)
    return jnp.where(
        idx[..., None] == iot, table[..., None, :], zero
    ).sum(-1)


def _row_feature_select(binned: jax.Array, feat: jax.Array) -> jax.Array:
    """code[..., r] = binned[r, max(feat[..., r], 0)] — the per-row
    feature gather of tree routing, as a one-hot select over the feature
    axis (one fused pass over binned)."""
    f = binned.shape[1]
    if not _use_onehot(feat.size, f):
        def one(rf):
            return jnp.take_along_axis(
                binned, jnp.maximum(rf, 0)[:, None], axis=1
            )[:, 0]

        return one(feat) if feat.ndim == 1 else jax.vmap(one)(feat)
    iot = jnp.arange(f, dtype=jnp.int32)
    sel = jnp.maximum(feat, 0)[..., None] == iot
    return jnp.where(sel, binned, 0).sum(-1)


def _occupancy(idx: jax.Array, size: int) -> jax.Array:
    """count of idx == m per m in [0, size) for idx [K, N] (out-of-range
    ids drop out) — compare-reduce while fused-form ops fit the budget,
    scatter-add beyond."""
    if not _use_onehot(idx.size, size):
        return jax.vmap(
            lambda nd: jnp.zeros(size + 1, jnp.int32).at[
                jnp.minimum(nd, size)
            ].add(1)
        )(idx)[:, :size]
    iot = jnp.arange(size, dtype=jnp.int32)
    return (idx[..., None] == iot).astype(jnp.int32).sum(axis=-2)


def _segment_sum_small(values: jax.Array, idx: jax.Array, size: int) -> jax.Array:
    """out[k, m] = Σ_r values[k, r]·1[idx[k, r] == m] — one fused
    compare/select reduction for small segment counts."""
    if not _use_onehot(idx.size, size):
        return jax.vmap(
            lambda nd, v: jnp.zeros(size + 1, values.dtype).at[
                jnp.minimum(nd, size)
            ].add(v)
        )(idx, values)[:, :size]
    iot = jnp.arange(size, dtype=jnp.int32)
    return jnp.where(
        idx[..., None] == iot, values[..., None], 0.0
    ).sum(axis=-2)


def grow_tree(
    binned: jax.Array,     # [N, F] int32 codes in [0, num_bins)
    grad: jax.Array,       # [N] float32
    hess: jax.Array,       # [N] float32
    row_mask: jax.Array,   # [N] float32
    feat_mask: jax.Array,  # [F] float32 (0 disables a feature — RF colsample)
    max_depth: int,
    num_bins: int,
    reg_lambda: float | jax.Array = 1.0,
    gamma: float | jax.Array = 0.0,
    min_child_weight: float | jax.Array = 1.0,
    min_info_gain: float | jax.Array = 0.0,
    hist_impl: str | None = None,
    feature_groups=None,
    info_gain_norm: float = 0.0,
) -> Tree:
    """Single-fit tree growth — the K=1 case of grow_tree_batched."""
    tree = grow_tree_batched(
        binned, grad[None, :], hess[None, :], row_mask[None, :],
        feat_mask[None, :],
        max_depth=max_depth, num_bins=num_bins,
        reg_lambda=reg_lambda, gamma=gamma,
        min_child_weight=min_child_weight, min_info_gain=min_info_gain,
        hist_impl=hist_impl, feature_groups=feature_groups,
        info_gain_norm=info_gain_norm,
    )
    return jax.tree.map(lambda a: a[0], tree)


@partial(
    jax.jit,
    static_argnames=(
        "max_depth", "num_bins", "hist_impl", "lowp", "info_gain_norm",
    ),
)
def grow_tree_batched(
    binned: jax.Array,     # [N, F] int32 codes, SHARED across fits
    grad: jax.Array,       # [K, N] float32
    hess: jax.Array,       # [K, N] float32
    row_mask: jax.Array,   # [K, N] float32
    feat_mask: jax.Array,  # [K, F] float32
    max_depth: int,
    num_bins: int,
    reg_lambda: jax.Array | float = 1.0,       # scalar or [K]
    gamma: jax.Array | float = 0.0,
    min_child_weight: jax.Array | float = 1.0,
    min_info_gain: jax.Array | float = 0.0,
    hist_impl: str | None = None,
    lowp: bool = False,
    feature_groups=None,
    info_gain_norm: float = 0.0,
) -> Tree:
    """Grow K trees at once — one per batched fit (hyperparameter grid point
    × CV fold). The fit axis is a kernel GRID dimension of the histogram
    build (hist_pallas.build_histogram_pallas_batched), NOT a vmap over the
    custom call (which crashes this TPU runtime), so the entire candidate
    sweep's tree growth runs as one compiled program. Returned Tree arrays
    carry a leading K axis."""
    return _grow_tree_impl(
        binned, grad, hess, row_mask, feat_mask,
        max_depth=max_depth, num_bins=num_bins,
        reg_lambda=reg_lambda, gamma=gamma,
        min_child_weight=min_child_weight, min_info_gain=min_info_gain,
        hist_impl=hist_impl, lowp=lowp, feature_groups=feature_groups,
        info_gain_norm=info_gain_norm,
    )[0]


def _grow_tree_impl(
    binned: jax.Array,     # [N_local, F] int32 codes, SHARED across fits
    grad: jax.Array,       # [K, N_local] float32, or [K, V, N_local]: V channels
    hess: jax.Array,       # [K, N_local] float32
    row_mask: jax.Array,   # [K, N_local] float32
    feat_mask: jax.Array,  # [K, F] float32
    max_depth: int,
    num_bins: int,
    reg_lambda: jax.Array | float = 1.0,
    gamma: jax.Array | float = 0.0,
    min_child_weight: jax.Array | float = 1.0,
    min_info_gain: jax.Array | float = 0.0,
    hist_impl: str | None = None,
    lowp: bool = False,
    axis_name: str | None = None,
    axis_size: int = 1,
    feature_groups: tuple[jax.Array, jax.Array] | None = None,
    max_depth_v: jax.Array | None = None,
    info_gain_norm: float = 0.0,
    node_subset: int | None = None,
    node_key: jax.Array | None = None,
) -> tuple[Tree, jax.Array, HistSlots]:
    """Tree-growth body shared by the single-device jit wrapper and the
    shard_map'd path: (tree, each row's final leaf slot, the histogram
    builds' slot counts per level). ``max_depth_v`` ([K] int32, optional)
    caps each LANE's depth at runtime: levels >= a lane's cap emit no splits, so one
    compiled program at the grid's max depth serves every depth point of a
    hyperparameter sweep (3 RF depth groups -> one program: acquisition,
    not execution, is the flagship's wall-clock). With ``axis_name`` set, the function runs per-shard
    inside shard_map: rows are the LOCAL shard, each level's histogram is
    psum'd over the mesh axis before the split search, node compaction uses
    a psum'd global occupancy mask, and leaf sums are psum'd — the direct
    ICI replacement for XGBoost's Rabit allreduce of per-worker histograms
    (reference OpXGBoostClassifier.scala:101, SURVEY §2.6 row 5). Split
    decisions consume the same reduced histogram either way, so sharded and
    single-device growth produce the same tree.

    ``feature_groups`` = (narrow_idx, wide_idx): original-feature index
    arrays partitioning the columns into ≤2-bin features (one-hot /
    indicator columns — the vast majority of a transmogrified matrix) and
    genuinely multi-bin ones. Split-search cost scales with features×bins,
    so searching 900 binary columns at num_bins=32 wastes ~16× the bin-axis
    work; the narrow group runs the same kernels at b=2 instead. Per-feature
    gains are bin-cumsum along each feature's own row, so grouped growth
    finds the SAME splits as ungrouped (tie-break by original feature id
    preserved across the group merge).

    ``grad`` with V value channels (``[K, V, N]``) grows ONE tree a lane on
    a vector statistic: every histogram has V + 1 channels, the gain is the
    sum of the channels' gains and a leaf holds V values (``[2^depth, V]``;
    V + 1 under ``GINI``: the class distribution). ``[K, N]`` is the V = 1
    case and keeps its shapes.

    ``info_gain_norm`` (static) chooses the impurity and the stop rule. 0:
    a node splits when its best gain ``bg`` (the formula above, a SUM over
    the node's rows) is over ``min_info_gain`` — XGBoost's absolute
    ``gamma`` semantics. ``VARIANCE`` (2) or ``GINI`` (4): Spark's rule —
    the impurity decrease per row ``2·bg/W`` (W the node's hessian sum, its
    weighted row count in a forest) must reach ``min_info_gain`` and be
    positive. Under ``GINI`` the value channels are the negated indicators
    of classes 1 … V of a (V + 1)-class label: the Gini decrease is the sum
    over ALL classes of the variance decrease of the class's indicator, and
    class 0's indicator is 1 less the others', whose decrease is that of
    their sum: ``bg`` adds that one term (at V = 1 it doubles ``bg``
    exactly, the factor 4 = 2·2 this rule had for a 0/1 target). The
    arg-max within a node is the same either way.

    ``node_subset`` (static; forests) is Spark's ``featureSubsetStrategy``
    count: every NODE searches ``node_subset`` distinct columns of the F,
    ``jax.random.choice(jax.random.fold_in(node_key, j), F, (node_subset,),
    replace=False)`` for the node at heap index ``j`` (root 1, children 2j
    and 2j+1): the draw depends on the tree's key and the node alone, not
    on the lane, the chunk, the compact slot, the width rung or the mesh.
    ``node_subset == F`` draws nothing; None also counts nothing
    (``HistSlots.subset_*`` stay 0)."""
    from .hist_pallas import BUILDERS, default_impl

    k_fits, n = hess.shape
    f = binned.shape[1]
    b = num_bins
    max_nodes = 1 << max_depth
    # [K, N], or [K, V, N]: the builders take either (hist_pallas)
    g = grad * (row_mask if grad.ndim == 2 else row_mask[:, None, :])
    h = hess * row_mask
    g_rows = [g] if g.ndim == 2 else [g[:, v] for v in range(g.shape[1])]
    n_values = len(g_rows)
    gini = info_gain_norm == GINI
    impl = hist_impl or default_impl()

    if feature_groups is not None:
        narrow_idx, wide_idx = feature_groups
        # (binned columns, per-fit feature mask, bin count, orig ids).
        # Narrow features hold exactly two values {0, t} in code space
        # (duplicate quantile edges put the '1' value at code t = #zeros);
        # recoding (code > 0) compresses them to b=2 while the stored split
        # bin 0 routes identically in ORIGINAL code space (code > 0 ⇔
        # value is the upper one) — predict needs no remapping. Index
        # arrays may be traced (per-tree colsample subsets); shapes are
        # static, values aren't. Empty groups simply drop out.
        groups = []
        with jax.named_scope("tree/group_columns"):
            if narrow_idx.shape[0]:
                groups.append(
                    (
                        (binned[:, narrow_idx] > 0).astype(jnp.int32),
                        feat_mask[:, narrow_idx], 2, narrow_idx,
                    )
                )
            if wide_idx.shape[0]:
                groups.append(
                    (binned[:, wide_idx], feat_mask[:, wide_idx], b,
                     wide_idx)
                )
        if not groups:
            groups = [(binned, feat_mask, b, None)]
    else:
        groups = [(binned, feat_mask, b, None)]

    def vec(v):
        arr = jnp.asarray(v, dtype=jnp.float32).reshape(-1)
        return arr  # shape (1,) broadcasts over K; shape (K,) is per-fit

    lam = vec(reg_lambda)[:, None, None, None]
    gam = vec(gamma)[:, None, None, None]
    mcw = vec(min_child_weight)[:, None, None, None]
    mig = vec(min_info_gain)[:, None]

    # ---- node compaction: at any level at most min(2^depth, N) node slots
    # are LIVE (every live slot holds ≥1 row), so histograms are built over
    # a compact slot space of ``cap`` ids instead of the full 2^d range —
    # depth-12 growth on 1k rows costs the same as depth-10 (the dominant
    # win for the deep ends of the reference's maxDepth {3,6,12} grids).
    # When sharded, the live bound is the GLOBAL row count.
    # which builder each group takes and how many node slots one build may
    # hold: decided in ONE place, hist_pallas.histogram_plan. A builder's
    # loop-invariant operand (the GEMM's one-hot codes) is made here, once
    # per group, outside the level scan and the tree scan above it.
    cap, plan, ladder = _slot_layout(
        impl, n, k_fits, [(gb_.shape[1], bb) for gb_, _, bb, _ in groups],
        max_depth, axis_size, sharded=axis_name is not None,
        stat_channels=n_values + 1, lowp=lowp,
    )
    # nodes a lane whose histograms a level keeps for the next (0: none)
    keep = plan.parent_slots
    assert b <= 1 << _BUILT_BIT, "a bin code shares its table entry's low bits"
    with jax.named_scope("tree/group_columns"):
        groups = [
            (gb_, gm, bb, gi, BUILDERS[name].build,
             BUILDERS[name].prepare(gb_, bb, lowp))
            for (gb_, gm, bb, gi), name in zip(groups, plan.builders)
        ]
    draw_subsets = node_subset is not None and node_subset < f

    # A level's histograms as rows, one a (lane, node): [K·M, S·B·F] from
    # the builders' [K, M, F, B, S], and back. The orders are the TPU
    # kernel's, whose output lies [S][K][B][F][M] in memory under that
    # logical shape: each way is ONE transpose of the node axis against the
    # rest, between shapes whose last two axes are both wide (a trailing
    # axis of S = 2 or B = 2 pads 64-fold to the chip's (8, 128) tiles).
    def node_rows(hist):
        return jnp.transpose(hist, (0, 1, 4, 3, 2)).reshape(
            hist.shape[0] * hist.shape[1], -1
        )

    def node_hists(rows, nodes, fg, bg):
        by_cell = jnp.transpose(rows.reshape(k_fits, nodes, -1), (0, 2, 1))
        return jnp.transpose(
            by_cell.reshape(k_fits, n_values + 1, bg, fg, nodes),
            (0, 4, 3, 2, 1),
        )

    def group_stats(gbinned, gmask, gb, gidx, build, operand, loc,
                    chunk_nodes, sel, pairs):
        """(gain, orig feat, bin, right child lighter) of the best split per
        compact slot for ONE feature group; ``sel`` [K, M, node_subset] are
        the slots' admissible columns (None: all). Also the (slot, feature)
        pairs of this group that ``sel`` admits, [K, M] (None: all), and the
        slots' histograms [K·M, Fg·Bg·S] for the next level to subtract
        from. ``pairs`` (None: every slot is built): ``loc`` numbers sibling
        PAIRS, M/2 of them, and holds the rows of one child of each; the
        other child is its parent less the one built."""
        nmask = None
        if sel is not None:
            with jax.named_scope("tree/node_subset"):
                fid = (
                    jnp.arange(gbinned.shape[1], dtype=jnp.int32)
                    if gidx is None else gidx.astype(jnp.int32)
                )
                nmask = (sel[..., None] == fid).any(axis=2)  # [K, M, Fg]
        with jax.named_scope("tree/histogram"):
            # [K, M, Fg, Bg, V + 1] (value channels, hess) sums of the group
            if pairs is None:
                hist = build(operand, loc, g, h, chunk_nodes, gb, lowp=lowp)
                if axis_name is not None:
                    # the Rabit-allreduce moment: per-shard partial
                    # histograms reduce over ICI; everything after sees the
                    # global histogram
                    hist = jax.lax.psum(hist, axis_name)
            else:
                built_right, parent, slot_live = pairs
                one = build(
                    operand, loc, g, h, chunk_nodes // 2, gb, lowp=lowp
                )
                rows = sibling_rows(
                    node_rows(one), parent, built_right, slot_live
                )
                hist = node_hists(rows, chunk_nodes, gbinned.shape[1], gb)
        with jax.named_scope("tree/split_search"):
            best = best_split(hist, gmask, nmask, gb, gidx, chunk_nodes)
        if not keep:
            rows = None
        elif pairs is None:
            rows = node_rows(hist)
        return best, None if nmask is None else nmask.sum(axis=2), rows

    def best_split(hist, gmask, nmask, gb, gidx, chunk_nodes):
        hh = hist[..., n_values]  # [K, M, Fg, Bg]
        hl = jnp.cumsum(hh, axis=3)[..., :-1]
        ht = hh.sum(axis=3, keepdims=True)
        hr = ht - hl

        def decrease(hg):
            """GL²/(HL+λ) + GR²/(HR+λ) − G²/(H+λ) of one value channel."""
            gl = jnp.cumsum(hg, axis=3)[..., :-1]
            gt = hg.sum(axis=3, keepdims=True)
            gr = gt - gl
            parent = (gt**2) / (ht + lam)
            return gl**2 / (hl + lam) + gr**2 / (hr + lam) - parent

        total = decrease(hist[..., 0])
        for v in range(1, n_values):
            total = total + decrease(hist[..., v])
        if gini:
            # class 0: the decrease of the other classes' summed indicator
            total = total + decrease(hist[..., :n_values].sum(axis=-1))
        gain = 0.5 * total - gam
        valid = (
            (hl >= mcw)
            & (hr >= mcw)
            & (gmask[:, None, :, None] > 0)
        )
        if nmask is not None:
            valid = valid & nmask[..., None]
        gain = jnp.where(valid, gain, -jnp.inf)

        flat_gain = gain.reshape(gain.shape[0], chunk_nodes, -1)
        best = jnp.argmax(flat_gain, axis=2)
        best_gain = jnp.take_along_axis(flat_gain, best[..., None], axis=2)[..., 0]
        best_feat = (best // (gb - 1)).astype(jnp.int32)
        best_bin = (best % (gb - 1)).astype(jnp.int32)
        if gidx is not None:
            best_feat = gidx[best_feat].astype(jnp.int32)
        # every row of a node lies in one bin of each feature: any
        # feature's total is the node's weight
        # the hessian sums of the two children at the split taken: the
        # LIGHTER child is the one the next level builds (ties: the left)
        def at_best(a):
            return jnp.take_along_axis(
                a.reshape(a.shape[0], chunk_nodes, -1), best[..., None],
                axis=2,
            )[..., 0]

        right_lighter = at_best(hr) < at_best(hl)
        return best_gain, best_feat, best_bin, ht[:, :, 0, 0], right_lighter

    def node_subsets(at, c0, chunk_nodes):
        """[K, M, node_subset] int32: the admissible columns of the nodes
        in compact slots [c0, c0 + M), drawn from each node's heap index
        (see the docstring); ``at`` is the level's (live, rank, level)."""
        live, rank, level = at
        with jax.named_scope("tree/node_subset"):
            ids = jnp.arange(max_nodes, dtype=jnp.int32)
            slots = c0 + jnp.arange(chunk_nodes, dtype=jnp.int32)
            # the node whose dense rank is the slot (0 where none is live:
            # its draw is never read)
            node_of = jnp.where(
                live[:, :, None] & (rank[:, :, None] == slots),
                ids[None, :, None], 0,
            ).sum(axis=1)
            heap = jnp.left_shift(jnp.int32(1), level) + node_of  # [K, M]

            def draw(j):
                return jax.random.choice(
                    jax.random.fold_in(node_key, j), f, (node_subset,),
                    replace=False,
                ).astype(jnp.int32)

            return jax.vmap(jax.vmap(draw))(heap)

    def build_slots(local, c0, chunk_nodes, sib):
        """[K, N] int32: the slot of the build over the compact node slots
        [c0, c0 + chunk_nodes) each row goes to, -1 for a row that takes no
        part. Every node is built (``sib`` None): the row's node, from 0.
        A subtracting level: the row's sibling PAIR (nodes 2j and 2j + 1
        are pair j), from 0 at c0 / 2, for the rows of the child the level
        above chose to build."""
        with jax.named_scope("tree/partition"):
            if sib is None:
                part = (local >= c0) & (local < c0 + chunk_nodes)
                return jnp.where(part, local - c0, -1)
            pair, p0 = local >> 1, c0 // 2
            part = sib.takes_part & (pair >= p0) & (
                pair < p0 + chunk_nodes // 2
            )
            return jnp.where(part, pair - p0, -1)

    def chunk_stats(loc, c0, chunk_nodes, at, sib, hists):
        """Best (feat, bin) per compact slot in [c0, c0 + chunk_nodes),
        merged across feature groups (tie-break: lowest original feature
        id — matches the single-group argmax order), whether the right
        child of that split is the lighter, the (live node, feature)
        pairs admitted and possible there, and ``hists`` (per group
        [K·keep, Fg·Bg·S]: the level's histograms so far) with these
        slots' written in. ``loc``: ``build_slots``' of the same range."""
        halves = [None] * len(groups)
        # dense numbering: a lane's live slots are those below its live count
        slot_live = (
            c0 + jnp.arange(chunk_nodes, dtype=jnp.int32)
        ) < at[0].sum(axis=1, dtype=jnp.int32)[:, None]
        if sib is not None:
            def of_chunk(a):
                return jax.lax.dynamic_slice_in_dim(
                    a, c0 // 2, chunk_nodes // 2, axis=1
                )

            # rows of the kept histograms ([K·keep, Fg·Bg·S], lane-major)
            parent_row = (
                jnp.arange(k_fits, dtype=jnp.int32)[:, None] * keep
                + of_chunk(sib.parent_slot)
            ).reshape(-1)
            with jax.named_scope("tree/histogram"):
                halves = [
                    (
                        of_chunk(sib.built_right),
                        jnp.take(kept, parent_row, axis=0, mode="clip"),
                        slot_live,
                    )
                    for kept in sib.hists
                ]
        sel = node_subsets(at, c0, chunk_nodes) if draw_subsets else None
        bg, bf, bb, bw, br = None, None, None, None, None
        admitted = None
        new_hists = []
        for grp, half, kept in zip(
            groups, halves, hists or [None] * len(groups)
        ):
            gbinned, gmask, grp_b, gidx, build, operand = grp
            (gg, gf, gbin, gw, gr), adm, hist = group_stats(
                gbinned, gmask, grp_b, gidx, build, operand, loc,
                chunk_nodes, sel, half,
            )
            if kept is not None:
                # compact slots [c0, c0 + chunk) of this level: the next
                # one's parents. A level's nodes past ``keep`` are the last
                # level's, which nobody subtracts from (the update clamps
                # there, onto slots never read again)
                with jax.named_scope("tree/histogram"):
                    kept = jax.lax.dynamic_update_slice_in_dim(
                        kept.reshape(k_fits, keep, -1),
                        hist.reshape(k_fits, chunk_nodes, -1)[:, :keep],
                        c0, axis=1,
                    ).reshape(kept.shape)
                new_hists.append(kept)
            if adm is not None:
                admitted = adm if admitted is None else admitted + adm
            if bg is None:
                bg, bf, bb, bw, br = gg, gf, gbin, gw, gr
            else:
                with jax.named_scope("tree/split_search"):
                    take = (gg > bg) | ((gg == bg) & (gf < bf))
                    bg = jnp.where(take, gg, bg)
                    bf = jnp.where(take, gf, bf)
                    bb = jnp.where(take, gbin, bb)
                    br = jnp.where(take, gr, br)
        with jax.named_scope("tree/split_search"):
            if info_gain_norm:
                do_split = (bg > 0.0) & (2.0 * bg / bw >= mig)
            else:
                do_split = bg > jnp.maximum(mig, 0.0)
            counts = (jnp.int32(0), jnp.int32(0))
            if node_subset is not None:
                pairs = slot_live.sum(dtype=jnp.int32) * f
                counts = (
                    pairs if admitted is None else
                    jnp.where(slot_live, admitted, 0).sum(dtype=jnp.int32),
                    pairs,
                )
            return (
                jnp.where(do_split, bf, -1),
                jnp.where(do_split, bb, 0),
                do_split & br,
                counts,
                tuple(new_hists),
            )  # [K, chunk] x 3, two scalars, the kept histograms

    sentinel = jnp.int32(max_nodes)  # out-of-range → dropped by scatters

    def leaf_values(sums_g, sum_h):
        """-G/(H+λ) of each value channel from the leaves' [K, leaves]
        sums: [K, leaves], or [K, leaves, V] at V > 1. Under ``GINI`` with
        V > 1 the leaf is the whole class distribution, [K, leaves, V + 1]:
        class 0's count is W less the others' (exact: they are integers),
        divided like theirs, so that equal counts give equal shares and an
        arg max can break ties by the lowest class. (A two-class leaf stays
        its class-1 share: 1 - p is exact there.)"""
        if axis_name is not None:
            sums_g = [jax.lax.psum(sg, axis_name) for sg in sums_g]
            sum_h = jax.lax.psum(sum_h, axis_name)
        if gini and n_values > 1:
            sums_g = [-(sum_h + sum(sums_g))] + sums_g
        leaves = [-sg / (sum_h + vec(reg_lambda)[:, None]) for sg in sums_g]
        return leaves[0] if len(leaves) == 1 else jnp.stack(leaves, axis=-1)

    if max_depth == 0:
        # root-only tree (legal Spark maxDepth=0): no splits, leaf = all rows
        with jax.named_scope("tree/leaf"):
            leaf_value0 = leaf_values(
                [gv.sum(axis=1, keepdims=True) for gv in g_rows],
                h.sum(axis=1, keepdims=True),
            )
        return Tree(
            split_feat=jnp.full((k_fits, 0, 1), -1, dtype=jnp.int32),
            split_bin=jnp.zeros((k_fits, 0, 1), dtype=jnp.int32),
            leaf_value=leaf_value0,
        ), jnp.zeros((k_fits, n), dtype=jnp.int32), HistSlots(
            *(jnp.zeros((0,), dtype=jnp.int32) for _ in HistSlots._fields)
        )

    # ---- lax.scan over levels with ONE shared body: an unrolled level
    # loop multiplies the compiled body (and its compile time and
    # executable size) by max_depth. Every level therefore uses the SAME
    # static slot layout: `cap` compact slots in `num_chunks` fixed chunks,
    # with node compaction numbering live slots densely from 0 so the
    # per-chunk occupancy cond skips the provably-empty tail.
    #
    # A level with few live slots does not pay for a whole chunk: its
    # histograms are built and searched at the smallest rung of `ladder`
    # that holds them (live_level below). A build costs by its WIDTH, not
    # by the rows a slot holds (every build passes over all rows against a
    # [T, nvar·width] one-hot operand: 0.52 s at 256 slots, 0.26 at 128,
    # 0.17 at 64, 0.13 at 32 on a v5e at 1M x 302 x 32 bins,
    # hist_pallas.binloop_tiles). The sharded path keeps the full width
    # (_slot_layout).
    #
    # So a level past the root builds HALF its nodes. A level's nodes come
    # in sibling pairs whose parent's histogram the level above held, and
    # hist(parent) = hist(left) + hist(right) cell for cell: one child of
    # each pair is built, at slot = the pair's number, and the other is the
    # parent less it, in float32 (XGBoost's ``hist`` updater and LightGBM
    # do the same). A level of L nodes asks the ladder for L/2 slots, and
    # 256 pair slots serve 512 nodes. The level above chooses the child:
    # the one with the smaller hessian sum at the split taken (ties: the
    # left). That is a rule of numerics, not of speed: the derived child
    # inherits the absolute rounding error of two builds, which stays
    # within a small factor of its own only if it is the heavier one.
    # Where every sum is exact in float32 (integer weights, ±0.5 and 0.25)
    # the trees are bit for bit those of direct builds. The fit keeps the
    # level's histograms for this (`keep` nodes a lane, a feature group
    # each, [K·keep, Fg·Bg·S]: a row is a node, so a pair's parent is one
    # row gather) where hist_pallas.histogram_plan finds room
    # for them; else (keep 0, and on the sharded path) every node is built.
    # The root is pair 0's left child with no parent to subtract from.
    n_nodes = cap
    chunk_nodes = plan.chunk_cap
    num_chunks = (n_nodes + chunk_nodes - 1) // chunk_nodes
    # a subtracting level's chunks hold `chunk_nodes` PAIRS
    num_pair_chunks = (n_nodes + 2 * chunk_nodes - 1) // (2 * chunk_nodes)
    pair_slots = num_pair_chunks * chunk_nodes

    def compact_local(hist_node):
        """Dense live-slot numbering via occupancy + cumsum rank. Slot =
        number of live node ids BELOW this row's id — identical numbering
        to sorted-unique compaction, but built from one scatter-add and a
        cumsum instead of sort + searchsorted (each searchsorted lowers to
        a ~log2(N)-step binary-search while loop of gather fusions, and
        three of them per level measured ~75% of deep forest exec). When
        sharded, every shard agrees on the numbering because the occupancy
        psums first. Returns ((live, rank), slot): live/rank are
        [K, max_nodes] masks/prefix-ranks used to densify per-slot results
        back into global node-id space gather-side."""
        occ = _occupancy(hist_node, max_nodes)
        if axis_name is not None:
            occ = jax.lax.psum(occ, axis_name)
        live = occ > 0
        live_i = live.astype(jnp.int32)
        rank = jnp.cumsum(live_i, axis=1) - live_i  # exclusive prefix
        slot = _small_table_lookup(
            rank, jnp.minimum(hist_node, max_nodes - 1)
        )
        slot = jnp.where(hist_node >= max_nodes, sentinel, slot).astype(
            jnp.int32
        )
        return (live, rank), slot

    def level_body(carry, level_idx):
        # rows whose node failed to split are DEAD for histogram purposes:
        # a non-split node's child holds the same rows, hence the same
        # histogram and the same failed gain test (the hereditary no-split
        # argument). Excluding them shrinks the live-slot frontier so the
        # occupancy skip drops the dead bulk of deep levels; `node` keeps
        # the full routing chain (dead rows continue left) so leaf
        # assignment is unchanged.
        node, active, alive, sib = carry
        with jax.named_scope("tree/partition"):
            hist_node = jnp.where(active, node, sentinel)
            (live, rank), local = compact_local(hist_node)
            # dead rows out of every histogram / occupancy check,
            # regardless of which slot the sentinel landed on after
            # compaction
            local = jnp.where(active, local, sentinel)

        # live compact slots at this level, the widest lane's: slots are
        # numbered densely from 0, so every live one is below this count
        n_live_k = live.sum(axis=1, dtype=jnp.int32)
        n_live = n_live_k.max()

        at = (live, rank, level_idx)
        zero2 = (jnp.int32(0), jnp.int32(0))
        hists0 = sib.hists if keep else ()

        def chunk_loop(sib_):
            """The level in chunks of ``chunk_nodes`` slots a build: nodes,
            or (``sib_``) sibling pairs, two nodes each."""
            width = chunk_nodes if sib_ is None else 2 * chunk_nodes
            chunks = num_chunks if sib_ is None else num_pair_chunks

            def chunk_body(ci, fb):
                feats_a, bins_a, right_a, built, (adm, prs), hists = fb
                c0 = ci * width
                loc = build_slots(local, c0, width, sib_)
                if axis_name is None:
                    occupied = (loc >= 0).any()
                    cf, cb, cr, (ca, cp), hists = jax.lax.cond(
                        occupied,
                        lambda: chunk_stats(loc, c0, width, at, sib_, hists),
                        lambda: (
                            jnp.full((k_fits, width), -1, dtype=jnp.int32),
                            jnp.zeros((k_fits, width), dtype=jnp.int32),
                            jnp.zeros((k_fits, width), dtype=bool),
                            zero2,
                            hists,
                        ),
                    )
                    built = built + jnp.where(occupied, chunk_nodes, 0)
                else:
                    # the sharded path always computes — its psums can't
                    # sit under a data-dependent cond
                    cf, cb, cr, (ca, cp), hists = chunk_stats(
                        loc, c0, width, at, sib_, hists
                    )
                    built = built + chunk_nodes
                return (
                    jax.lax.dynamic_update_slice(feats_a, cf, (0, c0)),
                    jax.lax.dynamic_update_slice(bins_a, cb, (0, c0)),
                    jax.lax.dynamic_update_slice(right_a, cr, (0, c0)),
                    built,
                    (adm + ca, prs + cp),
                    hists,
                )

            def run():
                slots = (k_fits, chunks * width)
                feats_a, bins_a, right_a, built, counts, hists = (
                    jax.lax.fori_loop(
                        0, chunks, chunk_body,
                        (
                            jnp.full(slots, -1, dtype=jnp.int32),
                            jnp.zeros(slots, dtype=jnp.int32),
                            jnp.zeros(slots, dtype=bool),
                            jnp.int32(0), zero2, hists0,
                        ),
                    )
                )
                return (
                    feats_a[:, :n_nodes], bins_a[:, :n_nodes],
                    right_a[:, :n_nodes], built, counts, hists,
                )

            return run

        def one_build(width, sib_):
            """The whole level in ONE build at ``width`` slots (a rung that
            holds every live node, or (``sib_``) every sibling pair),
            brought to the [K, n_nodes] the scan carries."""
            nodes = width if sib_ is None else 2 * width

            def fit(a, fill):
                return jnp.pad(
                    a[:, :n_nodes],
                    ((0, 0), (0, max(n_nodes - nodes, 0))),
                    constant_values=fill,
                )

            def run():
                loc = build_slots(local, 0, nodes, sib_)
                cf, cb, cr, counts, hists = chunk_stats(
                    loc, 0, nodes, at, sib_, hists0
                )
                return (
                    fit(cf, -1), fit(cb, 0), fit(cr, False),
                    jnp.int32(width), counts, hists,
                )

            return run

        def live_level(sib_, narrow, count):
            """The level at the smallest of the ``narrow`` rungs that holds
            ``count`` slots, else in chunks."""
            if not narrow:
                return chunk_loop(sib_)()
            rung = sum((count > w).astype(jnp.int32) for w in narrow)
            return jax.lax.switch(
                rung,
                [one_build(w, sib_) for w in narrow] + [chunk_loop(sib_)],
            )

        if keep:
            # Pair j's children sit in the compact slots 2j and 2j + 1 iff
            # every child of a split is live, i.e. holds a row. It does
            # wherever ``min_child_weight`` > 0 (both children of a valid
            # split hold hessian). Where it does not (a weightless child),
            # the lane has fewer live nodes than twice its splits, and the
            # level builds every node, in chunks: a path for soundness,
            # not for speed.
            subtract = (
                (n_live_k == 2 * sib.n_pairs) | (level_idx == 0)
            ).all()
            n_built_k = jnp.where(subtract, sib.n_pairs, n_live_k)

            def level():
                return jax.lax.cond(
                    subtract,
                    lambda: live_level(sib, ladder[:-1], sib.n_pairs.max()),
                    lambda: live_level(None, (), n_live),
                )
        else:
            subtract = jnp.asarray(False)
            n_built_k = n_live_k

            def level():
                return live_level(None, ladder[:-1], n_live)

        # ---- early level exit: no-split is hereditary, so once a level
        # produces zero splits every deeper level is all-leaves — skip the
        # histogram work under a cond. The sharded path always computes
        # (replicated-predicate collectives under shard_map are not worth
        # the coupling).
        if axis_name is not None:
            feats_c, bins_c, right_c, built, counts, hists = level()
        else:
            feats_c, bins_c, right_c, built, counts, hists = jax.lax.cond(
                alive,
                level,
                lambda: (
                    jnp.full((k_fits, n_nodes), -1, dtype=jnp.int32),
                    jnp.zeros((k_fits, n_nodes), dtype=jnp.int32),
                    jnp.zeros((k_fits, n_nodes), dtype=bool),
                    jnp.int32(0),
                    zero2,
                    hists0,
                ),
            )
        # builds made and chunks the occupancy branch left out: a rung is
        # one build under a chunk's width, the chunk loop whole chunks
        chunked = built >= chunk_nodes
        runs = jnp.where(
            chunked, built // chunk_nodes, (built > 0).astype(jnp.int32)
        )
        # (a chunk of pairs covers two chunks of nodes)
        skipped = jnp.where(
            chunked,
            jnp.where(subtract, num_pair_chunks, num_chunks) - runs, 0,
        )
        if max_depth_v is not None:
            # per-lane depth cap: a lane past its depth emits no splits
            # (identical trees to a program compiled at that lane's depth —
            # dead levels route left and add nothing)
            lane_live = (level_idx < max_depth_v)[:, None]
            feats_c = jnp.where(lane_live, feats_c, -1)
            bins_c = jnp.where(lane_live, bins_c, 0)
        alive = (feats_c >= 0).any()

        # write per-slot decisions into the GLOBAL node-slot tree arrays —
        # gather-side via the compaction rank (live id → its dense slot):
        # scatters serialize per index on TPU and searchsorted lowers to
        # binary-search while loops; both measured to dominate deep levels.
        # (A one-shot post-scan densify over all levels measured ~35%
        # SLOWER than these per-level gathers — the [depth, K, max_nodes]
        # batched gather schedules worse than the level-sized ones.)
        with jax.named_scope("tree/partition"):
            rank_c = jnp.minimum(rank, n_nodes - 1)
            # one-hot select, NOT take_along_axis: the [K, max_nodes]
            # gather from [K, cap] lowered to a serializing custom-fusion
            # gather measured at ~1 ms per level — 1.2 s of the 1.7 s
            # depth-12 RF program (Titanic's 891 rows, round 5)
            feats_d = jnp.where(
                live, _small_table_lookup(feats_c, rank_c), -1
            )
            bins_d = jnp.where(live, _small_table_lookup(bins_c, rank_c), 0)

            # ---- route rows to children (gather via compact slots —
            # cheaper)
            slot = jnp.clip(local, 0, n_nodes - 1)
            row_feat = _small_table_lookup(feats_c, slot)  # [K, N]
            if keep:
                # which child the next level builds rides in the table the
                # threshold is looked up from (no gather of its own)
                row_thr = _small_table_lookup(
                    bins_c + (right_c.astype(jnp.int32) << _BUILT_BIT), slot
                )
                row_right = row_thr >> _BUILT_BIT
                row_thr = row_thr & ((1 << _BUILT_BIT) - 1)
            else:
                row_thr = _small_table_lookup(bins_c, slot)
            code = _row_feature_select(binned, row_feat)
            go_right = active & (row_feat >= 0) & (code > row_thr)
            node = node * 2 + go_right.astype(jnp.int32)
            active = active & (row_feat >= 0)
            if keep:
                # the next level's pairs: the nodes that split, numbered
                # densely in slot order; pair j's parent is the j-th of
                # them (only a level that is not the last has a next, and
                # its nodes sit in the first `keep` slots)
                splits = feats_c >= 0
                split = splits[:, :keep]
                split_i = split.astype(jnp.int32)
                order = jnp.cumsum(split_i, axis=1) - split_i
                is_parent = split[:, None, :] & (
                    order[:, None, :]
                    == jnp.arange(pair_slots, dtype=jnp.int32)[:, None]
                )  # [K, pair_slots, keep]
                sib = _Siblings(
                    takes_part=active & (go_right == (row_right > 0)),
                    n_pairs=splits.sum(axis=1, dtype=jnp.int32),
                    parent_slot=jnp.where(
                        is_parent, jnp.arange(keep, dtype=jnp.int32), 0
                    ).sum(axis=2),
                    built_right=(
                        is_parent & right_c[:, None, :keep]
                    ).any(axis=2),
                    hists=hists,
                )
        return (node, active, alive, sib), (
            feats_d, bins_d,
            HistSlots(
                n_built_k.max(), built, runs, skipped, *counts, *zero2,
                n_built_k.sum(), (n_live_k - n_built_k).sum(),
            ),
        )

    sib0 = None
    if keep:
        # the root: pair 0's left child, every row taking part
        sib0 = _Siblings(
            takes_part=jnp.ones((k_fits, n), dtype=bool),
            n_pairs=jnp.ones((k_fits,), dtype=jnp.int32),
            parent_slot=jnp.zeros((k_fits, pair_slots), dtype=jnp.int32),
            built_right=jnp.zeros((k_fits, pair_slots), dtype=bool),
            hists=tuple(
                jnp.zeros(
                    (k_fits * keep, gb_.shape[1] * bb * (n_values + 1)),
                    dtype=jnp.float32,
                )
                for gb_, _, bb, *_ in groups
            ),
        )
    (node, active, _, _), (feats_s, bins_s, slots_s) = jax.lax.scan(
        level_body,
        (
            jnp.zeros((k_fits, n), dtype=jnp.int32),
            jnp.ones((k_fits, n), dtype=bool),
            jnp.asarray(True),
            sib0,
        ),
        jnp.arange(max_depth, dtype=jnp.int32),
    )
    feats = jnp.swapaxes(feats_s, 0, 1)  # [K, depth, max_nodes]
    bins = jnp.swapaxes(bins_s, 0, 1)

    with jax.named_scope("tree/leaf"):
        leaf_value = leaf_values(
            [_segment_sum_small(gv, node, max_nodes) for gv in g_rows],
            _segment_sum_small(h, node, max_nodes),
        )
    tree = Tree(split_feat=feats, split_bin=bins, leaf_value=leaf_value)
    # `node` is each row's final leaf slot — boosting's margin update reuses
    # it (leaf_value lookup) instead of re-traversing the tree (measured
    # ~100 ms/round of serialized gathers at 1M rows)
    return tree, node, slots_s


def leaf_lookup(leaf_value: jax.Array, node: jax.Array) -> jax.Array:
    """Each row's leaf value: ``leaf_value`` [K, leaves] at ``node`` [K, N]
    -> [K, N]; with V value channels ([K, leaves, V]) -> [K, V, N] (rows
    last: a trailing axis of V would pad to 128 lanes on the TPU)."""
    if leaf_value.ndim == 2:
        return _small_table_lookup(leaf_value, node)
    return jnp.stack(
        [
            _small_table_lookup(leaf_value[..., v], node)
            for v in range(leaf_value.shape[-1])
        ],
        axis=1,
    )


def leaf_channels(trees: Tree) -> list[Tree]:
    """A tree stack whose leaves hold V value channels as V stacks of
    scalar leaves over the same splits (one for a scalar-leaf stack): what
    the traversals that sum one value a tree take (the host's C kernel,
    the serve kernel)."""
    lv = trees.leaf_value
    if lv.ndim == trees.split_feat.ndim - 1:
        return [trees]
    return [trees._replace(leaf_value=lv[..., v]) for v in range(lv.shape[-1])]


def predict_tree(binned: jax.Array, tree: Tree) -> jax.Array:
    """Leaf value per row ([N]; [V, N] from leaves of V channels) —
    lax.scan over the [depth, ...] level arrays
    (one shared gather body). An unrolled depth loop with level-sliced
    one-hot lookups grows the vmapped sweep programs ~depth×; whether its
    execution win pays for that is not re-measured on a local chip (see
    ROADMAP M6 / C5), so the scan stays."""
    n = binned.shape[0]

    def level(node, sfsb):
        sf, sb = sfsb
        feat = _small_table_lookup(sf[None, :], node[None, :])[0]
        thr = _small_table_lookup(sb[None, :], node[None, :])[0]
        code = _row_feature_select(binned, feat)
        go_right = (feat >= 0) & (code > thr)
        return node * 2 + go_right.astype(jnp.int32), None

    node, _ = jax.lax.scan(
        level, jnp.zeros(n, dtype=jnp.int32),
        (tree.split_feat, tree.split_bin),
    )
    return leaf_lookup(tree.leaf_value[None], node[None, :])[0]


# --------------------------------------------------------------------------
# forests (bagged, batched over the fit axis) and boosting (round-scanned)
# --------------------------------------------------------------------------
def fit_forest(
    binned: jax.Array,
    target: jax.Array,      # [N] regression target, or class ids (num_classes)
    row_mask: jax.Array,    # [N]
    num_trees: int,
    max_depth: int,
    num_bins: int,
    subsample_rate: float | jax.Array = 1.0,
    colsample_rate: float | jax.Array = 1.0,
    min_instances: float | jax.Array = 1.0,
    min_info_gain: float | jax.Array = 0.0,
    seed: int = 42,
    bootstrap: bool = True,
    lowp: bool = False,
    feature_groups=None,
    feature_subset: int | None = None,
    info_gain_norm: float = 2.0,
    num_classes: int = 0,
) -> Tree:
    """Random forest of mean-target trees — the K=1 case of
    fit_forest_batched (Spark RandomForest parity: variance impurity ==
    gain formula with h=1, λ=0). Returns stacked Tree arrays [T, ...].
    ``seed`` must be a concrete int (it keys host-side PRNG splits)."""
    trees = fit_forest_batched(
        binned, target, jnp.asarray(row_mask)[None, :],
        num_trees=num_trees, max_depth=max_depth, num_bins=num_bins,
        subsample_rate=subsample_rate, colsample_rate=colsample_rate,
        min_instances=min_instances, min_info_gain=min_info_gain,
        seed=int(seed), bootstrap=bootstrap, lowp=lowp,
        feature_groups=feature_groups, feature_subset=feature_subset,
        info_gain_norm=info_gain_norm, num_classes=num_classes,
    )
    return jax.tree.map(lambda a: a[0], trees)


def sum_trees(per_tree: jax.Array) -> jax.Array:
    """[T, N] per-tree leaf values -> [N] ensemble sum, as a pairwise tree
    of explicit adds. A reduce op leaves the association to each program's
    compiler, so the same ensemble summed inside two different programs
    (the fused serving graph and the staged predict, the gather traversal
    and the Pallas one) differed in the last bits at a few hundred trees;
    explicit adds are never reassociated, which makes equal per-tree
    values give equal scores on every backend."""
    x = per_tree
    while x.shape[0] > 1:
        if x.shape[0] % 2:
            x = jnp.concatenate([x, jnp.zeros_like(x[:1])])
        x = x[0::2] + x[1::2]
    return x[0]


def predict_forest(binned: jax.Array, trees: Tree) -> jax.Array:
    """Mean leaf value across the stacked forest -> [N] ([V, N] from
    leaves of V channels: a class forest's mean vote)."""
    preds = jax.vmap(lambda t: predict_tree(binned, t))(trees)  # [T, N]
    return sum_trees(preds) / preds.shape[0]


@jax.jit
def predict_forest_raw(x: jax.Array, thresholds: jax.Array, trees: Tree) -> jax.Array:
    """Fused bin + forest predict — ONE dispatch per call (model scoring runs
    through here; the eager op-by-op path costs a host round-trip per
    op)."""
    return predict_forest(bin_data(x, thresholds), trees)


@jax.jit
def predict_boosted_raw(
    x: jax.Array, thresholds: jax.Array, trees: Tree,
    eta: jax.Array, base_score: jax.Array,
) -> jax.Array:
    """Fused bin + boosted predict — one dispatch; eta (a scalar, or the
    [R] per-tree weights of a Spark GBT model) and base_score are traced
    arrays so distinct hyperparameter values share the compilation."""
    return predict_boosted(bin_data(x, thresholds), trees, eta, base_score)


# --------------------------------------------------------------------------
# host (numpy) predict path — serving-size batches
# --------------------------------------------------------------------------
# Serving-size batches skip the device: no upload, no dispatch, no result
# sync. Where the crossover to the device sits is not re-measured on a
# local chip (TPTPU_HOST_PREDICT_MAX; see ROADMAP M6 / C5). Semantics mirror
# bin_data/predict_tree exactly (parity pinned in tests).


def _f32_order_keys(a: np.ndarray) -> np.ndarray:
    """Monotone uint32 image of float32 order (the radix-sort bit trick):
    strict order and ties are preserved EXACTLY, so integer binning matches
    float binning bit-for-bit. -0.0 normalizes to +0.0 first (they compare
    equal as floats but have different bit patterns); NaN maps above +inf,
    which matches the device path for NaN thresholds (x > NaN is False)."""
    f = np.ascontiguousarray(a, dtype=np.float32) + np.float32(0.0)
    b = f.view(np.uint32)
    return np.where(b >> 31 != 0, ~b, b | np.uint32(0x80000000))


def _threshold_flat_keys(thresholds: np.ndarray) -> np.ndarray:
    """Per-feature-offset int64 keys of a threshold matrix (the serving
    path calls bin_data_host per batch with FIXED model thresholds —
    callers cache this)."""
    thr = np.asarray(thresholds, dtype=np.float32)
    # canonicalize NaN thresholds to the positive-NaN bit pattern: a NaN
    # with the sign bit set would key BELOW all finite values via the ~b
    # branch, binning rows one higher than the device path (where
    # x > NaN is always False). Unreachable via quantile_thresholds but
    # this function is public API for other callers.
    thr = np.where(np.isnan(thr), np.float32(np.nan), thr)
    seg = np.arange(thr.shape[0], dtype=np.int64) << 32
    return (_f32_order_keys(thr).astype(np.int64) + seg[:, None]).ravel()


def bin_data_host(
    x: np.ndarray, thresholds: np.ndarray,
    flat_keys: np.ndarray | None = None,
) -> np.ndarray:
    """Host bin_data: ONE searchsorted over per-feature-offset integer keys
    — O(N·F·log(F·B)) with no Python per-feature loop, vs the device scan's
    O(N·F·B). Exact (integer key space, see _f32_order_keys): ties at a
    threshold bin identically to the device path. Requires per-row sorted
    thresholds (quantile_thresholds guarantees it); NaN x bins to 0.
    ``flat_keys`` (from _threshold_flat_keys) skips re-keying fixed model
    thresholds on every serving batch."""
    xs = np.asarray(x, dtype=np.float32)
    n, num_f = xs.shape
    bm1 = np.asarray(thresholds).shape[1]
    xk = _f32_order_keys(xs).astype(np.int64)
    xk[np.isnan(xs)] = 0  # device: NaN > thr is False -> bin 0
    seg = np.arange(num_f, dtype=np.int64) << 32
    if flat_keys is None:
        flat_keys = _threshold_flat_keys(thresholds)
    idx = np.searchsorted(flat_keys, (xk + seg[None, :]).ravel(), side="left")
    return (
        idx.reshape(n, num_f) - np.arange(num_f, dtype=np.int64) * bm1
    ).astype(np.int32)


class _PreparedStack:
    """Contiguous traversal arrays for a host tree stack, built once per
    model (the flagship winner is a 200-tree depth-10 stack; slicing
    ``sf[:, lvl, :]`` per call copies [200, 512] twice per level).

    ``raw`` feeds the C kernel directly; the numpy-fallback structures
    (per-level flat arrays, truncated past the deepest real split — a
    split-free level maps node -> 2*node unconditionally, folded into one
    final shift) are built LAZILY so the native path never holds a second
    copy of the split arrays."""

    __slots__ = ("raw", "r", "depth", "width", "leaf_width", "max_feat",
                 "_levels", "_tail_shift", "leaf_flat")

    def __init__(self, sf: np.ndarray, sb: np.ndarray, lv: np.ndarray):
        self.raw = (sf, sb, lv)
        self.r, self.depth, self.width = sf.shape
        # stack-shape validation happens HERE, once per model load — a
        # corrupt manifest fails at prepare time with the same IndexError
        # the traversals would raise, and the serving hot loop keeps only
        # the O(1) plane-width guard in _leaf_sum (native.tree_predict_sum
        # runs prevalidated; env TPTPU_NATIVE_VALIDATE restores the
        # per-call check)
        if lv.ndim != 2 or lv.shape[1] != (1 << self.depth):
            raise IndexError(
                f"tree stack: leaf table width {lv.shape[1:]} does not "
                f"match depth {self.depth} (expected {1 << self.depth})"
            )
        self.max_feat = int(sf.max()) if sf.size else -1
        self.leaf_width = lv.shape[1]
        self.leaf_flat = lv.ravel()  # contiguous -> view, not a copy
        self._levels = None
        self._tail_shift = 0

    @property
    def levels(self) -> tuple:
        if self._levels is None:
            sf, sb, _ = self.raw
            eff = 0
            for lvl in range(self.depth):
                if (sf[:, lvl, :] >= 0).any():
                    eff = lvl + 1
            self._levels = tuple(
                (np.ascontiguousarray(sf[:, lvl, :]).ravel(),
                 np.ascontiguousarray(sb[:, lvl, :]).ravel())
                for lvl in range(eff)
            )
            self._tail_shift = self.depth - eff
        return self._levels

    @property
    def tail_shift(self) -> int:
        self.levels  # noqa: B018 — computed together
        return self._tail_shift


def prepare_host_stack(t) -> _PreparedStack:
    return _PreparedStack(
        np.ascontiguousarray(t.split_feat, dtype=np.int32),
        np.ascontiguousarray(t.split_bin, dtype=np.int32),
        np.ascontiguousarray(t.leaf_value, dtype=np.float32),
    )


def _traverse_host(binned: np.ndarray, stack) -> np.ndarray:
    """Leaf values [R, N] for a stacked host-tree pytree (mirrors
    predict_tree's routing: split_feat < 0 routes left).

    Flat 1-D fancy gathers instead of take_along_axis: at serving sizes
    the traversal is gather-overhead-bound, and the flat form measured
    ~5x cheaper on the 891-row Titanic batch. ``stack`` is a Tree of host
    arrays or a _PreparedStack (see prepare_host_stack) that skips
    per-call level slicing."""
    ps = stack if isinstance(stack, _PreparedStack) else prepare_host_stack(stack)
    n = binned.shape[0]
    node = np.zeros((ps.r, n), dtype=np.intp)
    toff = (np.arange(ps.r, dtype=np.intp) * ps.width)[:, None]
    bflat = np.ascontiguousarray(binned).ravel()
    rowbase = np.arange(n, dtype=np.intp)[None, :] * binned.shape[1]
    for sf_l, sb_l in ps.levels:
        flat = node + toff
        feat = sf_l[flat]
        thrb = sb_l[flat]
        code = bflat[rowbase + np.maximum(feat, 0)]
        node = node * 2 + ((feat >= 0) & (code > thrb))
    if ps.tail_shift:
        node <<= ps.tail_shift
    return ps.leaf_flat[
        node + (np.arange(ps.r, dtype=np.intp) * ps.leaf_width)[:, None]
    ]


def _leaf_sum(binned: np.ndarray, stack) -> np.ndarray:
    """Per-row sum of leaf values across the stack, float32 [N] — the C
    kernel when the native library is built (about 4x the numpy traversal
    on the flagship's 200-tree depth-10 winner), numpy otherwise."""
    from .. import native

    ps = stack if isinstance(stack, _PreparedStack) else prepare_host_stack(stack)
    if ps.max_feat >= binned.shape[1]:
        raise IndexError(
            f"tree stack: split feature index {ps.max_feat} out of bounds "
            f"for {binned.shape[1]} binned feature(s)"
        )
    out = native.tree_predict_sum(binned, *ps.raw, prevalidated=True)
    if out is not None:
        return out
    return _traverse_host(binned, ps).sum(axis=0)


def host_serving_plan(
    thresholds: np.ndarray, stacks: list,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, list]:
    """Used-feature compaction for host serving batches.

    A fitted model's trees reference a small subset of the feature space
    (tens of features out of the flagship's 928), but bin_data_host bins
    every column. Returns ``(used, thr_used, flat_keys, stacks_c)`` where
    ``used`` is the sorted unique split-feature index set, ``thr_used`` /
    ``flat_keys`` are the threshold rows (and their searchsorted keys) for
    just those features, and ``stacks_c`` are the tree stacks with
    split_feat remapped into the compact space. Binning ``x[:, used]``
    against ``thr_used`` and traversing ``stacks_c`` is bit-identical to
    the full-width path (binning is columnwise-independent)."""
    feats = [
        np.asarray(t.split_feat)[np.asarray(t.split_feat) >= 0].ravel()
        for t in stacks
    ]
    used = np.unique(np.concatenate(feats + [np.zeros(1, np.int64)]))
    used = used.astype(np.int64)
    thr_used = np.ascontiguousarray(np.asarray(thresholds)[used])
    flat_keys = _threshold_flat_keys(thr_used)
    stacks_c = [
        prepare_host_stack(
            t._replace(
                split_feat=np.where(
                    np.asarray(t.split_feat) >= 0,
                    np.searchsorted(used, np.asarray(t.split_feat)),
                    np.asarray(t.split_feat),
                ).astype(np.int32)
            )
        )
        for t in stacks
    ]
    return used, thr_used, flat_keys, stacks_c


def predict_boosted_host(
    x: np.ndarray, thresholds: np.ndarray, trees: Tree,
    eta: float, base_score: float,
    binned: np.ndarray | None = None,
) -> np.ndarray:
    """Numpy twin of predict_boosted_raw; ``trees`` must hold host arrays
    (a Tree stack or a prepared one from prepare_host_stack/
    host_serving_plan). ``eta``: a scalar, or [R] per-tree weights (then
    the per-tree values come from the numpy traversal: the C kernel sums
    unweighted). ``binned`` lets multi-stack callers bin x once across
    stacks."""
    if binned is None:
        binned = bin_data_host(x, thresholds)
    eta = np.asarray(eta, dtype=np.float32)
    if eta.ndim == 0:
        return np.float32(base_score) + eta * _leaf_sum(binned, trees)
    per_tree = _traverse_host(binned, trees)  # [R, N]
    return np.float32(base_score) + (eta[:, None] * per_tree).sum(
        axis=0, dtype=np.float32
    )


def predict_forest_host(
    x: np.ndarray, thresholds: np.ndarray, trees: Tree,
    binned: np.ndarray | None = None,
) -> np.ndarray:
    """Numpy twin of predict_forest_raw; ``trees`` must hold host arrays
    (a Tree stack or a prepared one). ``binned`` lets multi-stack callers
    bin x once across stacks."""
    if binned is None:
        binned = bin_data_host(x, thresholds)
    t = trees if isinstance(trees, _PreparedStack) else prepare_host_stack(trees)
    return _leaf_sum(binned, t) / np.float32(t.r)


@jax.jit
def sweep_boosted_outputs(
    x: jax.Array, thresholds: jax.Array, trees: Tree,
    eta_v: jax.Array, base_v: jax.Array,
) -> jax.Array:
    """Margins for a WHOLE sweep stack in one dispatch: trees [K, R, ...]
    (folds × grid lanes) → [K, N]. The validator's per-model predict loop
    costs a dispatch + input upload per model; here
    the full candidate sweep's validation margins are one program."""
    binned = bin_data(x, thresholds)

    return jax.vmap(
        lambda t, e, b: predict_boosted(binned, t, e, b)
    )(trees, eta_v, base_v)


@jax.jit
def sweep_forest_outputs(
    x: jax.Array, thresholds: jax.Array, trees: Tree,
    eta_v: jax.Array, base_v: jax.Array,
) -> jax.Array:
    """Forest mean-leaf outputs for a sweep stack: trees [K, T, ...] →
    [K, N] ([K, V, N] from leaves of V channels). eta_v/base_v are
    accepted (and ignored) so both sweep entry points share a call
    signature."""
    binned = bin_data(x, thresholds)
    return jax.vmap(lambda t: predict_forest(binned, t))(trees)


def _node_key(tkey):
    """The key a tree's node subsets are folded from: the second half of
    the tree's key (the first draws its bootstrap counts)."""
    return jax.random.split(tkey)[1]


@partial(jax.jit, static_argnames=("n", "f", "bootstrap"))
def _bag_masks(tkey, sub, col, row_mask, n, f, bootstrap):
    """Bootstrap row counts + feature masks for one tree across K fits.
    Drawn over the UNPADDED row count so the sharded path (which pads rows
    afterwards) samples bit-identically to the single-device path."""
    k_fits = row_mask.shape[0]
    k1, k2 = jax.random.split(tkey)
    if bootstrap:
        # same key for every fit, drawn per-fit (vmap): each lane's sample
        # equals the sequential fit_forest draw, so batched and sequential
        # sweeps train bit-identical forests
        counts = jax.vmap(
            lambda r: jax.random.poisson(k1, r, (n,))
        )(sub).astype(jnp.float32)
    else:
        counts = jnp.ones((k_fits, n), dtype=jnp.float32)
    rmask = row_mask * counts
    fmask = jax.vmap(
        lambda c: (jax.random.uniform(k2, (f,)) < c)
    )(col).astype(jnp.float32)
    fmask = jnp.where(
        fmask.sum(axis=1, keepdims=True) == 0, jnp.ones((1, f)), fmask
    )
    return rmask, fmask


@partial(
    jax.jit,
    static_argnames=(
        "num_trees", "max_depth", "num_bins", "bootstrap", "lowp", "hist_impl",
        "feature_subset", "info_gain_norm", "num_classes",
    ),
)
def _forest_trees_scan(
    binned, target, row_mask, seed_arr, sub, col, min_instances,
    min_info_gain,
    feature_groups=None, max_depth_v=None, *,
    num_trees, max_depth, num_bins, bootstrap, lowp, hist_impl=None,
    feature_subset, info_gain_norm, num_classes=0,
) -> tuple[Tree, jax.Array, HistSlots]:
    """The whole bagged forest as ONE program: ``lax.scan`` over the
    per-tree PRNG keys with a single tree-growth body (the same shape as
    the boosting rounds scan, which runs 200 rounds in under a second on
    chip). This replaces both the host tree loop (a dispatch per tree)
    and the tree-folded K'=trees×K kernels
    (whose wide grids schedule badly and defeat the early level exit).
    Masks are drawn per tree from the same keys, so forests are
    bit-identical to the per-tree path.

    ``feature_subset`` columns are admissible at each NODE, drawn inside
    the growth from the tree's second key and the node's heap index
    (``_grow_tree_impl``): every tree builds its histograms over every
    column, as the source's algorithm does.

    ``num_classes`` (static): 0 for a real ``target`` (one value channel,
    a leaf its mean); K >= 2 where ``target`` holds class ids 0 … K - 1:
    the value channels are then the indicators of classes 1 … K - 1
    (``forest_gradients``), so every lane grows ONE forest whose nodes
    hold K class counts, whatever K.

    Returns (Tree arrays [K, T, ...], training outputs [K, N] (over C > 2
    classes [K, C, N]: each lane's mean class distribution), HistSlots
    [T, depth]) — the outputs
    are each lane's mean-leaf prediction over ALL rows, read from the
    grower's own final routing, so the CV sweep needs no separate eval
    traversal program."""
    k_fits, n = row_mask.shape
    f = binned.shape[1]
    with jax.named_scope("tree/gradients"):
        gb = forest_gradients(jnp.asarray(target), k_fits, num_classes)
    ones = jnp.ones((k_fits, n), dtype=jnp.float32)
    mi_k = jnp.broadcast_to(
        jnp.asarray(min_instances, dtype=jnp.float32).reshape(-1), (k_fits,)
    )
    mg_k = jnp.broadcast_to(
        jnp.asarray(min_info_gain, dtype=jnp.float32).reshape(-1), (k_fits,)
    )
    # per-tree keys derived IN-PROGRAM (same threefry ops → identical draws
    # to the old eager derivation; keeps PRNGKey/split eager compiles off
    # the per-process critical path)
    tkeys = jax.random.split(
        jax.random.PRNGKey(seed_arr[0].astype(jnp.uint32)), num_trees
    )

    def body(_, tk):
        rm_t, fm_t = _bag_masks(tk, sub, col, row_mask, n, f, bootstrap)
        tree, node, slots = _grow_tree_impl(
            binned, gb, ones, rm_t, fm_t,
            max_depth=max_depth, num_bins=num_bins,
            reg_lambda=0.0, gamma=0.0,
            min_child_weight=mi_k, min_info_gain=mg_k,
            hist_impl=hist_impl, lowp=lowp, feature_groups=feature_groups,
            max_depth_v=max_depth_v, info_gain_norm=info_gain_norm,
            node_subset=feature_subset, node_key=_node_key(tk),
        )
        # this tree's prediction for EVERY row from the grower's own final
        # routing (leaf lookup — no re-traversal)
        with jax.named_scope("tree/outputs"):
            pred_t = leaf_lookup(tree.leaf_value, node)
        return None, (tree, pred_t, slots)

    _, (trees, preds, slots) = jax.lax.scan(body, None, tkeys)  # [T, K, ...]
    with jax.named_scope("tree/outputs"):
        outs = preds.mean(axis=0)  # [K, (V,) N] forest mean-leaf outputs
    return (
        jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), trees), outs, slots
    )


def forest_gradients(target: jax.Array, k_fits: int, num_classes: int):
    """A forest's ``grad`` for ``_grow_tree_impl`` (h is 1) from the [N]
    ``target`` every lane shares: -target, [K, N], where it is a real
    number (``num_classes`` 0); where it holds class ids, the negated
    indicators of classes 1 … K - 1, [K, K - 1, N] ([K, N] at two classes:
    one value channel keeps its shapes)."""
    n = target.shape[0]
    if not num_classes:
        return jnp.broadcast_to(-target[None, :], (k_fits, n))
    ind = jnp.stack(
        [-(target == c).astype(jnp.float32) for c in range(1, num_classes)]
    )  # [K - 1, N]
    if ind.shape[0] == 1:
        return jnp.broadcast_to(ind, (k_fits, n))
    return jnp.broadcast_to(ind[None], (k_fits, *ind.shape))


def fit_forest_batched(
    binned: jax.Array,      # [N, F] shared
    target: jax.Array,      # [N] shared regression target / class ids
    row_mask: jax.Array,    # [K, N] per-fit row masks (folds × resamples)
    num_trees: int,
    max_depth: int,
    num_bins: int,
    subsample_rate: jax.Array | float = 1.0,   # scalar or [K]
    colsample_rate: jax.Array | float = 1.0,
    min_instances: jax.Array | float = 1.0,
    min_info_gain: jax.Array | float = 0.0,
    seed: int = 42,
    bootstrap: bool = True,
    lowp: bool = False,
    mesh=None,
    feature_groups=None,
    max_depth_v=None,     # [K] int32: per-lane depth caps (see _grow_tree_impl)
    return_outputs: bool = False,
    return_slots: bool = False,
    feature_subset: int | None = None,
    info_gain_norm: float = 2.0,
    num_classes: int = 0,
) -> Tree:
    """K random forests batched over the fit axis, the whole bagged forest
    as ONE scan-over-trees program (_forest_trees_scan — one tree-growth
    body, no per-tree dispatches, no tree-folded wide kernels). Returns
    stacked Tree arrays [K, T, ...]; with ``return_outputs`` also the
    [K, N] training-matrix mean-leaf outputs (each lane's predictions on
    every row — the CV sweep evaluates from these instead of re-traversing),
    and with ``return_slots`` last the fit's ``HistSlots`` [T, depth] (for
    ``await_outputs``; None from a sharded fit, which has recorded them).

    ``feature_subset`` (default: all F) is the count of columns each NODE
    may split on (Spark's featureSubsetStrategy, resolved by the caller:
    ``models/gbdt.py``); ``colsample_rate`` is a per-LANE Bernoulli column
    mask a tree (XGBoost's colsample_bytree), and the node's subset
    multiplies it. ``info_gain_norm``: ``GINI`` for a class label
    (``num_classes`` >= 2, ``target`` the class ids: one forest whose
    nodes hold the K class counts), ``VARIANCE`` for a real target (see
    ``_grow_tree_impl``, ``_forest_trees_scan``).

    With ``mesh`` set, rows shard over the mesh's data axis and each level's
    histogram psums over it (grows the same trees as the unsharded path —
    see _grow_tree_impl)."""
    k_fits, n = row_mask.shape
    if feature_subset is None:
        feature_subset = int(binned.shape[1])
    # host-side numpy for every small knob: a dtype-converting or
    # broadcasting jnp op here is an EAGER device program compiled per
    # process; f32 numpy arrays transfer without compiling anything, and
    # the broadcasts/PRNG-key derivation happen INSIDE the jitted program
    def _vec_np(v):
        return np.asarray(
            np.broadcast_to(np.asarray(v, dtype=np.float32).reshape(-1),
                            (k_fits,))
        )

    sub = _vec_np(subsample_rate)
    col = _vec_np(colsample_rate)
    mi = np.asarray(min_instances, dtype=np.float32)
    mg = np.asarray(min_info_gain, dtype=np.float32)
    seed_arr = np.asarray([seed], dtype=np.uint32)
    if mesh is None:
        from ..parallel.mesh import execution_mesh

        mesh = execution_mesh()
    if mesh is not None:
        if max_depth_v is not None:
            raise NotImplementedError(
                "per-lane depth caps are single-device only (the sweep path)"
            )
        key = jax.random.PRNGKey(seed)
        tkeys = jax.random.split(key, num_trees)
        trees, outs = _fit_forest_batched_sharded(
            mesh, binned, target, row_mask, tkeys, jnp.asarray(sub),
            jnp.asarray(col), mi, mg,
            num_trees=num_trees, max_depth=max_depth, num_bins=num_bins,
            bootstrap=bootstrap, lowp=lowp, feature_groups=feature_groups,
            feature_subset=feature_subset, info_gain_norm=info_gain_norm,
            num_classes=num_classes,
        )
        # the sharded fit has pulled its results, and recorded its slots
        return _fit_result(trees, outs, None, return_outputs, return_slots)
    from ..utils.aot import aot_call

    trees, outs, slots = aot_call(
        "forest_scan", _forest_trees_scan,
        (binned, target, row_mask, seed_arr, sub, col, mi, mg,
         feature_groups, max_depth_v),
        dict(num_trees=num_trees,
             feature_subset=int(feature_subset),
             info_gain_norm=float(info_gain_norm),
             num_classes=int(num_classes),
             max_depth=max_depth, num_bins=num_bins, bootstrap=bootstrap,
             # lowp is only sound when target values are bf16-exact
             # (classification indicators); regression keeps f32
             lowp=lowp,
             # resolved EARLY so both the jit cache and the AOT blob key
             # see the trace-time impl choice
             hist_impl=_resolved_impl()),
    )
    return _fit_result(trees, outs, slots, return_outputs, return_slots)


def _fit_result(trees, outputs, slots, return_outputs, return_slots):
    """trees, then what the caller asked for of (outputs, slots). A sharded
    fit has no slots left to hand on: it pulls its results to the host
    itself, and ``await_outputs`` recorded them there."""
    extra = (outputs,) * return_outputs + (slots,) * return_slots
    return (trees, *extra) if extra else trees


#: The boosting objectives, by the program's names for them (a static
#: argument of the fit programs; ``tree/fit_dispatch`` carries it).
#: ``binary:logistic`` and ``reg:squarederror`` are XGBoost's: Newton trees
#: (g and h the loss's two derivatives, leaf -G/(H + lambda)), every tree
#: weighted ``eta``. The ``spark:*`` pair are Spark ML's
#: ``GradientBoostedTrees.boost``: FIRST-order regression trees (h = 1, so a
#: node's H is its row count and ``min_child_weight`` a count), the first
#: fitted to the labels themselves at weight 1, every later one to the
#: loss's negative gradient at weight ``eta`` (Spark's ``stepSize``):
#: ``spark:logloss`` on labels 2y - 1 under L = 2 log(1 + exp(-2 y~ F)),
#: whose negative gradient is 4 y~ / (1 + exp(2 y~ F));
#: ``spark:squarederror`` under (y - F)^2, negative gradient 2 (y - F).
SPARK_OBJECTIVES = ("spark:logloss", "spark:squarederror")
OBJECTIVES = ("binary:logistic", "reg:squarederror") + SPARK_OBJECTIVES


def boost_tree_weights(objective: str, num_rounds: int, eta) -> np.ndarray:
    """[R] float32: the weight each round's tree carries in the margin."""
    w = np.full(int(num_rounds), eta, dtype=np.float32)
    if objective in SPARK_OBJECTIVES and len(w):
        w[0] = 1.0
    return w


def fit_boosted(
    binned: jax.Array,
    y: jax.Array,          # [N] labels (0/1 binary, float regression)
    row_mask: jax.Array,
    num_rounds: int,
    max_depth: int,
    num_bins: int,
    eta: float | jax.Array = 0.3,
    reg_lambda: float | jax.Array = 1.0,
    gamma: float | jax.Array = 0.0,
    min_child_weight: float | jax.Array = 1.0,
    min_info_gain: float | jax.Array = 0.0,
    base_score: float | jax.Array = 0.0,
    objective: str = "binary:logistic",
    feature_groups=None,
    info_gain_norm: float = 0.0,
) -> tuple[Tree, jax.Array]:
    """Gradient boosting — the K=1 case of fit_boosted_batched, under one
    of ``OBJECTIVES`` (XGBoost's Newton trees at shrinkage eta, or Spark
    GBT's first-order ones). Returns stacked trees [R, ...] and the
    training margin [N].
    ``info_gain_norm``: 0 for XGBoost's absolute stop rule, 2 for Spark
    GBT's per-row variance decrease (``_grow_tree_impl``)."""
    trees, margin = fit_boosted_batched(
        binned, y, jnp.asarray(row_mask)[None, :],
        num_rounds=num_rounds, max_depth=max_depth, num_bins=num_bins,
        eta=eta, reg_lambda=reg_lambda, gamma=gamma,
        min_child_weight=min_child_weight, min_info_gain=min_info_gain,
        base_score=base_score, objective=objective,
        feature_groups=feature_groups, info_gain_norm=info_gain_norm,
    )
    return jax.tree.map(lambda a: a[0], trees), margin[0]


def weighted_tree_sum(per_tree: jax.Array, eta) -> jax.Array:
    """[R, N] per-tree leaf values -> [N] ensemble margin (less the base
    score). ``eta`` is one shrinkage for every tree (a scalar: XGBoost's,
    applied to the sum) or the weight of each tree ([R]: Spark's
    ``treeWeights``, 1 then ``stepSize``: ``boost_tree_weights``)."""
    eta = jnp.asarray(eta, dtype=per_tree.dtype)
    if eta.ndim == 0:
        return eta * sum_trees(per_tree)
    return sum_trees(eta[:, None] * per_tree)


def predict_boosted(
    binned: jax.Array,
    trees: Tree,
    eta: float | jax.Array,
    base_score: float = 0.0,
) -> jax.Array:
    """``base_score`` + the weighted sum of the stacked trees' leaf values;
    ``eta``: a scalar or per-tree weights (``weighted_tree_sum``)."""
    preds = jax.vmap(lambda t: predict_tree(binned, t))(trees)  # [R, N]
    return base_score + weighted_tree_sum(preds, eta)


def _boost_chunk_body(
    binned, y, row_mask, margin0, eta_v, reg_lambda, gamma,
    min_child_weight, min_info_gain, feature_groups=None, *,
    num_rounds, max_depth, num_bins, objective,
    axis_name=None, axis_size=1, hist_impl=None, info_gain_norm=0.0,
) -> tuple[Tree, jax.Array, HistSlots]:
    """The boosting rounds of all K fits (lax.scan inside one program) —
    shared by the single-device jit and the shard_map'd path
    (axis_name set: per-level histograms psum over the mesh axis; margins,
    gradients and predictions stay row-local). Returns (trees [K, R, ...],
    margins [K, N], HistSlots [R, depth])."""
    k_fits, n = row_mask.shape
    f = binned.shape[1]
    feat_mask = jnp.ones((k_fits, f), dtype=jnp.float32)

    if objective not in OBJECTIVES:
        raise ValueError(f"objective {objective!r}: one of {OBJECTIVES}")
    first_order = objective in SPARK_OBJECTIVES

    def grads(margin, r):  # [K, N_local]; r: the round, from 0
        if objective == "binary:logistic":
            p = jax.nn.sigmoid(margin)
            return p - y[None, :], p * (1.0 - p)
        if first_order:
            # g = -target, h = 1: the leaf -G/H is the node's mean target
            if objective == "spark:logloss":
                ys = 2.0 * y[None, :] - 1.0
                t = 4.0 * ys / (1.0 + jnp.exp(2.0 * ys * margin))
            else:
                ys = jnp.broadcast_to(y[None, :], margin.shape)
                t = 2.0 * (ys - margin)
            return -jnp.where(r == 0, ys, t), jnp.ones_like(margin)
        return margin - y[None, :], jnp.ones_like(margin)

    def round_step(margin, r):
        with jax.named_scope("tree/gradients"):
            g, h = grads(margin, r)
        # no lowp under any objective: from the second round on g is a real
        # number (a pseudo-residual, a Newton gradient), which the kernel's
        # split-operand path carries float32-exact and one bfloat16 would not
        tree, leaf_slot, slots = _grow_tree_impl(
            binned, g, h, row_mask, feat_mask,
            max_depth=max_depth, num_bins=num_bins,
            reg_lambda=reg_lambda, gamma=gamma,
            min_child_weight=min_child_weight, min_info_gain=min_info_gain,
            axis_name=axis_name, axis_size=axis_size, hist_impl=hist_impl,
            feature_groups=feature_groups, info_gain_norm=info_gain_norm,
        )
        # margin update straight from the grower's final routing — one
        # small-table lookup instead of a full predict_tree re-traversal
        with jax.named_scope("tree/outputs"):
            step = _small_table_lookup(tree.leaf_value, leaf_slot)  # [K, N]
            weight = jnp.where(r == 0, 1.0, eta_v) if first_order else eta_v
            margin = margin + weight[:, None] * step
        if first_order:
            lanes = jnp.int32(k_fits)
            slots = slots._replace(
                rounds_label=jnp.where(r == 0, lanes, 0),
                rounds_residual=jnp.where(r == 0, 0, lanes),
            )
        return margin, (tree, slots)

    margin, (trees, slots) = jax.lax.scan(
        round_step, margin0, jnp.arange(num_rounds, dtype=jnp.int32)
    )
    # [R, K, ...] -> [K, R, ...] INSIDE the program: an eager transpose
    # after the fact costs a compile-cache round-trip per shape
    trees = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), trees)
    return trees, margin, slots  # trees [K, R, ...]


_boost_rounds_batched = partial(
    jax.jit,
    static_argnames=(
        "num_rounds", "max_depth", "num_bins", "objective",
        "axis_name", "axis_size", "hist_impl", "info_gain_norm",
    ),
)(_boost_chunk_body)


def _resolved_impl() -> str:
    """The histogram impl the trace WILL use, resolved at call time so it
    participates in jit-cache and AOT-blob identity (``_grow_tree_impl``
    would otherwise ask the backend at trace time)."""
    from .hist_pallas import default_impl

    return default_impl()


def fit_boosted_batched(
    binned: jax.Array,     # [N, F] shared
    y: jax.Array,          # [N] shared labels
    row_mask: jax.Array,   # [K, N]
    num_rounds: int,
    max_depth: int,
    num_bins: int,
    eta: jax.Array | float = 0.3,          # scalar or [K]
    reg_lambda: jax.Array | float = 1.0,
    gamma: jax.Array | float = 0.0,
    min_child_weight: jax.Array | float = 1.0,
    min_info_gain: jax.Array | float = 0.0,
    base_score: jax.Array | float = 0.0,
    objective: str = "binary:logistic",
    mesh=None,
    feature_groups=None,
    return_slots: bool = False,
    info_gain_norm: float = 0.0,
) -> tuple[Tree, jax.Array]:
    """K boosting runs batched over the fit axis: every round grows all K
    trees in one histogram build, and all the rounds are one scan in one
    program. Returns Tree arrays [K, R, ...] and the
    training margins [K, N]; with ``return_slots`` also the fit's
    ``HistSlots`` [R, depth] (for ``await_outputs``; None from a sharded
    fit, which has recorded them).

    With ``mesh`` set, rows shard over the mesh's data axis: gradients and
    margins live sharded, per-level histograms psum over ICI, and trees come
    back replicated — the Rabit-tracker topology with XLA collectives."""
    k_fits, n = row_mask.shape
    # numpy, not eager jnp: dtype-converting/broadcasting eager ops each
    # compile a device program per process; f32 numpy transfers compile
    # nothing
    def _np_f32(v):
        return np.asarray(v, dtype=np.float32)

    eta_v = np.asarray(
        np.broadcast_to(_np_f32(eta).reshape(-1), (k_fits,))
    )
    lam = _np_f32(reg_lambda)
    gam = _np_f32(gamma)
    mcw = _np_f32(min_child_weight)
    mig = _np_f32(min_info_gain)
    if mesh is None:
        from ..parallel.mesh import execution_mesh

        mesh = execution_mesh()
    if mesh is not None:
        trees, margin = _fit_boosted_batched_sharded(
            mesh, binned, y, row_mask, jnp.asarray(eta_v), jnp.asarray(lam),
            jnp.asarray(gam), jnp.asarray(mcw), jnp.asarray(mig),
            base_score=base_score, num_rounds=num_rounds,
            max_depth=max_depth, num_bins=num_bins, objective=objective,
            feature_groups=feature_groups, info_gain_norm=info_gain_norm,
        )
        return _fit_result(trees, margin, None, True, return_slots)
    # f32 numpy broadcast (no eager compile), then ONE device transfer
    margin = jnp.asarray(np.asarray(np.broadcast_to(
        _np_f32(base_score).reshape(-1, 1), (k_fits, n)
    )))
    from ..compiler.dispatch import donating
    from ..utils.aot import aot_call

    # the [K, N] margin is the scan's carry and nothing reads its initial
    # value afterwards, so the executable aliases it into the output margin
    # instead of allocating a second buffer (TPTPU_DONATE=0 opts out)
    boost_chunk_fn = donating(
        "boost_chunk", _boost_rounds_batched, donate_argnums=(3,),
        static_argnames=(
            "num_rounds", "max_depth", "num_bins", "objective",
            "axis_name", "axis_size", "hist_impl", "info_gain_norm",
        ),
    )
    trees, margin, slots = aot_call(
        "boost_chunk", boost_chunk_fn,
        (binned, y, row_mask, margin, eta_v, lam, gam, mcw, mig,
         feature_groups),
        dict(num_rounds=num_rounds, max_depth=max_depth, num_bins=num_bins,
             objective=objective, hist_impl=_resolved_impl(),
             info_gain_norm=float(info_gain_norm)),
    )
    return _fit_result(trees, margin, slots, True, return_slots)


# --------------------------------------------------------------------------
# mesh-sharded growth: rows shard over the data axis; per-level histograms
# psum over ICI — the XLA-collective replacement for XGBoost's Rabit
# allreduce of per-worker histograms (OpXGBoostClassifier.scala:101,
# SURVEY §2.6 row 5). The split search consumes the reduced histogram
# identically, so the sharded path grows the SAME trees as single-device.
# --------------------------------------------------------------------------
def _pad_axis(a: jax.Array, axis: int, multiple: int) -> jax.Array:
    """Zero-pad ``axis`` to a multiple (static shard shapes). Zero rows are
    inert in growth: row_mask 0 drops them from histograms and leaf sums."""
    pad = (-a.shape[axis]) % multiple
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@lru_cache(maxsize=None)
def _sharded_grow_kernel(mesh, max_depth, num_bins, hist_impl, lowp,
                         has_groups=False):
    """jit(shard_map(grow)) for one (mesh, statics) combo, built once —
    rebuilding per call would retrace every tree. Feature-group index
    arrays (when present) are replicated: the feature axis is unsharded."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS

    size = mesh.shape[DATA_AXIS]

    def body(binned, grad, hess, row_mask, feat_mask, lam, gam, mcw, mig,
             *grp):
        return _grow_tree_impl(
            binned, grad, hess, row_mask, feat_mask,
            max_depth=max_depth, num_bins=num_bins,
            reg_lambda=lam, gamma=gam, min_child_weight=mcw,
            min_info_gain=mig, hist_impl=hist_impl, lowp=lowp,
            axis_name=DATA_AXIS, axis_size=size,
            feature_groups=grp if grp else None,
        )[0]

    rep = P()
    sm = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS, None),   # binned [N, F]
            P(None, DATA_AXIS),   # grad [K, N]
            P(None, DATA_AXIS),   # hess
            P(None, DATA_AXIS),   # row_mask
            rep, rep, rep, rep, rep,
        ) + ((rep, rep) if has_groups else ()),
        out_specs=Tree(split_feat=rep, split_bin=rep, leaf_value=rep),
        check_vma=False,
    )
    return jax.jit(sm)


@lru_cache(maxsize=None)
def _sharded_forest_scan_kernel(mesh, max_depth, num_bins, hist_impl, lowp,
                                has_groups=False, feature_subset=None,
                                info_gain_norm=2.0, num_classes=0):
    """jit(shard_map(scan-over-trees)): the sharded counterpart of
    _forest_trees_scan. Per-tree masks are drawn OUTSIDE (global-row
    semantics) and enter sharded on the row axis; the scan carries the
    whole forest in one program, psum'ing each level's histograms. Each
    tree's node-subset key rides the scan too (replicated: every shard
    draws the same subsets). Also emits [K, N] training outputs
    (row-sharded) like the single-device scan."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS

    size = mesh.shape[DATA_AXIS]

    def body_fn(binned, target, rmasks, fmasks, nkeys, mi_k, mg_k, *grp):
        k_fits = rmasks.shape[1]
        n_local = binned.shape[0]
        with jax.named_scope("tree/gradients"):
            gb = forest_gradients(target, k_fits, num_classes)
        ones = jnp.ones((k_fits, n_local), dtype=jnp.float32)

        def one_tree(_, xs):
            rm_t, fm_t, nk = xs
            tree, node, slots = _grow_tree_impl(
                binned, gb, ones, rm_t, fm_t,
                max_depth=max_depth, num_bins=num_bins,
                reg_lambda=0.0, gamma=0.0,
                min_child_weight=mi_k, min_info_gain=mg_k,
                hist_impl=hist_impl, lowp=lowp,
                axis_name=DATA_AXIS, axis_size=size,
                feature_groups=grp if grp else None,
                info_gain_norm=info_gain_norm,
                node_subset=feature_subset, node_key=nk,
            )
            with jax.named_scope("tree/outputs"):
                pred_t = leaf_lookup(tree.leaf_value, node)
            return None, (tree, pred_t, slots)

        _, (trees, preds, slots) = jax.lax.scan(
            one_tree, None, (rmasks, fmasks, nkeys)
        )
        with jax.named_scope("tree/outputs"):
            outs = preds.mean(axis=0)  # [K, (V,) n_local]
        return (
            jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), trees), outs,
            slots,
        )

    rep = P()
    sm = shard_map(
        body_fn,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS, None),        # binned [N, F]
            P(DATA_AXIS),              # target [N]
            P(None, None, DATA_AXIS),  # rmasks [T, K, N]
            rep,                       # fmasks [T, K, F]
            rep,                       # nkeys [T, 2]
            rep, rep,
        ) + ((rep, rep) if has_groups else ()),
        out_specs=(
            Tree(split_feat=rep, split_bin=rep, leaf_value=rep),
            # the rows are the outputs' last axis
            P(None, DATA_AXIS) if num_classes <= 2
            else P(None, None, DATA_AXIS),
            HistSlots(*(rep for _ in HistSlots._fields)),
        ),
        check_vma=False,
    )
    return jax.jit(sm)


def _fit_forest_batched_sharded(
    mesh, binned, target, row_mask, tkeys, sub, col, mi, mg,
    num_trees, max_depth, num_bins, bootstrap, lowp, feature_groups=None,
    feature_subset=None, info_gain_norm=2.0, num_classes=0,
) -> tuple[Tree, np.ndarray]:
    from ..parallel.mesh import DATA_AXIS

    size = mesh.shape[DATA_AXIS]
    k_fits, n = row_mask.shape
    f = binned.shape[1]
    binned_p = _pad_axis(jnp.asarray(binned, jnp.int32), 0, size)
    target_p = _pad_axis(jnp.asarray(target, jnp.float32), 0, size)
    rm = jnp.asarray(row_mask, jnp.float32)
    # masks drawn over the UNPADDED n — bit-identical to the single-device
    # draw — then padded with zeros; [T, K, N] rides the scan axis
    rmasks, fmasks = jax.vmap(
        lambda tk: _bag_masks(tk, sub, col, rm, n=n, f=f, bootstrap=bootstrap)
    )(tkeys)
    rmasks = _pad_axis(rmasks, 2, size)
    mi_k = jnp.broadcast_to(jnp.asarray(mi, jnp.float32).reshape(-1), (k_fits,))
    mg_k = jnp.broadcast_to(jnp.asarray(mg, jnp.float32).reshape(-1), (k_fits,))
    kern = _sharded_forest_scan_kernel(
        mesh, max_depth, num_bins, _resolved_impl(), lowp,
        has_groups=feature_groups is not None,
        feature_subset=feature_subset, info_gain_norm=info_gain_norm,
        num_classes=num_classes,
    )
    grp_args = tuple(feature_groups) if feature_groups is not None else ()
    nkeys = jax.vmap(_node_key)(tkeys)
    trees, outs, slots = kern(binned_p, target_p, rmasks, fmasks, nkeys,
                              mi_k, mg_k, *grp_args)
    # pull replicated trees to HOST once
    trees, outs = await_outputs((trees, outs), hist_slots=slots)
    return trees, outs[..., :n]


@lru_cache(maxsize=None)
def _sharded_boost_kernel(mesh, num_rounds, max_depth, num_bins, objective,
                          hist_impl=None, has_groups=False,
                          info_gain_norm=0.0):
    """jit(shard_map(boosting rounds)): margins stay row-sharded across
    the scan; each round's histogram build psums over the data axis."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS

    size = mesh.shape[DATA_AXIS]

    def body(binned, y, row_mask, margin0, eta_v, lam, gam, mcw, mig,
             *grp):
        return _boost_chunk_body(
            binned, y, row_mask, margin0, eta_v, lam, gam, mcw, mig,
            grp if grp else None,
            num_rounds=num_rounds, max_depth=max_depth, num_bins=num_bins,
            objective=objective, axis_name=DATA_AXIS, axis_size=size,
            hist_impl=hist_impl, info_gain_norm=info_gain_norm,
        )

    rep = P()
    sm = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS, None),   # binned
            P(DATA_AXIS),         # y
            P(None, DATA_AXIS),   # row_mask
            P(None, DATA_AXIS),   # margin0
            rep, rep, rep, rep, rep,
        ) + ((rep, rep) if has_groups else ()),
        out_specs=(
            Tree(split_feat=rep, split_bin=rep, leaf_value=rep),
            P(None, DATA_AXIS),
            HistSlots(*(rep for _ in HistSlots._fields)),
        ),
        check_vma=False,
    )
    return jax.jit(sm)


def _fit_boosted_batched_sharded(
    mesh, binned, y, row_mask, eta_v, lam, gam, mcw, mig,
    base_score, num_rounds, max_depth, num_bins, objective,
    feature_groups=None, info_gain_norm=0.0,
) -> tuple[Tree, jax.Array]:
    from ..parallel.mesh import DATA_AXIS

    size = mesh.shape[DATA_AXIS]
    k_fits, n = row_mask.shape
    binned_p = _pad_axis(jnp.asarray(binned, jnp.int32), 0, size)
    y_p = _pad_axis(jnp.asarray(y, jnp.float32), 0, size)
    rm_p = _pad_axis(jnp.asarray(row_mask, jnp.float32), 1, size)
    n_pad = binned_p.shape[0]
    margin = jnp.broadcast_to(
        jnp.asarray(base_score, dtype=jnp.float32).reshape(-1, 1),
        (k_fits, n_pad),
    ).astype(jnp.float32)
    lam = jnp.asarray(lam, jnp.float32).reshape(-1)
    gam = jnp.asarray(gam, jnp.float32).reshape(-1)
    mcw = jnp.asarray(mcw, jnp.float32).reshape(-1)
    mig = jnp.asarray(mig, jnp.float32).reshape(-1)
    kern = _sharded_boost_kernel(
        mesh, num_rounds, max_depth, num_bins, objective, _resolved_impl(),
        has_groups=feature_groups is not None,
        info_gain_norm=float(info_gain_norm),
    )
    grp_args = tuple(feature_groups) if feature_groups is not None else ()
    trees, margin, slots = kern(
        binned_p, y_p, rm_p, margin, eta_v, lam, gam, mcw, mig, *grp_args
    )
    # host-fetch the replicated trees — eager multi-device reshapes
    # intermittently abort the XLA:CPU async runtime
    trees = await_outputs(trees, hist_slots=slots)
    return trees, await_outputs(margin)[:, :n]


# --------------------------------------------------------------------------
# compiled-program contract audit (analysis/program.py, TPJ0xx)
# --------------------------------------------------------------------------
def program_trace_specs():
    """Representative trace shapes for the banked fit-time tree programs
    (the boosting-round chunk, the bagged-forest scan and the binning's
    column statistics). The bucketed axis is the fit-lane count K (the
    rows, for the statistics); rounds/trees/depth stay tiny — jaxpr
    structure is independent of them (they only change scan lengths)."""
    import jax

    f32, i32 = "float32", "int32"

    def _common(k: int):
        return (
            jax.ShapeDtypeStruct((16, 3), i32),   # binned
            jax.ShapeDtypeStruct((16,), f32),     # y / target
            jax.ShapeDtypeStruct((k, 16), f32),   # row_mask
        )

    def _boost(k: int):
        binned, y, rm = _common(k)
        s = jax.ShapeDtypeStruct((), f32)
        return (
            (
                binned, y, rm,
                jax.ShapeDtypeStruct((k, 16), f32),  # margin (donated)
                jax.ShapeDtypeStruct((k,), f32),     # eta_v
                s, s, s, s,                          # lam, gam, mcw, mig
                None,                                # feature_groups
            ),
            # traced under the objective whose body holds the most: the
            # round index, per-round targets and weights, the round counts
            # (XGBoost's objectives differ in the gradient line alone)
            dict(
                num_rounds=2, max_depth=2, num_bins=4,
                objective="spark:logloss", hist_impl=_resolved_impl(),
                info_gain_norm=2.0,
            ),
        )

    def _forest(k: int):
        binned, target, rm = _common(k)
        s = jax.ShapeDtypeStruct((), f32)
        return (
            (
                binned, target, rm,
                jax.ShapeDtypeStruct((1,), "uint32"),  # seed_arr
                jax.ShapeDtypeStruct((k,), f32),       # sub
                jax.ShapeDtypeStruct((k,), f32),       # col
                s, s,                                  # mi, mg
                None, None,
            ),
            dict(
                num_trees=2, max_depth=2, num_bins=4, bootstrap=True,
                lowp=False, hist_impl=_resolved_impl(),
                feature_subset=2, info_gain_norm=4.0,
            ),
        )

    return [
        dict(
            name="boost_chunk",
            fn=_boost_rounds_batched,
            base_fn=_boost_chunk_body,
            build=_boost,
            buckets=(4, 8), bucket_axis="lanes",
            donate_argnums=(3,),
            static_argnames=(
                "num_rounds", "max_depth", "num_bins", "objective",
                "axis_name", "axis_size", "hist_impl", "info_gain_norm",
            ),
        ),
        dict(
            name="forest_scan",
            fn=_forest_trees_scan,
            build=_forest,
            buckets=(4, 8), bucket_axis="lanes",
        ),
        dict(
            name="bin_column_stats",
            fn=bin_column_stats,
            build=lambda n: (
                (jax.ShapeDtypeStruct((n, 3), f32),), dict(max_bins=4)
            ),
            buckets=(8, 16),
        ),
    ]
