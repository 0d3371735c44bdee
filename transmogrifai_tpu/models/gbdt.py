"""Tree-ensemble model stages: XGBoost / GBT / RandomForest / DecisionTree.

Reference stages replaced (all on the histogram learner in models/trees.py):
  * OpXGBoostClassifier/Regressor (core/.../classification/OpXGBoostClassifier.scala
    — JNI libxgboost + Rabit allreduce): XLA boosting with second-order
    gradients; pass ``mesh=`` to the trees.fit_* entry points to shard rows
    over the mesh data axis with per-level histograms psum'd over ICI
    (trees._sharded_boost_kernel — the Rabit replacement, proven
    tree-identical in tests/test_trees_sharded.py).
  * OpGBTClassifier/Regressor: Spark ML's ``GradientBoostedTrees.boost``
    (defaults maxIter 20, stepSize 0.1) — FIRST-order regression trees with
    the variance gain, the first fitted to the labels (±1 for the
    classifier) at weight 1, each later one to the loss's negative gradient
    at weight stepSize; ``minInstancesPerNode`` counts rows;
    P(y = 1) = σ(2F). The objectives ``spark:logloss`` /
    ``spark:squarederror`` of ``trees.OBJECTIVES``; pinned node for node in
    tests/test_gbt_source_semantics.py. (Up to PR 31 these two were the
    XGBoost learner under Spark's knob names.)
  * OpRandomForestClassifier/Regressor (Spark RF; defaults numTrees 50 in
    selector grids, maxDepth 5 spark default). Over K classes the
    classifier grows ONE forest, as Spark's does: a node holds its K
    class-weight sums (the histogram kernel's statistic axis: the
    indicators of classes 1 … K - 1 and w), its impurity is the K-class
    Gini 1 - Σ p_k², a leaf is the class distribution and the forest's
    probability the mean of its trees' leaf distributions
    (``FOREST_MULTICLASS``; K = 2 is the same code; pinned node for node
    in tests/test_forest_multiclass.py). Up to PR 33 more than two
    classes were K one-vs-rest indicator forests.
  * OpDecisionTreeClassifier/Regressor: single unbagged tree.

Known divergences (documented per SURVEY.md §7 hard-part 5): multiclass
XGBoost is one-vs-rest rather than softmax-per-round; every tree family
takes its split candidates from exact float64 quantiles (at most
``max_bins`` - 1 a column) where Spark samples rows for them, and breaks
ties between equal gains by lowest column, then lowest bin;
``GBTClassifier`` over more than two classes is one-vs-rest (Spark's is
binary only).
"""
from __future__ import annotations

import logging
import math

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger(__name__)

from ..telemetry import metrics as _tm
from ..telemetry import spans as _tspans
from .base import PredictorEstimator, PredictorModel
from . import trees as TR

import threading as _threading

# (matrix, max_bins) -> (x-ref, thresholds, binned, fgroups); see
# _TreeEstimator._binned
_BINNED_CACHE: dict = {}
_BINNED_LOCK = _threading.Lock()


class BinCacheStats(_tm.LedgerCore):
    """``treeStats`` — what ``_BINNED_CACHE`` was asked and what it holds:
    look-ups and hits (cumulative), the entries and device bytes it
    kept after the last look-up, and its misses by the route their
    thresholds took (``_bin_into_cache``). Registered as the ``tree``
    source of ``telemetry.render_prometheus()``; the cache's numbers ride
    each ``tree/bin_prepare`` span as attributes."""

    def __init__(self) -> None:
        super().__init__((
            "binCacheLookups", "binCacheHits",         # cumulative
            "binCacheEntries", "binCacheDeviceBytes",  # after the last look-up
            # cache misses by where their column statistics were computed
            "thresholdsDevice", "thresholdsHost",      # cumulative
        ))

    def record_lookup(self, hit: bool, entries: int, device_bytes: int) -> None:
        with self._lock:
            self._counts["binCacheLookups"] += 1
            self._counts["binCacheHits"] += int(hit)
            self._counts["binCacheEntries"] = entries
            self._counts["binCacheDeviceBytes"] = device_bytes

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            self._reset_counts()


_BIN_STATS = BinCacheStats()
_tm.REGISTRY.register_source(
    "tree",
    lambda: {**_BIN_STATS.snapshot(), **TR.hist_slot_stats().snapshot()},
)


def bin_cache_stats() -> BinCacheStats:
    return _BIN_STATS


def _bin_cache_census() -> dict:
    """What the cache holds now: entries, the device bytes of their bin
    codes, and the host bytes its strong references keep alive (matrix and
    thresholds)."""
    with _BINNED_LOCK:
        held = list(_BINNED_CACHE.values())
    return {
        "cache_entries": len(held),
        "cache_device_bytes": sum(int(e[2].nbytes) for e in held),
        "cache_host_bytes": sum(
            int(a.nbytes) for e in held for a in e[:2]
            if isinstance(a, np.ndarray)
        ),
    }


#: How the random-forest classifier treats a label of more than two classes,
#: stated as ``hist_pallas.default_impl`` states the histogram builder: ONE
#: forest a fit whose nodes hold the K class counts (Spark's learner). A
#: program from before PR 34 has no such statement (it grew K one-vs-rest
#: indicator forests).
FOREST_MULTICLASS = "one forest: K class counts a node"


def _sigmoid(m: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-m))


def _tree_from_arrays(arrays: dict, prefix: str = "") -> TR.Tree:
    return TR.Tree(
        split_feat=arrays[f"{prefix}split_feat"],
        split_bin=arrays[f"{prefix}split_bin"],
        leaf_value=arrays[f"{prefix}leaf_value"],
    )


def _class_trees_from_arrays(arrays: dict) -> list[TR.Tree]:
    out = []
    c = 0
    while f"c{c}__split_feat" in arrays:
        out.append(_tree_from_arrays(arrays, prefix=f"c{c}__"))
        c += 1
    return out


def _feature_bin_groups(x: np.ndarray):
    """(narrow_idx, wide_idx) partition of the columns: 0/1 indicator
    columns (the bulk of a transmogrified one-hot matrix) vs multi-valued
    ones. Tree growth searches the narrow group at 2 bins instead of
    max_bins — split-search cost scales with features×bins, so this is a
    ~10-16× cut on one-hot-heavy matrices with identical fitted trees
    (trees._grow_tree_impl docstring). Host-side: one vectorized pass
    over the matrix (``trees.bin_column_stats`` makes the same pass on the
    device where ``_bin_into_cache`` takes that route)."""
    xf = np.asarray(x)
    with np.errstate(invalid="ignore"):
        binary = ((xf == 0) | (xf == 1) | ~np.isfinite(xf)).all(axis=0)
    return _groups_from_flags(binary)


def _groups_from_flags(binary: np.ndarray):
    """``_feature_bin_groups``' result from its per-column predicate."""
    narrow = np.nonzero(binary)[0].astype(np.int32)
    wide = np.nonzero(~binary)[0].astype(np.int32)
    if len(narrow) == 0:
        return None
    return jnp.asarray(narrow), jnp.asarray(wide)


def _hist_shape_attrs(
    binned, fgroups, lanes, depth, bins, lowp, stat_channels
) -> dict:
    """What a ``tree/fit_dispatch`` span says of the histogram kernel, from
    the same shapes the fit program is traced with: ``hist_tiles`` (the
    bin-loop kernel's tiles at each width the fit can build at:
    ``trees.hist_tiles``), ``stat_channels`` (the statistics a node holds)
    and ``stat_channels_built`` (those the kernel's operand has lanes for:
    ``trees.stat_channels_built``)."""
    from ..parallel.mesh import DATA_AXIS, execution_mesh

    n, f = binned.shape
    groups = [(f, bins)] if fgroups is None else [
        (int(idx.shape[0]), b)
        for idx, b in zip(fgroups, (2, bins)) if idx.shape[0]
    ]
    mesh = execution_mesh()
    shape = dict(
        shards=None if mesh is None else mesh.shape[DATA_AXIS],
        stat_channels=stat_channels,
    )
    return dict(
        hist_tiles=TR.hist_tiles(n, lanes, groups, depth, lowp, **shape),
        stat_channels=stat_channels,
        stat_channels_built=TR.stat_channels_built(
            n, lanes, groups, depth, lowp, **shape
        ),
    )


# Planes of at least this many values (rows x columns) take their column
# statistics on the device (``_bin_into_cache``). The host's two passes cost
# 51-70 ns a value, the program 1.9 ns (a v5e at 1,002,701 x 357: PERF.md
# section 6, PR 28), but a shape the machine's bank has not seen compiles
# for 11-16 s first: at 2^26 values (a 268 MB plane, 3.4-4.7 s of host
# passes) four misses repay that; under it the host's pass is the smaller
# risk, and the tier-1 suite's planes (under 2^20) compile no sort.
_DEVICE_STATS_MIN_VALUES = 1 << 26


_bin_data_jit = jax.jit(TR.bin_data)


@jax.jit
def _stack_lane(trees, lane):
    """One lane of a stacked-trees pytree, sliced ON DEVICE (lane is a
    traced scalar, so every lane of a given stack shape shares one
    program)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, lane, 0, keepdims=False),
        trees,
    )


class _LazySlice:
    """Deferred materialization of one lane of a stacked-trees fit.

    The batched sweep fits K candidates' trees as one device array that the
    sweep itself never slices — candidate metrics come from
    sweep_eval_batched on the DEVICE stack, and only the winner's model
    ever needs its tree arrays (for persistence or re-scoring). The slice
    happens on device, so a whole refit-lane stack is never pulled to host
    for one lane; persistence downloads just the winner's lane when
    get_arrays() converts to numpy."""

    def __init__(self, stack: dict, lane: int):
        self.stack = stack
        self.lane = lane

    def get(self):
        cache = self.stack.setdefault("lane_slices", {})
        out = cache.get(self.lane)
        if out is None:
            trees = self.stack["trees"]
            if isinstance(jax.tree.leaves(trees)[0], np.ndarray):
                # host stack (multi-device mesh path pre-pulls — see
                # _batched_group_fit): plain numpy view
                out = jax.tree.map(lambda a: a[self.lane], trees)
            else:
                from ..utils.aot import aot_call

                # a dispatch on the fit program's result: it can block
                # until that program has run
                with _tspans.span("tree/await_outputs") as sp:
                    out = aot_call(
                        "stack_lane", _stack_lane,
                        (trees, np.int32(self.lane)), {},
                    )
                    sp.attrs["bytes"] = sum(
                        int(a.nbytes) for a in jax.tree.leaves(out)
                    )
            cache[self.lane] = out
        return out


def await_stack_outputs(stack: dict):
    """A fitted stack's [K, N] training outputs on the host. The first
    read of a stack hands its fit's ``HistSlots`` to the same span: the
    program that made the outputs made them too."""
    return TR.await_outputs(
        stack["outputs"], hist_slots=stack.pop("hist_slots", None)
    )


def _resolve_trees(t):
    return t.get() if isinstance(t, _LazySlice) else t


def _host_trees(t):
    """Tree pytree as host numpy (persistence path — downloads only this
    lane when the trees live on device)."""
    return jax.tree.map(np.asarray, _resolve_trees(t))


def _aot_predict_boosted(x, thresholds, trees, eta, base_score):
    """predict_boosted_raw through the AOT executable bank. The refit winner
    rides the validation sweep (detach_from_sweep), so the standalone
    scoring program is never compiled during training — without the bank,
    the FIRST model.score() of a fresh process pays the full remote compile
    (the round-3 score_s regression: 0.024 s -> 0.742 s)."""
    from ..utils.aot import aot_call

    return aot_call(
        "predict_boosted", TR.predict_boosted_raw,
        (x, thresholds, trees, eta, base_score), {},
    )


def _aot_predict_forest(x, thresholds, trees):
    """predict_forest_raw through the AOT executable bank (see
    _aot_predict_boosted)."""
    from ..utils.aot import aot_call

    return aot_call("predict_forest", TR.predict_forest_raw,
                    (x, thresholds, trees), {})


class _BinnedModel(PredictorModel):
    """Shared state for binned-tree models; prediction goes through the
    fused jitted entry points (trees.predict_*_raw) which bin internally —
    one dispatch per scoring call.

    Tree arrays are stored as given (host numpy from batched sweeps, device
    from sequential fits) and uploaded LAZILY on first predict: the sweep
    path never calls per-model predict (see sweep_eval_batched), so eagerly
    uploading every candidate's trees would re-send the whole stacked array
    for nothing."""

    def __init__(self, operation_name: str, thresholds: np.ndarray, uid=None):
        super().__init__(operation_name, uid=uid)
        self.thresholds = np.asarray(thresholds, dtype=np.float32)
        self._dev_cache = None
        self._host_cache = None
        self._serve_plan = None

    def _use_host(self, x) -> bool:
        """Serving-size batches predict in numpy on the host; larger ones
        dispatch on the device. The 16,384-row cutoff is not re-measured on
        a local chip; see ROADMAP M6 / C5 and D3."""
        import os

        return len(x) <= int(os.environ.get("TPTPU_HOST_PREDICT_MAX", "16384"))

    def _boost_weights(self) -> np.ndarray:
        """What a boosted model's predict paths take as ``eta``: one
        shrinkage for every tree (float32 scalar; XGBoost's), or, from a
        Spark GBT model, the [R] weight of each tree."""
        return np.float32(self.eta)

    def _host(self, trees):
        if self._host_cache is None:
            if isinstance(trees, list):
                self._host_cache = [_host_trees(t) for t in trees]
            else:
                self._host_cache = _host_trees(trees)
        return self._host_cache

    def _dev(self, trees):
        if self._dev_cache is None:
            if isinstance(trees, list):
                trees = [_resolve_trees(t) for t in trees]
            else:
                trees = _resolve_trees(trees)
            self._dev_cache = jax.tree.map(jnp.asarray, trees)
        return self._dev_cache

    def _predict_stacks(self, x, trees, boosted: bool) -> np.ndarray:
        """float64 [N, k] of margins (boosted) or mean-leaf values (forest)
        — k=1 for a single stacked-tree pytree (k = V where its leaves hold
        V value channels: a class forest), one column per class for a
        list. The ONLY host-vs-device dispatch point for scoring."""
        many = isinstance(trees, list)
        if self._use_host(x):
            # Fixed for a fitted model, built once: the used-feature subset
            # (trees touch tens of the flagship's 928 columns), its
            # threshold keys, and feature-remapped stacks — then each batch
            # bins ONLY those columns, once across all class stacks.
            plan = getattr(self, "_serve_plan", None)
            if plan is None:
                hs0 = self._host(trees)
                plan = TR.host_serving_plan(
                    self.thresholds, hs0 if many else TR.leaf_channels(hs0)
                )
                self._serve_plan = plan
                # the full-width host stacks are only needed to build the
                # plan — keeping them would double host serving memory
                self._host_cache = None
            used, thr_used, fk, hs = plan
            # xu/thr_used stay consistent with the REMAPPED stacks: if a
            # future path ever let ``binned`` default inside predict_*_host,
            # it would still bin in the compact feature space
            xu = np.asarray(x, dtype=np.float32)[:, used]
            binned = TR.bin_data_host(xu, thr_used, flat_keys=fk)
            if boosted:
                outs = [
                    TR.predict_boosted_host(
                        xu, thr_used, t, self._boost_weights(),
                        self.base_score,
                        binned=binned,
                    )
                    for t in hs
                ]
            else:
                outs = [
                    TR.predict_forest_host(xu, thr_used, t, binned=binned)
                    for t in hs
                ]
        else:
            from ..compiler.dispatch import device_f32

            # the serving path prefetches the feature matrix while earlier
            # plan stages run; pick that transfer up here
            xj = device_f32(x)
            thr = jnp.asarray(self.thresholds)
            ds = self._dev(trees)
            ds = ds if many else [ds]
            if boosted:
                eta = jnp.asarray(self._boost_weights())
                base = jnp.float32(self.base_score)
                outs = [np.asarray(_aot_predict_boosted(xj, thr, t, eta, base))
                        for t in ds]
            else:
                outs = [np.asarray(_aot_predict_forest(xj, thr, t))
                        for t in ds]
        # one column a stack; a class forest's [V, N] gives V
        return np.concatenate(
            [np.atleast_2d(o) for o in outs], axis=0
        ).T.astype(np.float64)

    # ---- shared predict entry: family-specific stacks + HOST epilogue ----
    def _tree_stacks(self):
        """(trees-or-per-class-list, boosted) — the arrays
        ``_predict_stacks`` dispatches over."""
        raise NotImplementedError

    def predictions_from_core(self, core: np.ndarray):
        """(pred, prob, raw) from the [N, k] margin/mean-leaf core — the
        numpy tail shared by the staged path and the fused graph's
        downloaded core, so the two are bit-identical."""
        raise NotImplementedError

    def predict_arrays(self, x):
        trees, boosted = self._tree_stacks()
        return self.predictions_from_core(
            self._predict_stacks(x, trees, boosted=boosted)
        )

    def fused_predict_spec(self):
        """Device core for the fused scoring graph: the same
        ``predict_*_raw`` programs the staged device path banks, traced
        over the in-graph plane — tree predictions stay bit-identical."""
        from ..compiler.fused import PredictorPlan
        from .serve_pallas import (
            predict_boosted_pallas, predict_forest_pallas, serve_impl,
            serve_interpret,
        )

        trees, boosted = self._tree_stacks()
        ds = self._dev(trees)
        # one scalar-leaf stack a column of the core
        ds = ds if isinstance(trees, list) else TR.leaf_channels(ds)
        params: dict = {
            "thr": np.asarray(self.thresholds, dtype=np.float32),
            "trees": tuple(ds),
        }
        if boosted:
            params["eta"] = self._boost_weights()
            params["base"] = np.float32(self.base_score)
        # implementation is resolved HERE, at spec-build time, never inside
        # the traced core — the choice is baked into the program and salts
        # the fused fingerprint (":pl") so the bank never replays a gather
        # executable for a pallas plan or vice versa
        pallas = serve_impl() == "pallas"
        interp = serve_interpret()

        def core(plane, p):
            if pallas:
                binned = TR.bin_data(plane, p["thr"])
                num_bins = p["thr"].shape[1] + 1
                if boosted:
                    outs = [
                        predict_boosted_pallas(
                            binned, t, p["eta"], p["base"],
                            interpret=interp, num_bins=num_bins,
                        )
                        for t in p["trees"]
                    ]
                else:
                    outs = [
                        predict_forest_pallas(
                            binned, t, interpret=interp, num_bins=num_bins,
                        )
                        for t in p["trees"]
                    ]
            elif boosted:
                outs = [
                    TR.predict_boosted_raw(
                        plane, p["thr"], t, p["eta"], p["base"]
                    )
                    for t in p["trees"]
                ]
            else:
                outs = [
                    TR.predict_forest_raw(plane, p["thr"], t)
                    for t in p["trees"]
                ]
            return jnp.stack(outs, axis=1)

        return PredictorPlan(
            stage=self, in_dim=int(self.thresholds.shape[0]), params=params,
            core=core, epilogue=self.predictions_from_core,
            descriptor=(
                f"{'boost' if boosted else 'forest'}:{len(ds)}"
                # per-tree weights are another program than one eta
                + (":tw" if boosted and params["eta"].ndim else "")
                + (":pl" if pallas else "")
            ),
        )

    def fused_bin_thresholds(self) -> np.ndarray:
        """Per-input bin edges for the quantized fused plane: the
        quantizer emits bin-aligned uint8 codes that re-bin IDENTICALLY
        in-graph, so quantized tree predictions stay bit-identical to the
        f32 plane (``featurize/quantize.py``)."""
        return np.asarray(self.thresholds, dtype=np.float32)

    def detach_from_sweep(self):
        """Cut every reference to the stacked sweep arrays: materialize this
        model's own lane (a small independent device array) and drop the
        stack attrs, so selecting a winner does not pin the whole
        (folds+refit) × grid stack in HBM for the model's lifetime."""
        def own(t):
            # numpy lane slices are VIEWS into the host stack — copy so the
            # base array can be collected; device slices are independent
            resolved = _resolve_trees(t)

            def _own_leaf(a):
                if isinstance(a, np.ndarray):
                    return np.array(a)
                # device lane: start the host transfer NOW — the first
                # consumer is the holdout predict's host serving plan, and
                # the async copy overlaps the holdout DAG transform instead
                # of blocking np.asarray on the download
                try:
                    a.copy_to_host_async()
                except Exception:
                    pass
                return a

            return jax.tree.map(_own_leaf, resolved)

        # predict caches built pre-detach hold lane VIEWS into the sweep
        # stack — clearing them is part of the contract
        self._dev_cache = None
        self._host_cache = None
        self._serve_plan = None
        for attr in ("trees", "trees_per_class"):
            t = getattr(self, attr, None)
            if isinstance(t, _LazySlice):
                setattr(self, attr, own(t))
            elif isinstance(t, list):
                setattr(self, attr, [own(x) for x in t])
        for attr in ("_sweep_stack", "_sweep_lane"):
            if hasattr(self, attr):
                delattr(self, attr)


class BoostedBinaryModel(_BinnedModel):
    def __init__(self, thresholds, trees: TR.Tree, eta: float, base_score: float, uid=None):
        super().__init__("xgbClassifier", thresholds, uid=uid)
        self.trees = trees
        self.eta = eta
        self.base_score = base_score

    def get_arrays(self):
        t = _host_trees(self.trees)
        return {
            "thresholds": self.thresholds,
            "split_feat": t.split_feat,
            "split_bin": t.split_bin,
            "leaf_value": t.leaf_value,
        }

    def get_params(self):
        return {"eta": self.eta, "base_score": self.base_score}

    @classmethod
    def from_params(cls, params, arrays):
        return cls(
            arrays["thresholds"], _tree_from_arrays(arrays),
            params["eta"], params["base_score"],
        )

    def _tree_stacks(self):
        return self.trees, True

    def predictions_from_core(self, core):
        return self.predictions_from_sweep(
            np.asarray(core, dtype=np.float64)[:, 0]
        )

    # ---- batched sweep-eval protocol (validators._sweep_family) ----------
    sweep_mode = "boost"

    def sweep_lane_params(self):
        return float(self.eta), float(self.base_score)

    #: P(y = 1) = sigmoid(_LINK * F): 1 for XGBoost's logistic margin, 2
    #: for Spark's LogLoss on +-1 labels (1 / (1 + exp(-2F)))
    _LINK = 1.0

    def predictions_from_sweep(self, margin):
        p1 = _sigmoid(self._LINK * np.asarray(margin, dtype=np.float64))
        prob = np.stack([1 - p1, p1], axis=1)
        raw = np.stack([-margin, margin], axis=1)
        return (p1 > 0.5).astype(np.float64), prob, raw


class BoostedMultiModel(_BinnedModel):
    """One-vs-rest stack of boosted binary models."""

    def __init__(self, thresholds, trees_per_class: list[TR.Tree], eta, base_score, uid=None):
        super().__init__("xgbClassifier", thresholds, uid=uid)
        self.trees_per_class = trees_per_class
        self.eta = eta
        self.base_score = base_score

    def get_arrays(self):
        out = {"thresholds": self.thresholds}
        for c, t in enumerate(map(_host_trees, self.trees_per_class)):
            out[f"c{c}__split_feat"] = t.split_feat
            out[f"c{c}__split_bin"] = t.split_bin
            out[f"c{c}__leaf_value"] = t.leaf_value
        return out

    def get_params(self):
        return {"eta": self.eta, "base_score": self.base_score}

    @classmethod
    def from_params(cls, params, arrays):
        return cls(
            arrays["thresholds"], _class_trees_from_arrays(arrays),
            params["eta"], params["base_score"],
        )

    def _tree_stacks(self):
        return self.trees_per_class, True

    _LINK = 1.0  # as BoostedBinaryModel's

    def predictions_from_core(self, core):
        margins = np.asarray(core, dtype=np.float64)
        p = _sigmoid(self._LINK * margins)
        prob = p / np.maximum(p.sum(axis=1, keepdims=True), 1e-12)
        return prob.argmax(axis=1).astype(np.float64), prob, margins


class BoostedRegressionModel(_BinnedModel):
    def __init__(self, thresholds, trees, eta, base_score, uid=None):
        super().__init__("xgbRegressor", thresholds, uid=uid)
        self.trees = trees
        self.eta = eta
        self.base_score = base_score

    def get_arrays(self):
        t = _host_trees(self.trees)
        return {
            "thresholds": self.thresholds,
            "split_feat": t.split_feat,
            "split_bin": t.split_bin,
            "leaf_value": t.leaf_value,
        }

    def get_params(self):
        return {"eta": self.eta, "base_score": self.base_score}

    @classmethod
    def from_params(cls, params, arrays):
        return cls(
            arrays["thresholds"], _tree_from_arrays(arrays),
            params["eta"], params["base_score"],
        )

    def _tree_stacks(self):
        return self.trees, True

    def predictions_from_core(self, core):
        return np.asarray(core, dtype=np.float64)[:, 0], None, None

    sweep_mode = "boost"

    def sweep_lane_params(self):
        return float(self.eta), float(self.base_score)

    @staticmethod
    def predictions_from_sweep(margin):
        return np.asarray(margin, dtype=np.float64), None, None


class _PerTreeWeights:
    """Mixin of a boosted model whose trees carry a weight EACH (Spark's
    ``treeWeights``: 1, then ``stepSize``), not one ``eta``, from a zero
    margin. The trees are kept as fitted (a leaf is its node's mean
    target) and the weights beside them (``get_arrays``); the predict paths
    (the host traversal, the banked ``predict_boosted`` program, the fused
    graph's core and the serve kernel's epilogue) take the weights where an
    XGBoost model hands them its ``eta``. Its classifiers map the margin
    through 1 / (1 + exp(-2F))."""

    _LINK = 2.0

    def __init__(self, thresholds, trees, tree_weights, uid=None):
        tree_weights = np.asarray(tree_weights, dtype=np.float32)
        # eta: what every tree after the first weighs (the model's params)
        super().__init__(
            thresholds, trees, float(tree_weights[-1]), 0.0, uid=uid
        )
        self.tree_weights = tree_weights

    def _boost_weights(self) -> np.ndarray:
        return self.tree_weights

    def get_arrays(self):
        return {**super().get_arrays(), "tree_weights": self.tree_weights}

    @classmethod
    def from_params(cls, params, arrays):
        trees = (
            _class_trees_from_arrays(arrays)
            if "c0__split_feat" in arrays else _tree_from_arrays(arrays)
        )
        return cls(arrays["thresholds"], trees, arrays["tree_weights"])

    def sweep_lane_params(self):
        # asked only of a stack that lacks the fit program's own outputs;
        # (eta, base) cannot say per-tree weights, so sweep_eval_batched
        # falls back to predict_arrays
        raise NotImplementedError("per-tree weights")


class GBTClassificationModel(_PerTreeWeights, BoostedBinaryModel):
    """Spark's ``GBTClassificationModel``: F = Σ w_r T_r over regression
    trees, raw [-F, F], P(y = 1) = 1 / (1 + exp(-2F)), label F > 0."""


class GBTMultiModel(_PerTreeWeights, BoostedMultiModel):
    """One-vs-rest stack of ``GBTClassificationModel`` margins (Spark's
    classifier is binary; more classes are this repo's one-vs-rest)."""


class GBTRegressionModel(_PerTreeWeights, BoostedRegressionModel):
    """Spark's ``GBTRegressionModel``: the prediction is Σ w_r T_r."""


class ForestClassifierModel(_BinnedModel):
    """ONE forest over the label's K classes (``FOREST_MULTICLASS``): a
    leaf holds the class distribution C_k / W (``leaf_value``
    [T, leaves, K]; at two classes the class-1 share alone, [T, leaves]);
    the forest's probability is the mean of its trees' leaf
    distributions."""

    def __init__(self, thresholds, trees: TR.Tree, uid=None):
        super().__init__("rfClassifier", thresholds, uid=uid)
        self.trees = trees

    def get_arrays(self):
        # ``c0__``: the one forest, under the prefix it always had
        t = _host_trees(self.trees)
        return {
            "thresholds": self.thresholds,
            "c0__split_feat": t.split_feat,
            "c0__split_bin": t.split_bin,
            "c0__leaf_value": t.leaf_value,
        }

    @classmethod
    def from_params(cls, params, arrays):
        if "c1__split_feat" in arrays:
            raise ValueError(
                "a saved one-vs-rest forest (one forest a class, from "
                "before the K-class learner) cannot be loaded: refit it"
            )
        return cls(arrays["thresholds"], _tree_from_arrays(arrays, "c0__"))

    def _tree_stacks(self):
        return self.trees, False

    def predictions_from_core(self, core):
        return self._probs_to_predictions(np.asarray(core, dtype=np.float64))

    @staticmethod
    def _probs_to_predictions(probs):
        """(pred, prob, raw) from the [N, K] mean leaf distributions ([N, 1]
        at two classes: class 1's share); ties go to the lowest class."""
        probs = np.clip(probs, 0.0, 1.0)
        if probs.shape[1] == 1:  # two classes: the leaf is class 1's share
            probs = np.concatenate([1 - probs, probs], axis=1)
        raw = probs.copy()
        prob = probs / np.maximum(probs.sum(axis=1, keepdims=True), 1e-12)
        return prob.argmax(axis=1).astype(np.float64), prob, raw

    # sweep-eval protocol: a lane's outputs are its forest's
    sweep_mode = "forest"

    def sweep_lane_params(self):
        return 1.0, 0.0

    def predictions_from_sweep(self, preds):
        """``preds``: one lane of the fit program's outputs, [N] at two
        classes, [K, N] at more."""
        return self._probs_to_predictions(
            np.atleast_2d(np.asarray(preds, dtype=np.float64)).T
        )


class ForestRegressionModel(_BinnedModel):
    def __init__(self, thresholds, trees, uid=None):
        super().__init__("rfRegressor", thresholds, uid=uid)
        self.trees = trees

    @classmethod
    def from_params(cls, params, arrays):
        return cls(arrays["thresholds"], _tree_from_arrays(arrays))

    def get_arrays(self):
        t = _host_trees(self.trees)
        return {
            "thresholds": self.thresholds,
            "split_feat": t.split_feat,
            "split_bin": t.split_bin,
            "leaf_value": t.leaf_value,
        }

    def _tree_stacks(self):
        return self.trees, False

    def predictions_from_core(self, core):
        return np.asarray(core, dtype=np.float64)[:, 0], None, None

    sweep_mode = "forest"

    def sweep_lane_params(self):
        return 1.0, 0.0

    @staticmethod
    def predictions_from_sweep(preds):
        return np.asarray(preds, dtype=np.float64), None, None


# ---------------------------------------------------------------------------
# estimators
# ---------------------------------------------------------------------------
class _TreeEstimator(PredictorEstimator):
    #: grid params that are STATIC in the jitted fit (shape-affecting);
    #: points sharing them batch into one vmapped fit
    _STATIC_GRID_KEYS: tuple = ()
    #: the impurity and its stop rule (``trees._grow_tree_impl``). 0:
    #: ``min_info_gain`` (and XGBoost's ``gamma``) are absolute, the XGBoost
    #: families. Spark's families compare the impurity decrease PER ROW:
    #: ``trees.GINI`` over a class label's K class counts,
    #: ``trees.VARIANCE`` of a real target
    _INFO_GAIN_NORM = 0.0

    def __init__(self, operation_name: str, max_depth: int, max_bins: int, uid=None):
        super().__init__(operation_name, uid=uid)
        self.max_depth = max_depth
        self.max_bins = max_bins

    def _binned(self, x: np.ndarray):
        """(thresholds, binned codes, narrow/wide feature groups).

        Cached per (matrix, max_bins) across estimators and threads: every
        family of a candidate sweep bins the SAME training matrix (XGB + 3
        RF depth groups = 4 redundant device bin_data dispatches + host
        quantile passes on the flagship otherwise). The cache keeps a
        strong reference to x, so buffer-address keys cannot alias."""
        key = (
            x.__array_interface__["data"][0] if isinstance(x, np.ndarray)
            else id(x),
            getattr(x, "shape", None), getattr(x, "strides", None),
            int(self.max_bins),
        )
        with _tspans.span("tree/bin_prepare") as sp:
            with _BINNED_LOCK:
                hit = _BINNED_CACHE.get(key)
            entry = hit if hit is not None else self._bin_into_cache(x, key)
            if _tspans.enabled():
                census = _bin_cache_census()
                sp.attrs.update(
                    cache="hit" if hit is not None else "miss", **census
                )
                _BIN_STATS.record_lookup(
                    hit is not None, census["cache_entries"],
                    census["cache_device_bytes"],
                )
        return entry[1], entry[2], entry[3]

    def _bin_into_cache(self, x, key):
        """The cache miss: the matrix to the device, the thresholds and
        the 0/1-column flags, the binning program; returns the new cache
        entry."""
        # through the AOT executable bank: a plain bin_data call would
        # acquire its program on the sweep's critical path
        from ..utils.aot import aot_call

        from ..compiler.dispatch import (
            device_f32, host_layout, prefetch_pending,
        )

        rows, cols, bins = int(x.shape[0]), int(x.shape[1]), int(self.max_bins)
        # device_f32 picks up the async upload the DAG fit prefetched for
        # this matrix, when one is in flight (compiler.dispatch); either
        # uploads the plane in the layout it has
        with _tspans.span(
            "tree/upload",
            bytes=4 * int(x.size) + 4 * cols * (bins - 1),
            prefetched=prefetch_pending(x), layout=host_layout(x),
        ):
            xj = device_f32(x)
        with _tspans.span(
            "tree/thresholds", rows=rows, cols=cols, bins=bins,
            dtype=str(getattr(x, "dtype", "")),
        ) as sp:
            thresholds, binary, why = self._column_stats(x, xj)
            sp.attrs.update(
                {"route": "device"} if why is None
                else {"route": "host", "why": why}
            )
            if _tspans.enabled():
                _BIN_STATS.bump(
                    "thresholdsDevice" if why is None else "thresholdsHost"
                )
        with _tspans.span("tree/bin_dispatch"):
            binned = aot_call(
                "bin_data", _bin_data_jit, (xj, jnp.asarray(thresholds)), {}
            )
        with _tspans.span("tree/feature_groups") as sp:
            fgroups = (
                _feature_bin_groups(x) if binary is None
                else _groups_from_flags(binary)
            )
            narrow = 0 if fgroups is None else int(fgroups[0].shape[0])
            sp.attrs.update(narrow=narrow, wide=cols - narrow)
        entry = (x, thresholds, binned, fgroups)
        with _BINNED_LOCK:
            _BINNED_CACHE[key] = entry
            while len(_BINNED_CACHE) > 4:
                _BINNED_CACHE.pop(next(iter(_BINNED_CACHE)))
        return entry

    def _column_stats(self, x, xj):
        """(thresholds, 0/1-column flags or None, why the host or None):
        what binning needs to know of each column of ``x``, from the
        device's copy ``xj`` (``trees.bin_column_stats``) where the plane is
        large, unsharded and free of NaN, else from the host's
        (``trees.quantile_thresholds``; the flags are then
        ``_feature_bin_groups``' to find). One algorithm run in two places:
        the results are equal, so nothing but the input picks the place."""
        from ..compiler.dispatch import _mesh_active
        from ..utils.aot import aot_call

        rows, cols = x.shape
        if _mesh_active():  # a row-sharded plane would need a distributed sort
            why = "mesh"
        elif rows * cols < _DEVICE_STATS_MIN_VALUES:
            why = "small"
        else:
            stats, binary, any_nan = jax.device_get(aot_call(
                "bin_column_stats", TR.bin_column_stats, (xj,),
                {"max_bins": int(self.max_bins)},
            ))
            if not any_nan:
                return TR.thresholds_from_order_stats(stats, rows), binary, None
            why = "nan"  # np.nanquantile counts each column's own rows
        return TR.quantile_thresholds(x, self.max_bins), None, why

    def _fit_group_masks(self, x, y, masks, group_points):
        """Fit len(masks) × len(group_points) same-static-shape models in
        ONE batched program (fit axis = histogram-kernel grid axis, see
        trees.grow_tree_batched); None → caller falls back to sequential
        fits. Overridden per family. ``masks`` is [M, N] float32."""
        return None

    def fit_arrays_batched(self, x, y, row_mask, points):
        """One mask, many grid points (back-compat validator hook)."""
        return self.fit_arrays_batched_masks(x, y, [row_mask], points)[0]

    def fit_arrays_batched_masks(self, x, y, masks, points):
        """Validator hook: the folds × grid sweep batches points that share
        static shapes into one compiled program per group — the TPU
        replacement for the reference's driver thread pool
        (OpValidator.scala:363-367). A 3-fold × 18-point RF grid becomes 3
        programs (one per max_depth) instead of 54 dispatches.

        Set TPTPU_BATCHED_FITS=0 to force sequential fits."""
        import os

        masks = [np.asarray(m, dtype=np.float32) for m in masks]
        if (
            os.environ.get("TPTPU_BATCHED_FITS") == "0"
            or not self._STATIC_GRID_KEYS
        ):
            return [
                [self.with_params(**p).fit_arrays(x, y, m) for p in points]
                for m in masks
            ]
        groups: dict[tuple, list[int]] = {}
        for i, p in enumerate(points):
            merged = {**self.get_params(), **p}
            key = tuple(merged.get(k) for k in self._STATIC_GRID_KEYS)
            groups.setdefault(key, []).append(i)
        models: list[list] = [[None] * len(points) for _ in masks]
        mask_arr = np.stack(masks)
        # deepest group first: its program is the sweep's long pole on the
        # chip, so putting it at the head of the device queue overlaps its
        # execution with the shallower groups' host phases
        def _depth_of(key_idxs):
            merged = {**self.get_params(), **points[key_idxs[1][0]]}
            return -int(merged.get("max_depth", 0) or 0)

        for _, idxs in sorted(groups.items(), key=_depth_of):
            fitted = self._fit_group_masks(
                x, y, mask_arr, [points[i] for i in idxs]
            )
            if fitted is None:
                fitted = [
                    [
                        self.with_params(**points[i]).fit_arrays(x, y, m)
                        for i in idxs
                    ]
                    for m in masks
                ]
            for mi in range(len(masks)):
                for j, i in enumerate(idxs):
                    models[mi][i] = fitted[mi][j]
        return models

    @staticmethod
    def _tree_slice(stacked_trees, i):
        return jax.tree.map(lambda a: a[i], stacked_trees)

    def sweep_eval_batched(self, models_by_fold, x, y, folds, evaluator):
        """Validator hook: validation metrics for the WHOLE folds × grid
        sweep with one device program per fitted stack. The per-model
        predict loop pays a dispatch + val-matrix upload per model (54 RF
        models in the default sweep); here each stack's
        [K, N] outputs come back in one download and the per-lane
        probability/metric math runs on host exactly as predict_arrays
        would. Returns [n_points][n_folds] metric values, or None when any
        model lacks the sweep protocol (caller falls back)."""
        from ..utils.aot import aot_call

        flat = [m for fold_models in models_by_fold for m in fold_models]
        if not flat or any(
            getattr(m, "_sweep_stack", None) is None
            or not hasattr(m, "predictions_from_sweep")
            for m in flat
        ):
            return None
        try:
            xj = None
            outputs: dict[int, np.ndarray] = {}
            for m in flat:
                stack = m._sweep_stack
                sid = id(stack)
                if sid in outputs:
                    continue
                if stack.get("outputs") is not None:
                    # the fit program already computed every lane's raw
                    # outputs on the training matrix — one tiny download,
                    # no traversal program, no x upload
                    outputs[sid] = await_stack_outputs(stack)
                    continue
                k = stack["k"]
                eta_v = np.ones(k, dtype=np.float32)
                base_v = np.zeros(k, dtype=np.float32)
                for mm in flat:
                    if mm._sweep_stack is stack:
                        e, b = mm.sweep_lane_params()
                        eta_v[mm._sweep_lane] = e
                        base_v[mm._sweep_lane] = b
                mode = m.sweep_mode
                fn = (
                    TR.sweep_boosted_outputs
                    if mode == "boost"
                    else TR.sweep_forest_outputs
                )
                if xj is None:
                    from ..compiler.dispatch import device_f32

                    xj = device_f32(x)
                out = aot_call(
                    f"sweep_{mode}_outputs", fn,
                    (
                        xj, jnp.asarray(stack["thresholds"]),
                        jax.tree.map(jnp.asarray, stack["trees"]),
                        jnp.asarray(eta_v), jnp.asarray(base_v),
                    ),
                    {},
                )
                outputs[sid] = TR.await_outputs(out)  # [K, (V,) N]
            values: list[list[float]] = [
                [] for _ in range(len(models_by_fold[0]))
            ]
            with _tspans.span(
                "selector/evaluate", lanes=len(flat), rows=len(y),
                # what the lanes are scored from: the outputs pulled to
                # the host, one row of them a class where the model has
                # classes
                bytes=sum(int(o.nbytes) for o in outputs.values()),
            ) as sp:
                prob = None
                for fi, (_train_mask, val_mask) in enumerate(folds):
                    val_idx = np.nonzero(val_mask)[0]
                    for gi, m in enumerate(models_by_fold[fi]):
                        # a lane's outputs, the rows last: [N] or [V, N]
                        pred, prob, _ = m.predictions_from_sweep(
                            outputs[id(m._sweep_stack)][m._sweep_lane][
                                ..., val_idx
                            ]
                        )
                        metrics = evaluator.evaluate_arrays(
                            y[val_idx], pred, prob
                        )
                        values[gi].append(evaluator.metric_of(metrics))
                sp.attrs["classes"] = 0 if prob is None else prob.shape[1]
            return values
        except Exception:
            log.warning("batched sweep-eval failed; falling back", exc_info=True)
            return None

    def _batched_group_fit(
        self, x, masks, group_points, run_batched, make_model, normalize=None,
        dispatch_attrs=None, lowp=False, stat_channels=2,
    ):
        """Shared plumbing for the masks × points batched fit: bin once,
        merge (+ normalize) params, stack the float knobs mask-major
        (fit k = mask_index * n_points + point_index), run the family's
        batched trainer, slice the [K, ...] tree pytree back out.

        ``run_batched(binned, m0, row_mask_K, knob, fgroups) -> ([K, ...]
        tree pytree, [K, N] training outputs-or-None, the fit's
        ``HistSlots``)`` where ``knob(name)`` returns the [K] float32 array
        for a param;
        ``make_model(thresholds, sliced_trees, merged_params, mask_index)``;
        ``dispatch_attrs(binned, m0)`` gives the family's own attributes of
        the ``tree/fit_dispatch`` span; ``lowp`` says the trainer hands the
        histogram kernel bf16-exact values and ``stat_channels`` how many
        statistics a node holds (the span's ``hist_tiles``,
        ``stat_channels``, ``stat_channels_built``).
        The training outputs (every lane's raw model output on the full
        training matrix, computed by the fit program itself) ride the stack
        so sweep_eval_batched needs no re-traversal program.
        """
        base = self.with_params(**group_points[0])
        thresholds, binned, fgroups = base._binned(x)
        self._last_feature_groups = fgroups
        norm = normalize or (lambda m: m)
        merged = [norm({**self.get_params(), **p}) for p in group_points]
        n_masks, n_pts = masks.shape[0], len(merged)
        # cross-candidate dedup ledger: every (mask × point) lane of this
        # static group shares ONE compiled program. Tree lanes do NOT pad
        # onto shape buckets (compiler.bucketing): split decisions are
        # discrete, and a reassociated histogram sum under a different
        # lane count can flip a borderline split.
        from ..compiler import stats as cstats

        cstats.stats().record_sweep(lanes=n_masks * n_pts)
        row_mask_k = jnp.asarray(np.repeat(masks, n_pts, axis=0))

        def knob(name):
            # numpy (not jnp): eager dtype-converting transfers compile a
            # device program per process; the batched trainers transfer
            # these once inside their jitted calls
            return np.asarray(
                [float(m[name]) for m in merged] * n_masks, dtype=np.float32
            )

        m0 = merged[0]
        shape_attrs = _hist_shape_attrs(
            binned, fgroups, n_masks * n_pts,
            max(int(m["max_depth"]) for m in merged),
            int(m0["max_bins"]), lowp, stat_channels,
        )
        if _tspans.enabled():
            TR.hist_slot_stats().record_channels(
                stat_channels, shape_attrs["stat_channels_built"]
            )
        # the asynchronous dispatch of boost_chunk / forest_scan
        with _tspans.span(
            "tree/fit_dispatch", lanes=n_masks * n_pts,
            rounds=int(m0.get("num_round", m0.get("num_trees", 1))),
            depth=int(m0["max_depth"]), bins=int(m0["max_bins"]),
            hist_impl=TR._resolved_impl(), **shape_attrs,
            **(dispatch_attrs(binned, m0) if dispatch_attrs else {}),
        ):
            trees, outputs, slots = run_batched(
                binned, m0, row_mask_k, knob, fgroups
            )
        # the stacked trees STAY on device for sweep_eval_batched (one
        # validation program per stack); per-model tree arrays materialize
        # lazily via _LazySlice — eager host pulls download the whole
        # stack and eager device slicing compiles a
        # dynamic_slice/squeeze program per shape. On a multi-device mesh
        # the stack is host-pulled once up front instead: keeping
        # replicated arrays around invites the eager multi-device slicing
        # that aborts the async XLA:CPU runtime.
        leaves = jax.tree.leaves(trees)
        is_dev = bool(leaves) and hasattr(leaves[0], "devices")
        multi_dev = is_dev and len(leaves[0].devices()) > 1
        if multi_dev or not is_dev:
            trees = TR.await_outputs(trees)
        stack = {
            "trees": trees,
            "thresholds": thresholds,
            "k": n_masks * n_pts,
            # [K, N] raw outputs on the training matrix straight from the
            # fit program (device-resident until eval time; ~85 KB at
            # flagship shapes). sweep_eval_batched downloads it instead of
            # dispatching a traversal program + x upload per stack.
            "outputs": outputs,
            # what the fit's histogram builds were sized for: read with
            # the outputs (await_stack_outputs)
            "hist_slots": slots,
        }
        models = [
            [
                make_model(
                    thresholds,
                    _LazySlice(stack, mi * n_pts + j),
                    merged[j],
                    mi,
                )
                for j in range(n_pts)
            ]
            for mi in range(n_masks)
        ]
        for mi in range(n_masks):
            for j in range(n_pts):
                m = models[mi][j]
                m._sweep_stack = stack
                m._sweep_lane = mi * n_pts + j
        return models


class XGBoostClassifier(_TreeEstimator):
    """OpXGBoostClassifier parity (XGBoost defaults: eta 0.3, maxDepth 6,
    lambda 1, numRound 100 in the reference grids)."""

    model_type = "OpXGBoostClassifier"

    def __init__(
        self,
        num_round: int = 100,
        eta: float = 0.3,
        max_depth: int = 6,
        reg_lambda: float = 1.0,
        gamma: float = 0.0,
        min_child_weight: float = 1.0,
        min_info_gain: float = 0.0,
        max_bins: int = 32,
        uid: str | None = None,
    ):
        super().__init__("xgbClassifier", max_depth, max_bins, uid=uid)
        self.num_round = num_round
        self.eta = eta
        self.reg_lambda = reg_lambda
        self.gamma = gamma
        self.min_child_weight = min_child_weight
        self.min_info_gain = min_info_gain

    def get_params(self):
        return {
            "num_round": self.num_round,
            "eta": self.eta,
            "max_depth": self.max_depth,
            "reg_lambda": self.reg_lambda,
            "gamma": self.gamma,
            "min_child_weight": self.min_child_weight,
            "min_info_gain": self.min_info_gain,
            "max_bins": self.max_bins,
        }

    _STATIC_GRID_KEYS = ("num_round", "max_depth", "max_bins")
    #: the boosting objective (``trees.OBJECTIVES``), a static argument of
    #: the fit program
    _OBJECTIVE = "binary:logistic"

    def _normalize_boost(self, merged: dict) -> dict:
        """Map this family's param names onto the boosting knobs (GBT uses
        Spark names: maxIter/stepSize/minInstancesPerNode)."""
        return merged

    def _boosted_model(self, thresholds, trees, m: dict, base: float = 0.0):
        """The fitted model of one lane; ``m``: its normalized params;
        ``trees``: one stack, or the per-class list of a one-vs-rest fit."""
        cls = BoostedMultiModel if isinstance(trees, list) else BoostedBinaryModel
        return cls(thresholds, trees, float(m["eta"]), base)

    def _boost_kwargs(self, m: dict, knob=None) -> dict:
        """``trees.fit_boosted[_batched]``'s arguments from the normalized
        params ``m``; ``knob(name)`` gives a per-lane [K] array instead."""
        knob = knob or (lambda name: float(m[name]))
        return dict(
            num_rounds=int(m["num_round"]), max_depth=int(m["max_depth"]),
            num_bins=int(m["max_bins"]),
            eta=knob("eta"), reg_lambda=knob("reg_lambda"),
            gamma=knob("gamma"), min_child_weight=knob("min_child_weight"),
            min_info_gain=knob("min_info_gain"),
            objective=self._OBJECTIVE, info_gain_norm=self._INFO_GAIN_NORM,
        )

    def _dispatch_attrs(self, binned, m0: dict) -> dict:
        first, rest = TR.boost_tree_weights(self._OBJECTIVE, 2, m0["eta"])
        return dict(
            objective=self._OBJECTIVE, tree_weights=f"{first:g} {rest:g}"
        )

    def fit_arrays(self, x, y, row_mask):
        thresholds, binned, fgroups = self._binned(x)
        present = y[row_mask > 0]
        num_classes = max(int(present.max()) + 1 if len(present) else 2, 2)
        m = self._normalize_boost(self.get_params())
        kwargs = dict(self._boost_kwargs(m), feature_groups=fgroups)
        rm = jnp.asarray(row_mask, dtype=jnp.float32)
        if num_classes == 2:
            trees, _ = TR.fit_boosted(binned, jnp.asarray(y, dtype=jnp.float32), rm, **kwargs)
            return self._boosted_model(thresholds, trees, m)
        per_class = []
        for c in range(num_classes):
            yc = jnp.asarray((y == c).astype(np.float32))
            trees, _ = TR.fit_boosted(binned, yc, rm, **kwargs)
            per_class.append(trees)
        return self._boosted_model(thresholds, per_class, m)

    def _fit_group_masks(self, x, y, masks, group_points):
        present = y[masks.max(axis=0) > 0]
        num_classes = max(int(present.max()) + 1 if len(present) else 2, 2)
        if num_classes != 2:
            return None  # one-vs-rest loops stay sequential
        yj = np.asarray(y, dtype=np.float32)

        def run_batched(binned, m0, row_mask_k, knob, fgroups):
            # the final margin IS each lane's raw output on every row
            return TR.fit_boosted_batched(
                binned, yj, row_mask_k, **self._boost_kwargs(m0, knob),
                feature_groups=fgroups, return_slots=True,
            )

        return self._batched_group_fit(
            x, masks, group_points, run_batched,
            lambda th, tr, m, mi: self._boosted_model(th, tr, m),
            normalize=self._normalize_boost,
            dispatch_attrs=self._dispatch_attrs,
        )


class XGBoostRegressor(_TreeEstimator):
    model_type = "OpXGBoostRegressor"

    def __init__(
        self,
        num_round: int = 100,
        eta: float = 0.3,
        max_depth: int = 6,
        reg_lambda: float = 1.0,
        gamma: float = 0.0,
        min_child_weight: float = 1.0,
        min_info_gain: float = 0.0,
        max_bins: int = 32,
        uid: str | None = None,
    ):
        super().__init__("xgbRegressor", max_depth, max_bins, uid=uid)
        self.num_round = num_round
        self.eta = eta
        self.reg_lambda = reg_lambda
        self.gamma = gamma
        self.min_child_weight = min_child_weight
        self.min_info_gain = min_info_gain

    get_params = XGBoostClassifier.get_params
    _STATIC_GRID_KEYS = ("num_round", "max_depth", "max_bins")
    _OBJECTIVE = "reg:squarederror"
    _normalize_boost = XGBoostClassifier._normalize_boost
    _boost_kwargs = XGBoostClassifier._boost_kwargs
    _dispatch_attrs = XGBoostClassifier._dispatch_attrs

    def _boosted_model(self, thresholds, trees, m: dict, base: float = 0.0):
        return BoostedRegressionModel(thresholds, trees, float(m["eta"]), base)

    def _base_scores(self, y, masks) -> np.ndarray:
        """[M] float64: each mask's starting margin, the mean target over
        its rows."""
        sums = masks @ y.astype(np.float64)
        cnts = masks.sum(axis=1)
        return np.where(cnts > 0, sums / np.maximum(cnts, 1), 0.0)

    def _fit_group_masks(self, x, y, masks, group_points):
        yj = np.asarray(y, dtype=np.float32)
        base_scores = self._base_scores(y, masks)
        n_pts = len(group_points)

        def run_batched(binned, m0, row_mask_k, knob, fgroups):
            return TR.fit_boosted_batched(
                binned, yj, row_mask_k, **self._boost_kwargs(m0, knob),
                base_score=np.repeat(base_scores, n_pts).astype(np.float32),
                feature_groups=fgroups, return_slots=True,
            )

        return self._batched_group_fit(
            x, masks, group_points, run_batched,
            lambda th, tr, m, mi: self._boosted_model(
                th, tr, m, float(base_scores[mi])
            ),
            normalize=self._normalize_boost,
            dispatch_attrs=self._dispatch_attrs,
        )

    def fit_arrays(self, x, y, row_mask):
        thresholds, binned, fgroups = self._binned(x)
        mask = np.asarray(row_mask, dtype=np.float32)
        base = float(self._base_scores(np.asarray(y), mask[None, :])[0])
        m = self._normalize_boost(self.get_params())
        kwargs = self._boost_kwargs(m)
        trees, _ = TR.fit_boosted(
            binned,
            jnp.asarray(y, dtype=jnp.float32),
            jnp.asarray(mask),
            **kwargs, base_score=base, feature_groups=fgroups,
        )
        return self._boosted_model(thresholds, trees, m, base)


class GBTClassifier(XGBoostClassifier):
    """OpGBTClassifier: Spark ML's ``GBTClassifier`` (defaults maxIter 20,
    stepSize 0.1, maxDepth 5; more than two classes go one-vs-rest, each a
    binary fit on its indicator). ``GradientBoostedTrees.
    boost`` on the labels 2y - 1 under ``LogLoss`` (the objective
    ``spark:logloss`` of ``trees.OBJECTIVES``): every tree a variance-
    impurity regression tree over all columns and all rows; a child under
    ``min_instances_per_node`` ROWS makes a split invalid; a node splits
    where its best valid split's variance decrease per row reaches
    ``min_info_gain`` and is positive; the first tree weighs 1, the rest
    ``step_size``."""

    model_type = "OpGBTClassifier"

    def __init__(
        self,
        max_iter: int = 20,
        step_size: float = 0.1,
        max_depth: int = 5,
        min_instances_per_node: int = 1,
        min_info_gain: float = 0.0,
        max_bins: int = 32,
        uid: str | None = None,
    ):
        super().__init__(
            num_round=max_iter,
            eta=step_size,
            max_depth=max_depth,
            reg_lambda=0.0,
            gamma=0.0,
            min_child_weight=float(min_instances_per_node),
            max_bins=max_bins,
            uid=uid,
        )
        self.max_iter = max_iter
        self.step_size = step_size
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain

    def get_params(self):
        return {
            "max_iter": self.max_iter,
            "step_size": self.step_size,
            "max_depth": self.max_depth,
            "min_instances_per_node": self.min_instances_per_node,
            "min_info_gain": self.min_info_gain,
            "max_bins": self.max_bins,
        }

    _STATIC_GRID_KEYS = ("max_iter", "max_depth", "max_bins")
    _INFO_GAIN_NORM = TR.VARIANCE
    _OBJECTIVE = "spark:logloss"

    def _normalize_boost(self, merged: dict) -> dict:
        return {
            "num_round": merged["max_iter"],
            "eta": merged["step_size"],
            "reg_lambda": 0.0,
            "gamma": 0.0,
            # h = 1 under the spark:* objectives: a child's weight IS its
            # row count
            "min_child_weight": float(merged["min_instances_per_node"]),
            "min_info_gain": merged["min_info_gain"],
            "max_depth": merged["max_depth"],
            "max_bins": merged["max_bins"],
        }

    def _boosted_model(self, thresholds, trees, m: dict, base: float = 0.0):
        cls = GBTMultiModel if isinstance(trees, list) else GBTClassificationModel
        return cls(
            thresholds, trees,
            TR.boost_tree_weights(self._OBJECTIVE, m["num_round"], m["eta"]),
        )


class GBTRegressor(XGBoostRegressor):
    """OpGBTRegressor: Spark ML's ``GBTRegressor`` under ``SquaredError``
    (the objective ``spark:squarederror``): the first tree fitted to the
    labels at weight 1 from a zero margin, each later one to 2 (y - F) at
    weight ``step_size``; rows, gain and stop rule as ``GBTClassifier``."""

    model_type = "OpGBTRegressor"
    _STATIC_GRID_KEYS = ("max_iter", "max_depth", "max_bins")
    _INFO_GAIN_NORM = TR.VARIANCE
    _OBJECTIVE = "spark:squarederror"
    _normalize_boost = GBTClassifier._normalize_boost

    def __init__(
        self,
        max_iter: int = 20,
        step_size: float = 0.1,
        max_depth: int = 5,
        min_instances_per_node: int = 1,
        min_info_gain: float = 0.0,
        max_bins: int = 32,
        uid: str | None = None,
    ):
        super().__init__(
            num_round=max_iter,
            eta=step_size,
            max_depth=max_depth,
            reg_lambda=0.0,
            gamma=0.0,
            min_child_weight=float(min_instances_per_node),
            max_bins=max_bins,
            uid=uid,
        )
        self.max_iter = max_iter
        self.step_size = step_size
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain

    get_params = GBTClassifier.get_params

    def _base_scores(self, y, masks) -> np.ndarray:
        return np.zeros(len(masks))  # Spark boosts from F = 0

    def _boosted_model(self, thresholds, trees, m: dict, base: float = 0.0):
        return GBTRegressionModel(
            thresholds, trees,
            TR.boost_tree_weights(self._OBJECTIVE, m["num_round"], m["eta"]),
        )


FEATURE_SUBSET_STRATEGIES = ("auto", "all", "sqrt", "onethird", "log2")


def resolve_feature_subset(
    strategy: str, num_features: int, num_trees: int, classification: bool
) -> int:
    """Columns each tree NODE may split on, as Spark's
    ``DecisionTreeMetadata.buildMetadata`` resolves ``featureSubsetStrategy``:
    ``auto`` is ``all`` for one tree, else ``sqrt`` for classification and
    ``onethird`` for regression; ``sqrt`` ⌈√F⌉, ``onethird`` ⌈F/3⌉, ``log2``
    max(1, ⌈log2 F⌉), ``all`` F."""
    s = str(strategy).lower()
    if s not in FEATURE_SUBSET_STRATEGIES:
        raise ValueError(
            f"feature_subset_strategy {strategy!r}: one of "
            f"{FEATURE_SUBSET_STRATEGIES}"
        )
    f = max(int(num_features), 1)
    if s == "auto":
        s = "all" if int(num_trees) == 1 else (
            "sqrt" if classification else "onethird"
        )
    if s == "sqrt":
        return min(f, math.ceil(math.sqrt(f)))
    if s == "onethird":
        return min(f, math.ceil(f / 3.0))
    if s == "log2":
        return min(f, max(1, math.ceil(math.log2(f))))
    return f


class RandomForestClassifier(_TreeEstimator):
    """OpRandomForestClassifier parity (Spark defaults: numTrees 20, maxDepth
    5, featureSubsetStrategy 'auto' = √F columns a node for a forest, Gini
    impurity, minInfoGain compared with the impurity decrease per row, a
    Poisson bootstrap when there is more than one tree)."""

    model_type = "OpRandomForestClassifier"
    _INFO_GAIN_NORM = TR.GINI
    _CLASSIFICATION = True

    def __init__(
        self,
        num_trees: int = 20,
        max_depth: int = 5,
        min_instances_per_node: int = 1,
        min_info_gain: float = 0.0,
        subsampling_rate: float = 1.0,
        max_bins: int = 32,
        seed: int = 42,
        feature_subset_strategy: str = "auto",
        uid: str | None = None,
    ):
        super().__init__("rfClassifier", max_depth, max_bins, uid=uid)
        self.num_trees = num_trees
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain
        self.subsampling_rate = subsampling_rate
        self.seed = seed
        self.feature_subset_strategy = feature_subset_strategy

    def get_params(self):
        return {
            "num_trees": self.num_trees,
            "max_depth": self.max_depth,
            "min_instances_per_node": self.min_instances_per_node,
            "min_info_gain": self.min_info_gain,
            "subsampling_rate": self.subsampling_rate,
            "max_bins": self.max_bins,
            "seed": self.seed,
            "feature_subset_strategy": self.feature_subset_strategy,
        }

    # max_depth STAYS static by default: collapsing the depth groups into
    # one max-depth program via max_depth_v makes every lane pay deep-level
    # eval in one fat program (not re-measured on a local chip; see
    # ROADMAP D3). run_batched still
    # wires per-lane caps for custom groupings that mix depths.
    _STATIC_GRID_KEYS = (
        "num_trees", "max_depth", "max_bins", "seed",
        "feature_subset_strategy",
    )

    def _forest_statics(self, m: dict, num_features: int, rates) -> dict:
        """What of Spark's forest a program is compiled for, from the
        merged params ``m`` of one static group: the columns a node may
        split on, whether rows are bootstrapped (Poisson counts; not for
        one tree on every row), and the impurity's stop-rule norm."""
        trees = int(m.get("num_trees", 1))
        return dict(
            feature_subset=resolve_feature_subset(
                m.get("feature_subset_strategy", "auto"), num_features,
                trees, self._CLASSIFICATION,
            ),
            bootstrap=trees > 1 or bool(np.any(np.asarray(rates) != 1.0)),
            info_gain_norm=self._INFO_GAIN_NORM,
        )

    @staticmethod
    def _num_classes(y, row_mask) -> int:
        """K of a fit: the label's largest class among the rows it may
        see, plus one (at least two)."""
        present = y[row_mask > 0]
        return max(int(present.max()) + 1 if len(present) else 2, 2)

    def fit_arrays(self, x, y, row_mask):
        thresholds, binned, fgroups = self._binned(x)
        trees = TR.fit_forest(
            binned, jnp.asarray(y, dtype=jnp.float32),
            jnp.asarray(row_mask, dtype=jnp.float32),
            num_trees=int(self.num_trees),
            max_depth=int(self.max_depth),
            num_bins=int(self.max_bins),
            subsample_rate=float(self.subsampling_rate),
            min_instances=float(self.min_instances_per_node),
            min_info_gain=float(self.min_info_gain),
            seed=int(self.seed),
            lowp=True,  # w and the class indicators are bf16-exact
            feature_groups=fgroups,
            num_classes=self._num_classes(y, row_mask),
            # the forest's params, whatever a subclass exposes of them
            **self._forest_statics(
                RandomForestClassifier.get_params(self), x.shape[1],
                self.subsampling_rate,
            ),
        )
        return ForestClassifierModel(thresholds, trees)

    def _dispatch_attrs(self, binned, m0: dict, classes: int = 0) -> dict:
        st = self._forest_statics(
            m0, int(binned.shape[1]), m0.get("subsampling_rate", 1.0)
        )
        attrs = dict(
            trees=int(m0.get("num_trees", 1)),
            feature_subset=str(m0.get("feature_subset_strategy", "auto")),
            n_sub=st["feature_subset"], bootstrap=st["bootstrap"],
        )
        if classes:
            attrs["classes"] = classes
        return attrs

    def _fit_group_masks(self, x, y, masks, group_points):
        # ONE forest a (mask, point) whatever K: the class count is the
        # fit's statistic channels (K = 2: w*y and w, as ever)
        num_classes = self._num_classes(y, masks.max(axis=0))
        yj = np.asarray(y, dtype=np.float32)

        def run_batched(binned, m0, row_mask_k, knob, fgroups):
            # depth rides the lane axis: ONE program at the grid's max
            # depth serves every depth point (program acquisition, not
            # execution, dominates the flagship sweep)
            depth_arr = np.asarray(knob("max_depth"))
            uniform = bool((depth_arr == depth_arr[0]).all())
            rates = knob("subsampling_rate")
            return TR.fit_forest_batched(
                binned, yj, row_mask_k,
                num_trees=int(m0["num_trees"]),
                max_depth=int(depth_arr.max()),
                num_bins=int(m0["max_bins"]),
                subsample_rate=rates,
                **self._forest_statics(m0, binned.shape[1], rates),
                min_instances=knob("min_instances_per_node"),
                min_info_gain=knob("min_info_gain"),
                seed=int(m0["seed"]),
                lowp=True,  # w and the class indicators are bf16-exact
                feature_groups=fgroups,
                num_classes=num_classes,
                max_depth_v=(
                    None if uniform
                    else depth_arr.astype(np.int32)
                ),
                return_outputs=True, return_slots=True,
            )

        return self._batched_group_fit(
            x, masks, group_points, run_batched,
            lambda th, tr, m, mi: ForestClassifierModel(th, tr),
            dispatch_attrs=lambda binned, m0: self._dispatch_attrs(
                binned, m0, classes=num_classes
            ),
            lowp=True, stat_channels=num_classes,
        )


class RandomForestRegressor(_TreeEstimator):
    model_type = "OpRandomForestRegressor"
    _INFO_GAIN_NORM = TR.VARIANCE
    _CLASSIFICATION = False

    def __init__(
        self,
        num_trees: int = 20,
        max_depth: int = 5,
        min_instances_per_node: int = 1,
        min_info_gain: float = 0.0,
        subsampling_rate: float = 1.0,
        max_bins: int = 32,
        seed: int = 42,
        feature_subset_strategy: str = "auto",
        uid: str | None = None,
    ):
        super().__init__("rfRegressor", max_depth, max_bins, uid=uid)
        self.num_trees = num_trees
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain
        self.subsampling_rate = subsampling_rate
        self.seed = seed
        self.feature_subset_strategy = feature_subset_strategy

    get_params = RandomForestClassifier.get_params
    # max_depth stays static: see RandomForestClassifier
    _STATIC_GRID_KEYS = RandomForestClassifier._STATIC_GRID_KEYS
    _forest_statics = RandomForestClassifier._forest_statics
    _dispatch_attrs = RandomForestClassifier._dispatch_attrs

    def fit_arrays(self, x, y, row_mask):
        thresholds, binned, fgroups = self._binned(x)
        trees = TR.fit_forest(
            binned,
            jnp.asarray(y, dtype=jnp.float32),
            jnp.asarray(row_mask, dtype=jnp.float32),
            num_trees=int(self.num_trees),
            max_depth=int(self.max_depth),
            num_bins=int(self.max_bins),
            subsample_rate=float(self.subsampling_rate),
            min_instances=float(self.min_instances_per_node),
            min_info_gain=float(self.min_info_gain),
            seed=int(self.seed),
            feature_groups=fgroups,
            **self._forest_statics(
                RandomForestClassifier.get_params(self), x.shape[1],
                self.subsampling_rate,
            ),
        )
        return ForestRegressionModel(thresholds, trees)

    def _fit_group_masks(self, x, y, masks, group_points):
        yj = np.asarray(y, dtype=np.float32)

        def run_batched(binned, m0, row_mask_k, knob, fgroups):
            depth_arr = np.asarray(knob("max_depth"))
            uniform = bool((depth_arr == depth_arr[0]).all())
            rates = knob("subsampling_rate")
            return TR.fit_forest_batched(
                binned, yj, row_mask_k,
                num_trees=int(m0["num_trees"]),
                max_depth=int(depth_arr.max()),
                num_bins=int(m0["max_bins"]),
                subsample_rate=rates,
                **self._forest_statics(m0, binned.shape[1], rates),
                min_instances=knob("min_instances_per_node"),
                min_info_gain=knob("min_info_gain"),
                seed=int(m0["seed"]),
                feature_groups=fgroups,
                max_depth_v=(
                    None if uniform
                    else depth_arr.astype(np.int32)
                ),
                return_outputs=True, return_slots=True,
            )

        return self._batched_group_fit(
            x, masks, group_points, run_batched,
            lambda th, tr, m, mi: ForestRegressionModel(th, tr),
            dispatch_attrs=self._dispatch_attrs,
        )


class DecisionTreeClassifier(RandomForestClassifier):
    """Single unbagged tree (OpDecisionTreeClassifier parity): the forest
    of ONE tree, which by Spark's rules searches every column at every
    node (``auto`` is ``all``) and draws no bootstrap."""

    model_type = "OpDecisionTreeClassifier"

    def _fit_group_masks(self, x, y, masks, group_points):
        # sequential fits: the forest's batched path reads forest params
        # (num_trees, seed) that a decision tree does not expose
        return None

    def __init__(self, max_depth: int = 5, min_instances_per_node: int = 1,
                 min_info_gain: float = 0.0, max_bins: int = 32, uid=None):
        super().__init__(
            num_trees=1, max_depth=max_depth,
            min_instances_per_node=min_instances_per_node,
            min_info_gain=min_info_gain, max_bins=max_bins, uid=uid,
        )

    def get_params(self):
        # a single tree has no forest knobs (num_trees/subsampling/seed);
        # params must mirror __init__ so the persistence round trip holds
        return {
            "max_depth": self.max_depth,
            "min_instances_per_node": self.min_instances_per_node,
            "min_info_gain": self.min_info_gain,
            "max_bins": self.max_bins,
        }


class DecisionTreeRegressor(RandomForestRegressor):
    model_type = "OpDecisionTreeRegressor"

    def _fit_group_masks(self, x, y, masks, group_points):
        return None  # see DecisionTreeClassifier

    def __init__(self, max_depth: int = 5, min_instances_per_node: int = 1,
                 min_info_gain: float = 0.0, max_bins: int = 32, uid=None):
        super().__init__(
            num_trees=1, max_depth=max_depth,
            min_instances_per_node=min_instances_per_node,
            min_info_gain=min_info_gain, max_bins=max_bins, uid=uid,
        )

    get_params = DecisionTreeClassifier.get_params


# --------------------------------------------------------------------------
# compiled-program contract audit (analysis/program.py, TPJ0xx)
# --------------------------------------------------------------------------
def _trace_tree_stack(*lead: int):
    """Abstract Tree stack with the given leading axes (depth 2)."""
    import jax

    return TR.Tree(
        split_feat=jax.ShapeDtypeStruct((*lead, 2, 4), "int32"),
        split_bin=jax.ShapeDtypeStruct((*lead, 2, 4), "int32"),
        leaf_value=jax.ShapeDtypeStruct((*lead, 4), "float32"),
    )


def program_trace_specs():
    """Representative trace shapes for the banked serving/sweep tree
    programs. Serving programs bucket the BATCH axis (the scoring
    closure's pow2 row buckets); sweep programs bucket the LANE axis."""
    import jax

    f32, i32 = "float32", "int32"

    def _x(n: int):
        return jax.ShapeDtypeStruct((n, 3), f32)

    _thr = jax.ShapeDtypeStruct((3, 3), f32)
    _scalar = jax.ShapeDtypeStruct((), f32)

    def _predict_boosted(n: int):
        return (
            (_x(n), _thr, _trace_tree_stack(2), _scalar, _scalar), {}
        )

    def _predict_forest(n: int):
        return ((_x(n), _thr, _trace_tree_stack(2)), {})

    def _sweep(k: int):
        return (
            (
                _x(8), _thr, _trace_tree_stack(k, 2),
                jax.ShapeDtypeStruct((k,), f32),
                jax.ShapeDtypeStruct((k,), f32),
            ),
            {},
        )

    return [
        dict(
            name="bin_data",
            fn=_bin_data_jit,
            build=lambda n: ((_x(n), _thr), {}),
            buckets=(8, 16), scoring=True,
        ),
        dict(
            name="stack_lane",
            fn=_stack_lane,
            build=lambda k: (
                (
                    _trace_tree_stack(k, 2),
                    jax.ShapeDtypeStruct((), i32),
                ),
                {},
            ),
            buckets=(4, 8), bucket_axis="lanes", scoring=True,
        ),
        dict(
            name="predict_boosted",
            fn=TR.predict_boosted_raw,
            build=_predict_boosted,
            buckets=(8, 16), scoring=True,
        ),
        dict(
            name="predict_forest",
            fn=TR.predict_forest_raw,
            build=_predict_forest,
            buckets=(8, 16), scoring=True,
        ),
        dict(
            name="sweep_boost_outputs",
            fn=TR.sweep_boosted_outputs,
            build=_sweep,
            buckets=(4, 8), bucket_axis="lanes",
        ),
        dict(
            name="sweep_forest_outputs",
            fn=TR.sweep_forest_outputs,
            build=_sweep,
            buckets=(4, 8), bucket_axis="lanes",
        ),
    ]
