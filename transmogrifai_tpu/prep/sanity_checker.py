"""SanityChecker — automated feature validation / leakage detection.

Reference: core/.../stages/impl/preparators/SanityChecker.scala:58-581 and
DerivedFeatureFilterUtils.scala. BinaryEstimator(label RealNN, features
OPVector) -> OPVector with bad columns removed.

Checks (thresholds mirrored from SanityChecker.scala:561-581):
  * variance < MinVariance (1e-5)                        -> drop column
  * |corr(feature, label)| > MaxCorrelation (0.95)        -> drop (leakage)
  * corr(feature, feature') > MaxFeatureCorr (0.99)       -> drop the later
  * Cramér's V (categorical group vs label) > MaxCramersV (0.95)
                                                          -> drop the group
  * association-rule max confidence > MaxRuleConfidence with support >=
    MinRequiredRuleSupport (both 1.0 = off by default)    -> drop the group
RemoveFeatureGroup (default true): a label-leakage drop removes the whole
pivot group the column belongs to (null indicator included).

TPU mapping (SURVEY.md §7 step 4): all statistics are dense reductions —
correlation is a centered XᵀX matmul over [X | y] and every Cramér's V table
is a one-hot matmul — computed in utils/stats.py (jitted).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from ..dataset import Dataset
from ..stages.base import Estimator
from ..stages.metadata import VectorMetadata
from ..telemetry import spans as _tspans
from ..types import OPVector, RealNN
from ..types.columns import Column, NumericColumn, VectorColumn
from ..utils import stats as S
from .derived_filter import FeatureRemovalModel

# SanityChecker.scala:561-581 defaults
CHECK_SAMPLE = 1.0
SAMPLE_LOWER_LIMIT = 1_000
SAMPLE_UPPER_LIMIT = 1_000_000
PROTECT_TEXT_SHARED_HASH = False  # SanityChecker.ProtectTextSharedHash
#: parent types whose shared-hash columns protect_text_shared_hash shields
#: (DerivedFeatureFilterUtils.isTextSharedHash)
_TEXT_HASH_PARENT_TYPES = frozenset(
    {"Text", "TextArea", "TextMap", "TextAreaMap"}
)
MAX_CORRELATION = 0.95
MAX_FEATURE_CORR = 0.99
MIN_CORRELATION = 0.0
MIN_VARIANCE = 1e-5
MAX_CRAMERS_V = 0.95
MAX_RULE_CONFIDENCE = 1.0
MIN_REQUIRED_RULE_SUPPORT = 1.0


@dataclasses.dataclass
class ColumnReport:
    name: str
    parent: str | None
    mean: float
    variance: float
    corr_label: float
    cramers_v: float | None
    dropped: bool
    reasons: list[str]


class SanityChecker(Estimator):
    """Estimator[(RealNN label, OPVector features)] -> OPVector."""

    input_types = (RealNN, OPVector)
    output_type = OPVector
    label_inputs = (0,)  # label-aware by design: correlation screening

    def __init__(
        self,
        max_correlation: float = MAX_CORRELATION,
        max_feature_corr: float = MAX_FEATURE_CORR,
        min_correlation: float = MIN_CORRELATION,
        min_variance: float = MIN_VARIANCE,
        max_cramers_v: float = MAX_CRAMERS_V,
        max_rule_confidence: float = MAX_RULE_CONFIDENCE,
        min_required_rule_support: float = MIN_REQUIRED_RULE_SUPPORT,
        remove_bad_features: bool = False,
        remove_feature_group: bool = True,
        protect_text_shared_hash: bool = PROTECT_TEXT_SHARED_HASH,
        correlation_type: str = "pearson",
        correlation_exclusion: str = "NoExclusion",  # or "HashedText"
        check_sample: float = CHECK_SAMPLE,
        sample_lower_limit: int = SAMPLE_LOWER_LIMIT,
        sample_upper_limit: int = SAMPLE_UPPER_LIMIT,
        sample_seed: int = 42,
        uid: str | None = None,
    ):
        super().__init__("sanityCheck", uid=uid)
        self.max_correlation = max_correlation
        self.max_feature_corr = max_feature_corr
        self.min_correlation = min_correlation
        self.min_variance = min_variance
        self.max_cramers_v = max_cramers_v
        self.max_rule_confidence = max_rule_confidence
        self.min_required_rule_support = min_required_rule_support
        self.remove_bad_features = remove_bad_features
        self.remove_feature_group = remove_feature_group
        self.protect_text_shared_hash = protect_text_shared_hash
        self.correlation_type = correlation_type
        self.correlation_exclusion = correlation_exclusion
        self.check_sample = check_sample
        self.sample_lower_limit = sample_lower_limit
        self.sample_upper_limit = sample_upper_limit
        self.sample_seed = sample_seed

    def _sample_fraction(self, total: int) -> float:
        """SanityChecker.fraction (SanityChecker.scala:356-361): clamp the
        requested check_sample fraction so the checked row count lands in
        [sample_lower_limit, sample_upper_limit]."""
        min_fraction = min(1.0, self.sample_lower_limit / max(total, 1))
        max_fraction = max(0.0, self.sample_upper_limit / max(total, 1))
        return max(min(self.check_sample, max_fraction), min_fraction)

    def get_params(self) -> dict[str, Any]:
        return {
            "max_correlation": self.max_correlation,
            "max_feature_corr": self.max_feature_corr,
            "min_correlation": self.min_correlation,
            "min_variance": self.min_variance,
            "max_cramers_v": self.max_cramers_v,
            "max_rule_confidence": self.max_rule_confidence,
            "min_required_rule_support": self.min_required_rule_support,
            "remove_bad_features": self.remove_bad_features,
            "remove_feature_group": self.remove_feature_group,
            "protect_text_shared_hash": self.protect_text_shared_hash,
            "correlation_type": self.correlation_type,
            "correlation_exclusion": self.correlation_exclusion,
            "check_sample": self.check_sample,
            "sample_lower_limit": self.sample_lower_limit,
            "sample_upper_limit": self.sample_upper_limit,
            "sample_seed": self.sample_seed,
        }

    # ------------------------------------------------------------------ fit
    def fit_model(self, dataset: Dataset) -> FeatureRemovalModel:
        """Five spans under the workflow's ``train/fit`` of this stage, one
        for each part of the work: ``sanity/matrix``, ``sanity/stats``,
        ``sanity/categorical``, ``sanity/rules``, ``sanity/report``."""
        label_name, vector_name = self.input_names
        label_col = dataset[label_name]
        vec_col = dataset[vector_name]
        assert isinstance(label_col, NumericColumn) and isinstance(vec_col, VectorColumn)

        with _tspans.span("sanity/matrix") as sp:
            x = np.asarray(vec_col.values, dtype=np.float64)
            y = label_col.values.astype(np.float64)
            n_total = x.shape[0]
            frac = self._sample_fraction(n_total)
            if frac < 1.0:
                # stats on a seeded row sample (SanityChecker.scala:356-361,
                # 562-564): the checker's cost is bounded by
                # sample_upper_limit rows no matter the dataset size
                rng = np.random.default_rng(self.sample_seed)
                take = rng.choice(
                    n_total, size=max(1, round(frac * n_total)), replace=False
                )
                take.sort()
                x, y = x[take], y[take]
            n, d = x.shape
            sp.attrs.update(rows_in=n_total, rows=n, cols=d, bytes=x.nbytes)
        meta = vec_col.metadata or VectorMetadata(vector_name, ())
        names = (
            meta.column_names() if meta.size == d else [f"col_{j}" for j in range(d)]
        )

        spearman = self.correlation_type == "spearman"
        with _tspans.span(
            "sanity/stats", kind="spearman" if spearman else "pearson"
        ):
            col_stats = S.column_stats(x)
            if spearman:
                corr = S.spearman_correlation_matrix(x, y)
            else:
                corr = S.correlation_matrix(x, y)
            corr_label = corr[:d, d].copy()
            corr_features = corr[:d, :d].copy()

            # CorrelationExclusion.HashedText (SanityChecker.scala:428):
            # text-shared-hash columns sit out the correlation checks
            # entirely
            if self.correlation_exclusion == "HashedText" and meta.size == d:
                excluded = np.array(
                    [
                        c.parent_type in _TEXT_HASH_PARENT_TYPES
                        and c.grouping is None
                        and c.indicator_value is None
                        for c in meta.columns
                    ],
                    dtype=bool,
                )
                corr_label[excluded] = np.nan
                corr_features[excluded, :] = 0.0
                corr_features[:, excluded] = 0.0

        # categorical groups: Cramér's V and association-rule confidences,
        # with a label one-hot (binary or small multiclass). A CONTINUOUS
        # label gets no Cramér's V / association-rule treatment at all
        # (SanityChecker.scala categoricalLabel auto-detection: the label
        # counts as categorical only when its distinct-value count is small
        # relative to the row count; BadFeatureZooTest :264/:628 pin the
        # skip).
        group_v: dict[tuple, float] = {}
        group_cols: dict[tuple, list[int]] = {}
        group_rules: dict[tuple, tuple[np.ndarray, np.ndarray]] = {}
        with _tspans.span("sanity/categorical") as sp:
            classes = np.unique(y)
            label_is_categorical = len(classes) <= min(
                100, max(2, int(0.1 * len(y)))
            )
            if meta.size == d and label_is_categorical:
                # [rows, classes]: only for a label of few classes (a
                # continuous label has about as many classes as rows)
                label_onehot = (y[:, None] == classes[None, :]).astype(
                    np.float64)
                for key, idxs in meta.index_of_group().items():
                    cats = [
                        i for i in idxs
                        if meta.columns[i].indicator_value is not None
                    ]
                    if len(cats) < 1:
                        continue
                    contingency = S.contingency_table(x[:, cats], label_onehot)
                    group_v[key] = S.cramers_v(contingency)
                    group_cols[key] = cats
                    group_rules[key] = S.association_rule_confidence(
                        contingency
                    )
            sp.attrs.update(
                groups=len(group_cols),
                cols=sum(len(c) for c in group_cols.values()),
            )

        with _tspans.span("sanity/rules"):
            drop_reasons = self._drop_reasons(
                meta, d, col_stats, corr_label, corr_features,
                group_v, group_cols, group_rules,
            )
            indices_to_keep = [j for j in range(d) if j not in drop_reasons]

        with _tspans.span("sanity/report"):
            reports = [
                ColumnReport(
                    name=names[j],
                    parent=(
                        meta.columns[j].parent_names[0]
                        if meta.size == d and meta.columns[j].parent_names
                        else None
                    ),
                    mean=float(col_stats.mean[j]),
                    variance=float(col_stats.variance[j]),
                    corr_label=float(corr_label[j]),
                    cramers_v=(
                        group_v.get(meta.columns[j].grouped_key())
                        if meta.size == d
                        else None
                    ),
                    dropped=j in drop_reasons,
                    reasons=drop_reasons.get(j, []),
                )
                for j in range(d)
            ]
            self.metadata["sanityCheckerSummary"] = {
                "numRows": n,
                "numColumns": d,
                "numDropped": len(drop_reasons),
                "columns": [dataclasses.asdict(r) for r in reports],
                "correlationType": self.correlation_type,
            }
            new_meta = meta.select(indices_to_keep) if meta.size == d else None
            return FeatureRemovalModel(
                indices_to_keep=indices_to_keep,
                remove_bad_features=self.remove_bad_features,
                new_metadata=new_meta,
                operation_name="sanityCheck",
            )

    def _drop_reasons(
        self, meta, d, col_stats, corr_label, corr_features,
        group_v, group_cols, group_rules,
    ) -> dict[int, list[str]]:
        """{column: the reasons it is dropped}, by the rules in order."""
        drop_reasons: dict[int, list[str]] = {}

        def drop(j: int, reason: str) -> None:
            drop_reasons.setdefault(j, []).append(reason)

        # 1. low variance
        for j in np.nonzero(col_stats.variance < self.min_variance)[0]:
            drop(int(j), f"variance<{self.min_variance}")

        # 2. label-correlation leakage (+ too-low correlation if configured)
        for j in range(d):
            c = abs(corr_label[j])
            if c > self.max_correlation:
                drop(j, f"|corrLabel|={c:.4f}>{self.max_correlation}")
            elif c < self.min_correlation:
                drop(j, f"|corrLabel|={c:.4f}<{self.min_correlation}")

        # 3. feature-feature correlation: drop the later column of each pair
        hi = np.argwhere(np.triu(np.abs(corr_features), k=1) > self.max_feature_corr)
        for _, j in hi:
            drop(int(j), f"featureCorr>{self.max_feature_corr}")

        # 4. categorical groups: Cramér's V + association rules
        for key, v in group_v.items():
            cats = group_cols[key]
            if v > self.max_cramers_v:
                for i in cats:
                    drop(i, f"cramersV={v:.4f}>{self.max_cramers_v}")
            conf, support = group_rules[key]
            if self.max_rule_confidence < 1.0:
                for ci, i in enumerate(cats):
                    if (
                        conf[ci] > self.max_rule_confidence
                        and support[ci] >= self.min_required_rule_support
                    ):
                        drop(i, f"ruleConfidence={conf[ci]:.4f}")

        # 5. group-wise removal at PARENT-FEATURE granularity
        # (DerivedFeatureFilterUtils.reasonsToRemove parentExclusionReasons):
        # a leaky categorical group takes down every column of the same
        # parent feature — incl. its hashed-text block and null indicator —
        # unless the column is a text shared hash and protection is on
        # (isTextSharedHash: Text-family parent, no grouping, no indicator).
        if self.remove_feature_group and meta.size == d:

            def parent_key(c):
                base = "_".join(c.parent_names)
                if c.grouping and c.grouping != base:
                    return f"{base}_{c.grouping}"  # parentNamesWithMapKeys
                return base

            def no_keys(c):
                return "_".join(c.parent_names)

            # max |corrLabel| and max Cramér's V per parent (NaN-filtered,
            # makeColumnStatistics.maxByParent)
            parent_corr: dict[str, float] = {}
            parent_corr_nk: dict[str, float] = {}
            for j in range(d):
                c = abs(corr_label[j])
                if np.isnan(c):
                    continue
                for table, key in (
                    (parent_corr, parent_key(meta.columns[j])),
                    (parent_corr_nk, no_keys(meta.columns[j])),
                ):
                    table[key] = max(table.get(key, 0.0), float(c))
            parent_v: dict[str, float] = {}
            parent_v_nk: dict[str, float] = {}
            for key, v in group_v.items():
                if np.isnan(v):
                    continue
                for i in group_cols[key]:
                    for table, pk in (
                        (parent_v, parent_key(meta.columns[i])),
                        (parent_v_nk, no_keys(meta.columns[i])),
                    ):
                        table[pk] = max(table.get(pk, 0.0), float(v))

            def is_text_shared_hash(c) -> bool:
                return (
                    c.parent_type in _TEXT_HASH_PARENT_TYPES
                    and c.grouping is None
                    and c.indicator_value is None
                )

            for j in range(d):
                c = meta.columns[j]
                if self.protect_text_shared_hash and is_text_shared_hash(c):
                    continue
                pk, nk = parent_key(c), no_keys(c)
                pv = parent_v.get(pk, parent_v_nk.get(nk))
                if pv is not None and pv > self.max_cramers_v:
                    drop(j, f"parentCramersV={pv:.4f}>{self.max_cramers_v}")
                pc = parent_corr.get(pk, parent_corr_nk.get(nk))
                if pc is not None and pc > self.max_correlation:
                    drop(j, f"parentCorr={pc:.4f}>{self.max_correlation}")

            # rule-confidence drops still take their indicator group
            # (removedGroups in getFeaturesToDrop)
            groups = meta.index_of_group()
            for j in list(drop_reasons):
                if not any(r.startswith("ruleConfidence")
                           for r in drop_reasons[j]):
                    continue
                key = meta.columns[j].grouped_key()
                if key[1] is None:
                    continue
                for i in groups.get(key, []):
                    if i not in drop_reasons:
                        drop(i, "featureGroupRemoval")

        return drop_reasons
