"""Warm per-family sweep timing for the flagship (run each family twice in
isolation; rep1 is the in-process warm floor).

Usage: python tools/profile_families.py [rf|lr|xgb|all]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402  (enables the compile cache)
import numpy as np  # noqa: E402


def main() -> None:
    import threading

    from transmogrifai_tpu.utils import aot

    warm = threading.Thread(target=aot.prewarm, daemon=True)
    warm.start()

    from transmogrifai_tpu.features import from_dataset
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.prep import SanityChecker
    from transmogrifai_tpu.readers import infer_csv_dataset
    from transmogrifai_tpu.workflow.fit import fit_and_transform_dag

    ds = infer_csv_dataset(bench.TITANIC)
    resp, preds = from_dataset(ds, response="Survived")
    preds = [p for p in preds if p.name != "PassengerId"]
    vector = transmogrify(preds)
    checked = resp.transform_with(SanityChecker(remove_bad_features=True), vector)
    data, _ = fit_and_transform_dag(ds, [checked, resp])
    x = np.asarray(data[checked.name].values, dtype=np.float32)
    y = np.asarray(data[resp.name].values, dtype=np.float64)

    from transmogrifai_tpu.evaluators import BinaryClassificationEvaluator
    from transmogrifai_tpu.models import (
        LogisticRegression,
        RandomForestClassifier,
        XGBoostClassifier,
    )
    from transmogrifai_tpu.selector.model_selector import (
        _lr_grid,
        _rf_grid,
        _xgb_binary_grid,
    )
    from transmogrifai_tpu.selector.validators import CrossValidator, expand_grid

    cv = CrossValidator(num_folds=3, seed=42)
    folds = cv.split_masks(y)
    evaluator = BinaryClassificationEvaluator()
    extra = [np.ones(len(y), dtype=np.float32)]
    all_masks = [tm.astype(np.float32) for tm, _ in folds] + extra

    fams = {
        "rf": (RandomForestClassifier(), expand_grid(_rf_grid())),
        "lr": (LogisticRegression(), expand_grid(_lr_grid())),
        "xgb": (XGBoostClassifier(), expand_grid(_xgb_binary_grid())),
    }
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    for name, (est, points) in fams.items():
        if which not in ("all", name):
            continue
        for rep in range(2):
            t0 = time.perf_counter()
            models = est.fit_arrays_batched_masks(x, y, all_masks, points)
            t1 = time.perf_counter()
            se = getattr(est, "sweep_eval_batched", None)
            vals = (
                se(models[: len(folds)], x, y, folds, evaluator)
                if se else None
            )
            t2 = time.perf_counter()
            if vals is None:
                # per-model predict loop (what the validator would do)
                for fi, (_tm, vm) in enumerate(folds):
                    vi = np.nonzero(vm)[0]
                    for m in models[fi]:
                        pred, prob, _ = m.predict_arrays(x[vi])
                        evaluator.evaluate_metric(y[vi], pred, prob)
                t2 = time.perf_counter()
            print(
                f"{name} rep{rep}: fit {t1-t0:6.2f}s  eval {t2-t1:6.2f}s "
                f"({len(points)} pts, sweep={'y' if vals is not None else 'n'})",
                flush=True,
            )


if __name__ == "__main__":
    main()
