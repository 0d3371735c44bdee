"""Warm blocked exec timing per flagship tree program (RF depth groups +
XGB boost chunks) at the real flagship shapes.

Usage: python tools/profile_treeexec.py
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402
import numpy as np  # noqa: E402


def main() -> None:
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.features import from_dataset
    from transmogrifai_tpu.models import trees as TR
    from transmogrifai_tpu.models.gbdt import _feature_bin_groups
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
    n = len(y)
    print(f"x {x.shape}")

    thr = TR.quantile_thresholds(x, 32)
    binned = TR.bin_data(jnp.asarray(x), jnp.asarray(thr))
    fg = _feature_bin_groups(x)
    print(f"narrow {len(fg[0])} wide {len(fg[1])}")
    rng = np.random.default_rng(0)
    masks = np.stack(
        [(rng.random(n) < 0.67).astype(np.float32) for _ in range(4)]
    )
    rm24 = jnp.asarray(np.repeat(masks, 6, axis=0))  # K=24
    yj = jnp.asarray((y == 1).astype(np.float32))
    n_sub = int(np.ceil(np.sqrt(x.shape[1])))  # Spark's sqrt, a node

    for depth in (3, 6, 12):
        for rep in range(2):
            t0 = time.perf_counter()
            trees, outs = TR.fit_forest_batched(
                binned, yj, rm24, num_trees=50, max_depth=depth,
                num_bins=32, subsample_rate=1.0, feature_subset=n_sub,
                info_gain_norm=4.0,
                min_instances=10.0, min_info_gain=0.001, seed=42,
                lowp=True, feature_groups=fg, return_outputs=True,
            )
            jax.block_until_ready(outs)
            dt = time.perf_counter() - t0
        print(f"rf depth {depth:2d}: warm blocked {dt:6.3f}s")

    # XGB: 200 rounds depth 10, K=8
    rm8 = jnp.asarray(np.repeat(masks, 2, axis=0))
    for rep in range(2):
        t0 = time.perf_counter()
        trees, margin = TR.fit_boosted_batched(
            binned, yj, rm8, num_rounds=200, max_depth=10, num_bins=32,
            eta=0.02, reg_lambda=1.0, gamma=0.8,
            min_child_weight=jnp.asarray([1.0, 10.0] * 4),
            objective="binary:logistic", feature_groups=fg,
        )
        jax.block_until_ready(margin)
        dt = time.perf_counter() - t0
    print(f"xgb 200r depth 10: warm blocked {dt:6.3f}s")


if __name__ == "__main__":
    main()
