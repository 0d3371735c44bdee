"""Profile score_function(model).batch on Titanic (the red batch-serving bench).

Run: JAX_PLATFORMS=cpu python tools/profile_serve_batch.py
"""
from __future__ import annotations

import cProfile
import io
import os
import pstats
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from transmogrifai_tpu.features import from_dataset
from transmogrifai_tpu.local.scoring import score_function
from transmogrifai_tpu.ops import transmogrify
from transmogrifai_tpu.prep import SanityChecker
from transmogrifai_tpu.readers import infer_csv_dataset
from transmogrifai_tpu.selector import BinaryClassificationModelSelector
from transmogrifai_tpu.workflow.workflow import Workflow

TITANIC = "/root/reference/test-data/PassengerDataAllWithHeader.csv"


def main() -> None:
    ds = infer_csv_dataset(TITANIC)
    resp, preds = from_dataset(ds, response="Survived")
    preds = [p for p in preds if p.name != "PassengerId"]
    vector = transmogrify(preds)
    checked = resp.transform_with(SanityChecker(remove_bad_features=True), vector)
    selector = BinaryClassificationModelSelector(seed=42)
    pred = selector.set_input(resp, checked).get_output()
    model = Workflow().set_result_features(pred).set_input_dataset(ds).train()

    f = score_function(model)
    names = [feat.name for feat in model.raw_features]
    rows = [
        {n: v for n, v in zip(names, vals)}
        for vals in zip(*(ds[n].to_list() for n in names))
    ]
    print(f"rows: {len(rows)}")
    f.batch(rows)  # warm
    for i in range(3):
        t1 = time.perf_counter()
        f.batch(rows)
        dt = time.perf_counter() - t1
        print(f"batch pass {i}: {dt*1000:.1f}ms  ({len(rows)/dt:,.0f} rows/s)")
    f.columns(ds)  # warm
    for i in range(3):
        t1 = time.perf_counter()
        f.columns(ds)
        dt = time.perf_counter() - t1
        print(f"columns pass {i}: {dt*1000:.1f}ms  ({len(rows)/dt:,.0f} rows/s)")
    # per-row p50 after the plan optimizations
    lat = []
    f(rows[0])
    for r in rows[:100]:
        t1 = time.perf_counter()
        f(r)
        lat.append(time.perf_counter() - t1)
    lat.sort()
    print(f"per-row p50: {lat[50]*1000:.2f}ms")

    pr = cProfile.Profile()
    pr.enable()
    for _ in range(3):
        f.batch(rows)
    pr.disable()
    s = io.StringIO()
    pstats.Stats(pr, stream=s).sort_stats("cumulative").print_stats(40)
    print(s.getvalue())


if __name__ == "__main__":
    main()
