"""``resweep``: closed loop, one client, back-to-back selector sweeps.

Set-up makes the table from the seed and runs ONE cold ``Workflow.train()``
through the product path (reader -> transmogrify -> SanityChecker ->
selector -> fitted model). That train is the warm-up of every shape the
window uses, and hands the window the very objects it drives: the selector
stage and the plane ``(x, y, row_mask)`` the workflow's own stages gave it.
The window calls ``selector.fit_arrays(x, y, row_mask)`` back to back —
the call ``Workflow.train()`` makes — and starts no sweep after
``--seconds``; each sweep ends with the winner's refit parameters on the
host. The comparison with the plain reference (benchmarks/lib/reference.py)
runs on the LAST timed sweep's product.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.lib import datagen, reference


def _wrap_spans(ctx) -> None:
    """Host spans around the program functions the configuration lists
    under ``host_spans`` (name -> "module:attribute"): wrapped on the
    attribute the program looks up at call time; no program file changes."""
    import importlib

    for name, target in ctx.cfg.get("host_spans", {}).items():
        mod_name, path = target.split(":")
        owner = importlib.import_module(mod_name)
        *owners, attr = path.split(".")
        for part in owners:
            owner = getattr(owner, part)
        fn = getattr(owner, attr)

        def wrapped(*a, _fn=fn, _name=name, **kw):
            with ctx.span(_name):
                return _fn(*a, **kw)

        setattr(owner, attr, wrapped)


def _candidates(cfg):
    from transmogrifai_tpu.selector.model_selector import make_candidates

    models = make_candidates("BinaryClassification", list(cfg["families"]))
    for _est, grid in models:
        for key, values in cfg.get("grid", {}).items():
            if key in grid:
                grid[key] = list(values)
    return models


def _validator(cfg, seed):
    from transmogrifai_tpu.selector import validators

    spec = cfg["validator"]
    if spec["kind"] == "CrossValidator":
        return validators.CrossValidator(
            num_folds=int(spec["num_folds"]), seed=seed
        )
    if spec["kind"] == "TrainValidationSplit":
        return validators.TrainValidationSplit(
            train_ratio=float(spec["train_ratio"]), seed=seed
        )
    raise ValueError(f"unknown validator {spec!r}")


def setup(ctx) -> None:
    from transmogrifai_tpu.features import from_dataset
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.prep import SanityChecker
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector
    from transmogrifai_tpu.workflow.workflow import Workflow

    cfg, seed = ctx.cfg, ctx.seed
    with ctx.span("datagen"):
        table = datagen.flagship_table(int(cfg["rows"]), seed)
        ds = datagen.to_dataset(table)
    resp, preds = from_dataset(ds, response="label")
    checked = resp.transform_with(
        SanityChecker(remove_bad_features=True), transmogrify(preds)
    )
    selector = BinaryClassificationModelSelector(
        seed=seed, models=_candidates(cfg), validator=_validator(cfg, seed)
    )
    pred = selector.set_input(resp, checked).get_output()
    plane: dict = {}
    fit_arrays = selector.fit_arrays

    def spanned_fit_arrays(x, y, row_mask):
        plane.update(x=x, y=y, row_mask=row_mask)
        with ctx.span("fit_arrays"):
            return fit_arrays(x, y, row_mask)

    selector.fit_arrays = spanned_fit_arrays
    _wrap_spans(ctx)
    with ctx.span("cold_train"):
        model = (
            Workflow().set_result_features(pred)
            .set_input_dataset(ds).train()
        )
    if "x" not in plane:
        raise RuntimeError("Workflow.train() never reached the selector")
    ctx.state.update(table=table, selector=selector, model=model, plane=plane)
    ctx.counters["plane_shape"] = tuple(int(v) for v in plane["x"].shape)
    ctx.counters["lanes"] = reference.lane_count(cfg)


def run(ctx) -> dict:
    """Back-to-back sweeps; no sweep starts after ``--seconds``."""
    st = ctx.state
    selector, plane = st["selector"], st["plane"]
    x, y, row_mask = plane["x"], plane["y"], plane["row_mask"]
    sweeps = 0
    t0 = time.perf_counter()
    while True:
        with ctx.span("sweep"):
            selected = selector.fit_arrays(x, y, row_mask)
            # the product of a sweep: the winner's refit parameters, on
            # the host (what persistence and serving take)
            arrays = {
                k: np.asarray(v)
                for k, v in selected.best_model.get_arrays().items()
            }
        sweeps += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= ctx.seconds:
            break
    st["product"] = product(ctx.cfg, plane, selected, arrays)
    failed = sum(
        1 for a in selected.summary["candidateAttempts"] if a["excluded"])
    return {
        "attempted": sweeps, "failed": failed, "sweeps": sweeps,
        "elapsed_s": elapsed,
    }


def end_to_end(ctx) -> dict:
    w = ctx.counters["window"]
    return {"sweep_s": w["elapsed_s"] / w["sweeps"]}


def product(cfg, plane, selected, arrays) -> dict:
    """What a sweep gave, as the comparison reads it: the plane it ran on,
    its summary, the winner's refit, and what the program states of
    itself where the configuration holds it to a guarantee."""
    summary = selected.summary
    states = {}
    if "hist_impl" in cfg:
        from transmogrifai_tpu.models import hist_pallas

        states["hist_impl"] = hist_pallas.default_impl()
    return {
        "plane": plane, "summary": summary, "states": states,
        "winner": {
            "type": summary["bestModelType"],
            "grid": dict(summary["bestGrid"]), "arrays": arrays,
            "thresholds": getattr(selected.best_model, "thresholds", None),
        },
    }


def check(ctx) -> list[dict]:
    """Free the program's device state, then compare the last timed
    sweep's product with the plain reference."""
    st = ctx.state
    made = st.pop("product")
    columns = plane_columns(st["model"])
    for key in ("selector", "model", "plane"):
        st.pop(key, None)
    free_program_state()
    ref = reference.build(ctx.cfg, st["table"], columns, ctx.seed)
    return reference.compare(ctx.cfg, ref, made)


def plane_columns(model):
    """What the program says each column of the plane is: (parent raw
    column, indicator value, descriptor) per column, from the fitted
    SanityChecker's output metadata."""
    for stage in model.fitted.values():
        md = getattr(stage, "new_metadata", None)
        if md is not None and hasattr(stage, "indices_to_keep"):
            return [
                (c.parent_names[0], c.indicator_value, c.descriptor_value)
                for c in md.columns
            ]
    raise RuntimeError("no metadata for the selector's feature vector")


def free_program_state() -> None:
    import gc

    from transmogrifai_tpu.compiler import dispatch
    from transmogrifai_tpu.models import gbdt

    gbdt._BINNED_CACHE.clear()
    dispatch._PREFETCH.clear()
    gc.collect()
