"""``resweep_multiclass``: ``resweep``'s loop on a table whose label has
several classes, under the multiclass selector.

``drivers/resweep.py``'s set-up hard-codes the binary table and
``BinaryClassificationModelSelector``, so a cell with another label brings
its own set-up: the seven-class table of ``lib/datagen_multiclass.py`` and
``MultiClassificationModelSelector`` with ``DataCutter`` at its defaults
(it keeps every row of this table, so the plane reaches the fits where it
lies). Everything else is ``resweep``'s, imported: the window's loop, the
sweep's product, the end-to-end metric, the check, the host spans.

The configuration guarantees ONE forest a grid point whatever the number of
classes. The program states how its forest treats more than two classes
(``models/gbdt.FOREST_MULTICLASS``) as it states its histogram builder; the
set-up reads that statement BEFORE the table is made and raises where it is
absent or says another learner, so a program from before the K-class
learner fails in seconds and does not grind through 84 one-vs-rest lanes.
The statement rides the sweep's product for the check
(``forest_learner_other``).

What a run's seed draws. A forest's node count follows its table, and with
it the sweep's seconds (``lib/datagen_multiclass.py``), so the resident
table is ONE table: the table, the holdout and the fold split are drawn
from the configuration's ``table_seed``, and the run's seed draws the names
of the seven classes. Every seed then does the same work on another label.
"""
from __future__ import annotations

import copy

from benchmarks.drivers import resweep
from benchmarks.drivers.resweep import (  # noqa: F401 (the driver's API)
    _validator, _wrap_spans, end_to_end, free_program_state, plane_columns,
    product,
)
from benchmarks.lib import datagen, datagen_multiclass, reference


def forest_statement():
    """What the program states of its forest over more than two classes,
    or None from a program that states nothing."""
    from transmogrifai_tpu.models import gbdt

    return getattr(gbdt, "FOREST_MULTICLASS", None)


def _candidates(cfg):
    from transmogrifai_tpu.selector.model_selector import make_candidates

    models = make_candidates("MultiClassification", list(cfg["families"]))
    for _est, grid in models:
        for key, values in cfg.get("grid", {}).items():
            if key in grid:
                grid[key] = list(values)
    return models


def setup(ctx) -> None:
    cfg, seed = ctx.cfg, int(ctx.cfg["table_seed"])
    stated = forest_statement()
    if stated != cfg["forest_multiclass"]:
        raise SystemExit(
            f"the configuration guarantees {cfg['forest_multiclass']!r}; "
            f"the program states {stated!r} of its forest over more than "
            "two classes: not run"
        )
    from transmogrifai_tpu.features import from_dataset
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.prep import SanityChecker
    from transmogrifai_tpu.selector import MultiClassificationModelSelector
    from transmogrifai_tpu.workflow.workflow import Workflow

    with ctx.span("datagen"):
        order = datagen_multiclass.class_order(ctx.seed)
        table = datagen_multiclass.multiclass_table(
            int(cfg["rows"]), seed, order)
        ds = datagen.to_dataset(table)
    resp, preds = from_dataset(ds, response="label")
    checked = resp.transform_with(
        SanityChecker(remove_bad_features=True), transmogrify(preds)
    )
    selector = MultiClassificationModelSelector(
        seed=seed, models=_candidates(cfg), validator=_validator(cfg, seed)
    )  # its default splitter: DataCutter(seed=seed) at its defaults
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
    ctx.state.update(table=table, selector=selector, model=model, plane=plane,
                     forest_multiclass=stated)
    ctx.counters["plane_shape"] = tuple(int(v) for v in plane["x"].shape)
    ctx.counters["lanes"] = reference.lane_count(cfg)
    ctx.counters["classes"] = datagen_multiclass.CLASSES
    ctx.counters["class_order"] = tuple(int(c) for c in order)


def check(ctx) -> list[dict]:
    """``resweep``'s check, its reference drawing the holdout and the fold
    split from the table's seed as the selector did."""
    at = copy.copy(ctx)
    at.seed = int(ctx.cfg["table_seed"])
    return resweep.check(at)


def run(ctx) -> dict:
    """``resweep``'s window; the last sweep's product also carries what the
    program stated of its forest."""
    counts = resweep.run(ctx)
    ctx.state["product"]["states"]["forest_multiclass"] = ctx.state[
        "forest_multiclass"]
    return counts
