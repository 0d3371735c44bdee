"""``resweep_regression``: ``resweep``'s loop with the regression selector
and Spark's generalized linear regression.

``drivers/resweep.py`` makes the binary table and the binary selector, so a
cell of another problem brings its own set-up; the window's loop and the
end-to-end metric are ``resweep``'s, imported.

The configuration guarantees Spark's learner (``glr_irls``: gaussian/
identity one weighted-least-squares solve, every other pair IRLS from
FamilyAndLink.initialize to Spark's stop) and every GLM product at the
precision it states. The program states both (``models/glm.GLR_IRLS``,
``logistic.solver_precision()``); the set-up reads the learner's statement
BEFORE the table is made and exits where it is absent or names another
learner, so a program from before the batched IRLS learner fails in
seconds and does not walk into 32 one-lane programs a sweep, each with its
own copy of the plane. Both statements ride the sweep's product for the
check (``glr_learner_other``, ``precision_other``).

What a run's seed draws: nothing. The table, its label's noise, the
holdout, the DataSplitter's refit sample and the fold split all come from
the configuration's ``table_seed`` (``lib/datagen_regression.py``): an
amount has no class names to permute and no polarity to flip, and any
other draw (a new noise, a new split) moves the iterations each IRLS lane
needs, so every seed does the same work on the same table.

``lib/reference.compare`` reads grid values as numbers and the larger
mean as the better; the check hands it the configuration and the sweep's
summary in ``checks/glr_winner.py``'s coded form (the family and the link
by their index in the grid, the RMSE negated), the same points in the
same order.
"""
from __future__ import annotations

import sys

from benchmarks.checks import glr_winner
from benchmarks.drivers import resweep
from benchmarks.drivers.resweep import (  # noqa: F401 (the driver's API)
    _validator, _wrap_spans, end_to_end,
)
from benchmarks.lib import datagen, datagen_regression, reference


def learner_statement():
    """What the program states of its generalized linear regression, and
    of its GLM products' precision; None for either it does not state."""
    try:
        from transmogrifai_tpu.models import glm, logistic
    except ImportError:
        return None, None
    stated = getattr(glm, "GLR_IRLS", None)
    precision = getattr(logistic, "solver_precision", None)
    return stated, precision() if precision is not None else None


def _candidates(cfg):
    from transmogrifai_tpu.selector.model_selector import make_candidates

    models = make_candidates("Regression", list(cfg["families"]))
    for _est, grid in models:
        for key, values in cfg.get("grid", {}).items():
            if key in grid:
                grid[key] = list(values)
    return models


def setup(ctx) -> None:
    cfg, seed = ctx.cfg, int(ctx.cfg["table_seed"])
    stated, precision = learner_statement()
    if stated != cfg["glr_irls"]:
        raise SystemExit(
            f"the configuration guarantees {cfg['glr_irls']!r}; the program "
            f"states {stated!r} of its generalized linear regression: not run"
        )
    from transmogrifai_tpu.features import from_dataset
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.prep import SanityChecker
    from transmogrifai_tpu.selector import RegressionModelSelector
    from transmogrifai_tpu.workflow.workflow import Workflow

    with ctx.span("datagen"):
        table = datagen_regression.regression_table(int(cfg["rows"]), seed)
        ds = datagen.to_dataset(table)
    resp, preds = from_dataset(ds, response="label")
    checked = resp.transform_with(
        SanityChecker(remove_bad_features=True), transmogrify(preds)
    )
    selector = RegressionModelSelector(
        seed=seed, models=_candidates(cfg), validator=_validator(cfg, seed),
    )  # its default splitter: DataSplitter(seed=seed) at its defaults
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
                     states={"glr_irls": stated, "glm_precision": precision})
    ctx.counters["plane_shape"] = tuple(int(v) for v in plane["x"].shape)
    ctx.counters["lanes"] = reference.lane_count(cfg)


def run(ctx) -> dict:
    """``resweep``'s window; the last sweep's product also carries what the
    program stated of its learner and its precision."""
    counts = resweep.run(ctx)
    ctx.state["product"]["states"].update(ctx.state["states"])
    # the last sweep's IRLS counts, for the record (the program's own span)
    try:
        from transmogrifai_tpu.telemetry import spans

        last = [e["args"] for e in spans.snapshot_events()
                if e["name"] == "glm/await_outputs"
                and "iters_needed" in e.get("args", {})]
        if last:
            print(f"irls counts of the last sweep: {last[-1]}",
                  file=sys.stderr, flush=True)
    except ImportError:
        pass
    return counts


def check(ctx) -> list[dict]:
    """Free the program's device state, then compare the last timed
    sweep's product with the plain reference, which draws the holdout, the
    refit sample and the fold split from the table's seed as the selector
    did; in ``checks/glr_winner.py``'s coded form."""
    st = ctx.state
    made = st.pop("product")
    columns = resweep.plane_columns(st["model"])
    for key in ("selector", "model", "plane"):
        st.pop(key, None)
    resweep.free_program_state()
    cfg = glr_winner.coded_config(ctx.cfg)
    ref = reference.build(cfg, st["table"], columns, int(cfg["table_seed"]))
    made = {**made, "summary": glr_winner.coded_summary(cfg, made["summary"])}
    return reference.compare(cfg, ref, made)
