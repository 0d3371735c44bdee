"""``resweep_glm_multiclass``: ``resweep_multiclass``'s loop with the
multiclass selector's linear family, Spark's multinomial logistic
regression.

``drivers/resweep_multiclass.py`` guarantees ONE forest and raises on a
configuration that states no forest learner, so a cell of another family
brings its own set-up; the table, the selector, the window's loop, the
product, the check and the end-to-end metric are theirs, imported.

The configuration guarantees ONE softmax fit a grid point (Spark's
multinomial family), not K one-vs-rest fits, and every GLM product at the
precision it states. The program states both
(``models/logistic.LOGISTIC_MULTINOMIAL``, ``logistic.solver_precision()``);
the set-up reads the learner's statement BEFORE the table is made and
exits where it is absent or names another learner, so a program from
before the batched multinomial learner fails in seconds and does not walk
into a ``[K, N, D]`` plane copy a lane. Both statements ride the sweep's
product for the check (``glm_learner_other``, ``precision_other``).

What a run's seed draws: the names of the seven classes, as in
``resweep_multiclass`` (the softmax objective is symmetric in the classes
and the start, the centred log class counts, is permuted with them), on ONE
table drawn from the configuration's ``table_seed``.
"""
from __future__ import annotations

from benchmarks.drivers import resweep, resweep_multiclass
from benchmarks.drivers.resweep import (  # noqa: F401 (the driver's API)
    _validator, _wrap_spans, end_to_end,
)
from benchmarks.lib import datagen, datagen_multiclass, reference


def learner_statement():
    """What the program states of its logistic regression over more than
    two classes, and of its GLM products' precision; None for either it
    does not state."""
    try:
        from transmogrifai_tpu.models import logistic
    except ImportError:
        return None, None
    stated = getattr(logistic, "LOGISTIC_MULTINOMIAL", None)
    precision = getattr(logistic, "solver_precision", None)
    return stated, precision() if precision is not None else None


def setup(ctx) -> None:
    cfg, seed = ctx.cfg, int(ctx.cfg["table_seed"])
    stated, precision = learner_statement()
    if stated != cfg["logistic_multinomial"]:
        raise SystemExit(
            f"the configuration guarantees {cfg['logistic_multinomial']!r}; "
            f"the program states {stated!r} of its logistic regression over "
            "more than two classes: not run"
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
        seed=seed, models=resweep_multiclass._candidates(cfg),
        validator=_validator(cfg, seed),
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
                     states={"logistic_multinomial": stated,
                             "glm_precision": precision})
    ctx.counters["plane_shape"] = tuple(int(v) for v in plane["x"].shape)
    ctx.counters["lanes"] = reference.lane_count(cfg)
    ctx.counters["classes"] = datagen_multiclass.CLASSES
    ctx.counters["class_order"] = tuple(int(c) for c in order)


def run(ctx) -> dict:
    """``resweep``'s window; the last sweep's product also carries what the
    program stated of its learner and its precision."""
    counts = resweep.run(ctx)
    ctx.state["product"]["states"].update(ctx.state["states"])
    return counts


def check(ctx) -> list[dict]:
    """``resweep_multiclass``'s check: the reference draws the holdout and
    the fold split from the table's seed as the selector did."""
    return resweep_multiclass.check(ctx)
