#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the flagship binary flow once, in ONE process, through the entry
points a user calls: seeded typed dataset -> ``from_dataset`` ->
``transmogrify`` -> ``SanityChecker`` -> the default
``BinaryClassificationModelSelector`` (LR 8 + RF 18 + XGBoost 2 grid
points, 3 folds) -> ``Workflow.train`` -> ``model.score`` ->
``score_function`` -> ``ScoringService``. A second workflow whose selector
holds only the tree families makes sure the tree serve program runs
whichever family wins the first sweep.

Row counts follow the code's own thresholds: every fold trains on more
than 4,096 rows, so tree fits take the Pallas histogram kernels
(``models/trees.py``), and serve batches exceed the 16,384-row host
cutoff, so predict and the fused scoring graph dispatch on the device
(``models/gbdt.py``, ``local/scoring.py``).

It fails — non-zero exit, no result line — unless JAX's first device is a
TPU and every check below holds. No number it prints is a benchmark
result. ``--rehearsal`` is the CPU walk-through of the same control flow at
a tiny size — a few hundred rows, and the same grids over a handful of
shallow trees (tier-1 runs it); every line it prints says so.

    python chip_smoke.py                 # on the chip
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: banked program names the flagship sweep must have acquired on one
#: device; over a mesh the tree kernels are shard_map programs outside the
#: bank and the GLM sweep is one sharded program named for the mesh
FIT_PROGRAMS = ("boost_chunk", "forest_scan", "logistic_binary_batched")
FIT_PROGRAMS_MESH = ("sweep_logistic_binary_sharded",)
#: LR 8 + RF 18 + XGBoost 2 (selector/model_selector.py defaults)
DEFAULT_GRID_POINTS = 28
SEED = 7
TREE_FAMILIES = ["OpXGBoostClassifier", "OpRandomForestClassifier"]
DEFAULT_FAMILIES = ["OpLogisticRegression", "OpRandomForestClassifier",
                    "OpXGBoostClassifier"]
#: the rehearsal's ensembles: same families, same number of grid points,
#: a handful of shallow trees — XLA:CPU needs minutes for the real ones
REHEARSAL_GRID = {"num_trees": [4], "num_round": [6]}
REHEARSAL_DEPTH = {3: 2, 6: 3, 12: 4, 10: 3}


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--rehearsal", action="store_true",
        help="CPU walk-through at a tiny size (interpret-mode kernels)",
    )
    p.add_argument("--rows", type=int, default=None,
                   help="training rows (default 16384; rehearsal 768)")
    return p.parse_args(argv)


def main(argv) -> int:
    args = _parse(argv)
    if not os.path.isdir(os.path.join(HERE, "transmogrifai_tpu")):
        print(
            "chip_smoke.py drives the checkout it sits in; no "
            f"transmogrifai_tpu/ beside it in {HERE}", file=sys.stderr,
        )
        return 2
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    tag = "rehearsal " if args.rehearsal else ""

    def say(msg: str) -> None:
        print(f"{tag}{msg}", flush=True)

    rows = args.rows or (768 if args.rehearsal else 16384)
    serve_rows = 512 if args.rehearsal else 32768
    if args.rehearsal:
        # the CPU walk-through: same code, interpret-mode serve kernel,
        # and a host cutoff below the tiny serve batch so the fused graph
        # still dispatches
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["TPTPU_SERVE_TREES"] = "pallas"
        os.environ["TPTPU_HOST_PREDICT_MAX"] = str(serve_rows // 2)
    elif rows * 0.9 * 2 / 3 <= 4096:
        print(
            "too few rows: every fold must train on more than 4,096 for "
            "the tree fits to take the Pallas histogram kernels",
            file=sys.stderr,
        )
        return 2

    t_start = time.monotonic()
    # the host kernels build with `make` on first use in a clean checkout:
    # let that child run now, before this process holds the chip
    from transmogrifai_tpu import native

    native_built = native.available()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"JAX found no usable backend: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }
    if not args.rehearsal and dev.platform != "tpu":
        print(
            f"chip_smoke.py needs a TPU; JAX reports {device}. "
            "(--rehearsal walks the control flow on CPU.)", file=sys.stderr,
        )
        return 2

    import numpy as np

    from transmogrifai_tpu.compiler import cache as ccache
    from transmogrifai_tpu.compiler import stats as cstats
    from transmogrifai_tpu.features import from_dataset
    from transmogrifai_tpu.local.scoring import score_function
    from transmogrifai_tpu.models import hist_pallas
    from transmogrifai_tpu.models.serve_pallas import serve_impl
    from transmogrifai_tpu.ops import transmogrify
    from transmogrifai_tpu.parallel.mesh import default_execution_mesh
    from transmogrifai_tpu.prep import SanityChecker
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector
    from transmogrifai_tpu.selector.model_selector import make_candidates
    from transmogrifai_tpu.serving.service import ScoringService
    from transmogrifai_tpu.testkit import flagship_dataset
    from transmogrifai_tpu.utils import aot
    from transmogrifai_tpu.workflow.workflow import Workflow

    failures: list[str] = []

    def check(ok: bool, what: str) -> None:
        say(f"{'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            failures.append(what)

    mesh = default_execution_mesh()
    say(
        f"device platform: {dev.platform} kind: {dev.device_kind} "
        f"count: {len(devices)} jax: {jax.__version__} mesh: "
        f"{None if mesh is None else dict(mesh.shape)}"
    )
    cache_dir = ccache.enable_persistent_cache()
    entries_before = ccache.entry_count()
    say(f"compile cache: {cache_dir} ({entries_before} entries before)")
    say(f"native host kernels: {native_built} (False = numpy fallbacks)")
    say(f"sizes: {rows} training rows, {serve_rows} rows per serve batch")
    if not args.rehearsal:
        check(
            hist_pallas.default_impl() == "pallas",
            f"histogram impl is pallas (got {hist_pallas.default_impl()})",
        )

    train_ds = flagship_dataset(rows, seed=SEED)
    serve_ds = flagship_dataset(serve_rows, seed=SEED + 1)
    y_serve = np.asarray(serve_ds["label"].values)

    def candidates(families=None):
        """The selector's ``models``: its own defaults (None) unless
        ``families`` narrows them. The rehearsal cuts every ensemble,
        keeping the grid shape."""
        if not args.rehearsal:
            return families and make_candidates(
                "BinaryClassification", families
            )
        models = make_candidates(
            "BinaryClassification", families or DEFAULT_FAMILIES
        )
        for _est, grid in models:
            for key, cut in REHEARSAL_GRID.items():
                if key in grid:
                    grid[key] = cut
            if "num_trees" in grid or "num_round" in grid:
                grid["max_depth"] = [
                    REHEARSAL_DEPTH[d] for d in grid["max_depth"]
                ]
        return models

    def train(models, what):
        resp, preds = from_dataset(train_ds, response="label")
        checked = resp.transform_with(
            SanityChecker(remove_bad_features=True), transmogrify(preds)
        )
        selector = BinaryClassificationModelSelector(
            seed=SEED, models=models
        )
        pred = selector.set_input(resp, checked).get_output()
        t0 = time.monotonic()
        model = (
            Workflow().set_result_features(pred)
            .set_input_dataset(train_ds).train()
        )
        seconds = time.monotonic() - t0  # train() ends in host pulls
        summary = model.summary_json()["modelSelectorSummary"]
        say(
            f"{what}: train_s {seconds:.1f} (cold: includes compilation), "
            f"winner {summary['bestModelType']} {summary['bestGrid']}"
        )
        return model, pred.name, summary, seconds

    def check_sweep(what, summary, grid_points):
        got = len(summary["validationResults"])
        check(
            got == grid_points,
            f"{what}: {got} of {grid_points} grid points validated",
        )
        excluded = [
            a["modelName"] for a in summary["candidateAttempts"]
            if a["excluded"]
        ]
        check(not excluded, f"{what}: no family excluded (got {excluded})")
        aupr = summary["holdoutEvaluation"]["AuPR"]
        chance = float(np.mean(train_ds["label"].values))
        check(
            np.isfinite(aupr) and aupr > chance + 0.05,
            f"{what}: holdout AuPR {aupr:.4f} above chance {chance:.3f}",
        )

    def row_dicts(k):
        """The first ``k`` serve rows as the row-dict entry points take
        them."""
        head = np.arange(k)
        names = list(serve_ds.columns)
        return [
            dict(zip(names, vals))
            for vals in zip(
                *(serve_ds[n].take(head).to_list() for n in names)
            )
        ]

    def arrays(out, name):
        col = out[name]
        return (
            np.asarray(col.prediction), np.asarray(col.probability),
            np.asarray(col.raw),
        )

    def serve(what, model, name, tree: bool):
        """model.score + the serving closure over device-size batches."""
        scored = model.score(dataset=serve_ds)
        pred, prob, _raw = arrays(scored, name)
        check(
            pred.shape == (serve_rows,) and prob.shape == (serve_rows, 2)
            and bool(np.isfinite(prob).all()),
            f"{what}: model.score gives finite [{serve_rows}, 2] "
            "probabilities",
        )
        acc = float(np.mean(pred == y_serve))
        check(acc > 0.6, f"{what}: accuracy {acc:.3f} on unseen rows")

        fn = score_function(model)
        fn.columns(serve_ds)  # warm-up: compiles the bucket's program
        timed = []
        for _ in range(3):
            t0 = time.monotonic()
            fused_out = fn.columns(serve_ds)  # ends in the core download
            timed.append(time.monotonic() - t0)
        say(
            f"{what}: serve batch seconds after warm-up "
            f"{[round(s, 4) for s in timed]}"
        )
        one = fn(row_dicts(1)[0])
        check(
            "prediction" in one[name],
            f"{what}: the row-dict entry point answers",
        )
        md = fn.metadata()["fused"]
        prog = fn.fused_state["program"]
        descriptor = None if prog is None else prog.pspec.descriptor
        check(bool(md["active"]), f"{what}: fused graph active "
              f"(reason {md['reason']})")
        check(md["dispatches"] >= 4, f"{what}: fused dispatches "
              f"{md['dispatches']}")
        check(
            md["fallbacks"] == 0 and not md["fallbackReasons"],
            f"{what}: no fused fallback (got {md['fallbacks']}, "
            f"{md['fallbackReasons']})",
        )
        say(f"{what}: fused predictor {descriptor}, fingerprint "
            f"{md['fingerprint']}")
        if tree:
            impl = serve_impl()
            check(
                descriptor is not None
                and descriptor.endswith(":pl") == (impl == "pallas"),
                f"{what}: tree traversal baked is {impl} ({descriptor})",
            )
        # the same batch through the staged loop of the same closure
        os.environ["TPTPU_FUSED"] = "0"
        try:
            staged_out = fn.columns(serve_ds)
        finally:
            del os.environ["TPTPU_FUSED"]
        f_pred, f_prob, f_raw = arrays(fused_out, name)
        s_pred, s_prob, s_raw = arrays(staged_out, name)
        err = float(np.abs(f_prob - s_prob).max())
        if tree and not args.rehearsal:
            same = (
                np.array_equal(f_pred, s_pred)
                and np.array_equal(f_prob, s_prob)
                and np.array_equal(f_raw, s_raw)
            )
            check(same, f"{what}: fused and staged scores bit-identical "
                  f"(max |dprob| {err:.3g})")
        else:
            # GLMs differ by f32-on-device arithmetic; on XLA:CPU the two
            # tree programs also contract base + eta * sum differently
            check(err <= 1e-6, f"{what}: fused vs staged |dprob| "
                  f"{err:.3g} <= 1e-6")
        return fn

    # ------------------------------------------------------ flagship sweep
    before = cstats.snapshot()
    model, name, summary, train_s = train(candidates(), "flagship")
    check_sweep("flagship", summary, DEFAULT_GRID_POINTS)
    ledger = cstats.delta(before)
    compiled = ledger["programsCompiledByName"]
    missing = [
        p for p in (FIT_PROGRAMS if mesh is None else FIT_PROGRAMS_MESH)
        if not any(name.startswith(p) for name in compiled)
    ]
    reused = ledger["cacheHitsDisk"] + ledger["warmupPrograms"]
    check(
        len(missing) <= reused,
        f"flagship: fit programs acquired (compiled {sorted(compiled)}, "
        f"{reused} loaded from the bank)",
    )
    best_is_tree = "Logistic" not in summary["bestModelType"]
    serve("flagship", model, name, tree=best_is_tree)

    # ---------------------------------------------------- tree-only sweep
    tree_models = candidates(TREE_FAMILIES)
    tree_points = sum(
        int(np.prod([len(v) for v in grid.values()]))
        for _est, grid in tree_models
    )
    t_model, t_name, t_summary, tree_train_s = train(tree_models, "trees")
    check_sweep("trees", t_summary, tree_points)
    serve("trees", t_model, t_name, tree=True)

    # ------------------------------------------- standing service, defaults
    svc_fn = score_function(model)
    rows_in = row_dicts(24)
    with ScoringService(svc_fn) as svc:
        pending = [svc.submit(r) for r in rows_in[:16]]
        pending.append(svc.submit(rows_in[16:]))
        answers = [p.result(timeout=120) for p in pending]
    stats = svc.stats()
    check(
        sum(len(a) for a in answers) == len(rows_in)
        and stats["completed"] == len(pending) and stats["errors"] == 0,
        f"service: {stats['completed']} of {len(pending)} requests "
        f"completed in {stats['batches']} batches",
    )
    # a default service batches at most 256 rows, under the 16,384-row
    # host cutoff: its batches predict on the host, and are NOT chip work
    say(
        "service_device_batches: "
        f"{svc_fn.metadata()['fused']['dispatches']} (batches of at most "
        f"{svc.config.max_batch_rows} rows run on the host; ROADMAP S7)"
    )

    # ------------------------------------------------- device and caches
    peaks = []
    for d in devices:
        ms = d.memory_stats() or {}
        peaks.append(int(ms.get("peak_bytes_in_use", 0)))
    if args.rehearsal:
        say(f"device peak bytes: {peaks} (the CPU backend reports none)")
    else:
        check(all(p > 0 for p in peaks),
              f"device peak_bytes_in_use per device {peaks}")
    t0 = time.monotonic()
    aot._drain_exports()  # what the atexit hook waits for, timed here
    exit_s = time.monotonic() - t0
    final = cstats.snapshot()
    report = {
        "device": device,
        "mesh": None if mesh is None else dict(mesh.shape),
        "rows": rows, "serve_rows": serve_rows,
        "train_s_cold_including_compile": round(train_s, 1),
        "trees_train_s": round(tree_train_s, 1),
        "compile_cache_dir": cache_dir,
        "cache_entries_before": entries_before,
        "cache_entries_after": ccache.entry_count(),
        "compileStats": {
            k: final[k] for k in (
                "programsCompiled", "cacheHitsMemory", "cacheHitsDisk",
                "warmupPrograms", "savesFailed", "corruptBlobsDropped",
            )
        },
        "native_available": native_built,
        "device_peak_bytes": peaks,
        "exit_drain_s": round(exit_s, 1),
        "total_s": round(time.monotonic() - t_start, 1),
    }
    say("report " + json.dumps(report, sort_keys=True))
    if failures:
        for f in failures:
            print(f"{tag}FAILED {f}", file=sys.stderr)
        return 1
    result = {"ok": True, "device": device}
    if args.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
