#!/usr/bin/env python3
"""A benchmark cell's controls and planted faults at the cell's own size,
on the chip, through the comparison a run uses.

    python3 tools/bench_control.py --workload flagship_gbt.fit --seed N
        [--rows R] [--controls fit,plane] [--fault NAME --faults-from FILE]

One process: the cell's set-up (the table from the seed, one cold
``Workflow.train()``), ONE sweep, then ``benchmarks/lib/reference.py``'s
``build`` and ``compare`` on that sweep's product (the program's own
readings), and for each control named (``fit``: the reference in the
program's place with its fits one step below the stated precision;
``plane``: with a bfloat16 plane) ``stand_in`` and the same ``compare``.
``--fault NAME`` first applies ``FAULTS[NAME]()`` of the test file given
(``tests/bench/test_bench_*_control.py``), so the program under the sweep is
the broken one. ``--rows`` overrides the configuration's rows (a fault does
not need the whole table to read false). Prints one JSON object a line:
``{"what": ..., "correct": ..., "compared": {...}}``; nothing is timed.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _say(what, compared, **more):
    print(json.dumps({
        "what": what, **more,
        "correct": all(c["ok"] for c in compared),
        "compared": {c["name"]: repr(c["value"]) for c in compared},
        "over": [c["name"] for c in compared if not c["ok"]],
    }), flush=True)


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rows", type=int, default=None)
    p.add_argument("--controls", default="fit")
    p.add_argument("--fault", default=None)
    p.add_argument("--faults-from", default=None)
    args = p.parse_args(argv)

    from benchmarks import run as bench_run
    from benchmarks.lib import by_name, reference

    bench = bench_run._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = bench_run._load_json(os.path.join(ROOT, config["file"]))
    traffic = bench_run._load_json(os.path.join(
        ROOT, "benchmarks", "traffic", f"{cell['traffic']}.json"))
    if args.rows:
        cfg["rows"] = int(args.rows)
    if args.fault:
        spec = importlib.util.spec_from_file_location(
            "faults", os.path.join(ROOT, args.faults_from))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.FAULTS[args.fault]()
    from transmogrifai_tpu import native

    native.available()
    import jax

    from transmogrifai_tpu.compiler import cache as ccache

    dev = jax.devices()[0]
    ccache.enable_persistent_cache()
    ns = argparse.Namespace(seed=args.seed, seconds=0.0, rehearsal=False)
    ctx = bench_run.Context(ns, cfg, traffic)
    ctx.device_kind = dev.device_kind
    driver = by_name("drivers", traffic["driver"])
    driver.setup(ctx)
    counts = driver.run(ctx)
    ctx.counters["window"] = counts
    st = ctx.state
    made = st.pop("product")
    columns = driver.plane_columns(st["model"])
    for key in ("selector", "model", "plane"):
        st.pop(key, None)
    driver.free_program_state()
    ref = reference.build(cfg, st["table"], columns, ctx.seed)
    compared = reference.compare(cfg, ref, made)
    failed = int(counts.get("failed", 0))
    _say("program" if not args.fault else f"fault:{args.fault}", compared,
         device=dev.device_kind, rows=cfg["rows"], failed=failed,
         winner=made["winner"]["grid"])
    del made
    if args.fault:
        return 0
    for control in filter(None, args.controls.split(",")):
        precision = {"fit": {"plane": "f32", "fit": "bf16"},
                     "plane": {"plane": "bf16", "fit": "f32"}}[control]
        product = reference.stand_in(cfg, ref, precision)
        _say(f"control:{control}", reference.compare(cfg, ref, product),
             precision=precision, winner=product["winner"]["grid"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
