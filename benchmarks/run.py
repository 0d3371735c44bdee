#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one kind of
window or one per-layer metric is a file of its own, found by the name
``BENCHMARK.json`` gives it (see benchmarks/README.md):

    configs/<config>.json -> checks/<check>.py, work/<work>.py
    traffic/<mix>.json    -> drivers/<driver>.py
    layer_metrics/<metric>.py

A run is one process: set-up (table from the seed, one cold
``Workflow.train()``, which warms every shape), the measured window, the
device's peak memory, then the comparison with the plain reference that
decides ``correct``. The last line of standard output is the result object.
Without a TPU the run exits non-zero and prints no result; ``--rehearsal``
is the only CPU path, runs a tiny table, and reports no device metric.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import resource
import shutil
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: metrics whose value is a reading of the device: never printed from a CPU run
DEVICE_SOURCES = ("device_trace",)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Context:
    """What a driver and the per-layer readers share for one run."""

    def __init__(self, args, cfg, traffic):
        self.cfg = cfg
        self.traffic = traffic
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.rehearsal = bool(args.rehearsal)
        self.tracing = False
        self.spans: list[tuple[str, float, float]] = []
        self.counters: dict = {}
        self.state: dict = {}
        self.device_kind = None
        #: set while set-up runs: (span, device peak bytes at its end)
        self.peak_of = None
        self.peaks: list[tuple[str, int]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: kept in memory, and written into the profiler's
        trace (on its clock) while one is being taken."""
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench:{name}")
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))
                if self.peak_of is not None:
                    held = self.peak_of.memory_stats() or {}
                    self.peaks.append(
                        (name, int(held.get("peak_bytes_in_use", 0))))

    def span_seconds(self, name: str) -> list[float]:
        return [t1 - t0 for n, t0, t1 in self.spans if n == name]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _metrics_of(bench: dict, section: str, cell: str, reported=None):
    """The entries of ``section`` that this cell reports."""
    out = []
    for m in bench[section]:
        cells = m.get("workloads")
        if cells is None:
            ok = reported is None or m.get("moves") in reported
        else:
            ok = cell in cells
        if ok:
            out.append(m)
    return out


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU walk-through at the config's rehearsal size")
    return p.parse_args(argv)


def main(argv) -> int:
    args = _parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "transmogrifai_tpu")):
        say(f"no system under test: {ROOT} holds no transmogrifai_tpu/")
        return 2
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        say(f"unknown workload {args.workload!r}; have {sorted(cells)}")
        return 2
    cell = cells[args.workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = _load_json(os.path.join(ROOT, config["file"]))
    traffic = _load_json(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    )
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        cfg = {**cfg, **cfg.get("rehearsal", {})}
    sys.path.insert(0, ROOT)
    from benchmarks.lib import by_name

    driver = by_name("drivers", traffic["driver"])

    # the host kernels build with `make` on first use in a clean checkout:
    # let that child run and end before this process holds the chip
    from transmogrifai_tpu import native

    native.available()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        say(f"JAX found no usable backend: {e}")
        return 3
    dev = devices[0]
    if not args.rehearsal and (
        dev.platform != "tpu" or len(devices) < int(cell["chips"])
    ):
        say(
            f"{args.workload} needs {cell['chips']} TPU chip(s); JAX reports "
            f"{len(devices)} x {dev.platform} ({dev.device_kind}). "
            "--rehearsal is the only CPU path."
        )
        return 3
    from transmogrifai_tpu.compiler import cache as ccache
    from transmogrifai_tpu.compiler import stats as cstats

    cache_dir = ccache.enable_persistent_cache()
    say(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}; seed {args.seed}; rows {cfg['rows']}")

    ctx = Context(args, cfg, traffic)
    ctx.device_kind = dev.device_kind
    reported_e2e = [
        m["name"] for m in _metrics_of(bench, "end_to_end", cell["name"])
    ]

    # ---------------------------------------------------------------- set-up
    compiles_0 = cstats.snapshot()
    ctx.peak_of = dev
    driver.setup(ctx)
    ctx.peak_of = None
    ctx.counters["setup_compiles"] = cstats.delta(compiles_0)
    setup_s = time.monotonic() - T_START
    # where set-up's device peak was set: the first span that ended at it
    rises = [(n, b) for i, (n, b) in enumerate(ctx.peaks)
             if b > max([0] + [v for _n, v in ctx.peaks[:i]])]
    say("device peak by the end of set-up's spans: "
        + "; ".join(f"{n} {b}" for n, b in rises))

    # ---------------------------------------------------------------- window
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", f"{os.getpid()}")
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        ctx.tracing = True
    compiles_1 = cstats.snapshot()
    t_w0 = time.perf_counter()
    try:
        with ctx.span("window"):
            counts = driver.run(ctx)
    finally:
        if args.trace:
            ctx.tracing = False
            jax.profiler.stop_trace()
    window_s = time.perf_counter() - t_w0
    ctx.counters["window_compiles"] = cstats.delta(compiles_1)
    ctx.counters["window"] = counts
    say(f"set-up {setup_s:.1f}s; window {window_s:.1f}s; counts {counts}")
    totals: dict = {}
    for name, t0, t1 in ctx.spans:
        n, s = totals.get(name, (0, 0.0))
        totals[name] = (n + 1, s + t1 - t0)
    say("spans " + "; ".join(
        f"{k} x{n} {s:.2f}s" for k, (n, s) in totals.items()
    ) + f"; host max RSS {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f} GiB")

    stats = [d.memory_stats() or {} for d in devices[: int(cell["chips"])]]
    peaks = [int(m.get("peak_bytes_in_use", 0)) for m in stats]
    say(f"device bytes held after the window "
        f"{[int(m.get('bytes_in_use', 0)) for m in stats]}, peak {peaks}")
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
        # a CPU run reports no device reading
        "memory_peak_bytes": None if args.rehearsal else max(peaks),
    }
    ctx.counters["memory_peak_bytes"] = max(peaks)

    # ------------------------------------------------- the traced reduction
    trace = None
    if args.trace:
        from benchmarks.lib import trace_reduce

        trace = trace_reduce.reduce_dir(
            trace_dir, chips=int(cell["chips"]),
            require_device=not args.rehearsal,
        )
        if not args.rehearsal:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ----------------------------------------------------------- correctness
    t_c0 = time.perf_counter()
    compared = driver.check(ctx)
    say(f"reference check took {time.perf_counter() - t_c0:.1f}s")
    failed = int(counts.get("failed", 0))
    correct = failed == 0 and all(c["ok"] for c in compared)

    # --------------------------------------------------------------- metrics
    metrics: dict = {}
    if args.trace:
        for m in _metrics_of(bench, "per_layer", cell["name"], reported_e2e):
            if args.rehearsal and m["source"] in DEVICE_SOURCES:
                continue
            reader = by_name("layer_metrics", m["name"])
            value = reader.read(trace, ctx.spans, ctx.counters, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s, **driver.end_to_end(ctx)}
        for m in _metrics_of(bench, "end_to_end", cell["name"]):
            metrics[m["name"]] = {
                "value": values[m["name"]], "unit": m["unit"]
            }
    result = {
        "correct": bool(correct),
        "attempted": int(counts.get("attempted", 0)),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if trace is not None and not args.rehearsal:
        result["breakdown"] = trace["breakdown"]
    # a number that could not be read (a shape that differs, a grid point
    # that is missing) is infinite: written as a string, to stay JSON
    result["compared"] = {
        c["name"]: {
            "value": c["value"] if c["value"] == c["value"]
            and abs(c["value"]) != float("inf") else repr(c["value"]),
            "limit": c["limit"],
        }
        for c in compared
    }
    for c in compared:
        say(f"compared {c['name']}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAIL'}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    from transmogrifai_tpu.utils import aot

    aot._drain_exports()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
