"""From a profiler trace (``.xplane.pb``) to busy time, idle gaps and top ops.

The JAX profiler writes one plane per device (``/device:TPU:<i>``) whose
``XLA Ops`` line holds one event per executed operation, and host planes
whose thread lines hold the benchmark's ``bench:<name>`` spans
(``jax.profiler.TraceAnnotation``) on the same clock. The reduction:

* ``busy_s``: the union of the device-op intervals inside the window span,
  averaged over the chips used; ``window_s``: the ``bench:window`` span;
* idle time: the spaces between busy intervals, cut at span boundaries,
  each piece under the innermost benchmark span that covers it;
* top ops: device seconds summed by the name XLA gives the operation.

All of it works on plain ``(name, start_ns, duration_ns)`` tuples, so the
arithmetic is tested without a profiler (tests/bench).
"""
from __future__ import annotations

import glob
import os

SPAN_PREFIX = "bench:"
OPS_LINE = "XLA Ops"
SHORT_GAP_NS = 50_000


def load(path: str) -> dict:
    """{"devices": {plane name: [events]}, "spans": [events]} of one
    ``.xplane.pb`` file; an event is (name, start_ns, duration_ns)."""
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_profile(profile) -> dict:
    devices: dict[str, list] = {}
    spans: list = []
    for plane in profile.planes:
        is_device = plane.name.startswith("/device:") and "CUSTOM" not in plane.name
        for line in plane.lines:
            if is_device:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events
                    )
            else:
                spans.extend(
                    (e.name[len(SPAN_PREFIX):], int(e.start_ns), int(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX)
                )
    return {"devices": devices, "spans": spans}


def busy_union(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [start, end) intervals of ``events`` clipped to [lo, hi)."""
    cut = sorted(
        (max(s, lo), min(s + d, hi)) for _n, s, d in events
        if s < hi and s + d > lo
    )
    merged: list[list[int]] = []
    for s, e in cut:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def idle_gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def covering_span(spans, t: int, skip=("window",)) -> str:
    """Name of the innermost (shortest) span that covers instant ``t``."""
    best, best_d = "other", None
    for name, s, d in spans:
        if name in skip or not (s <= t < s + d):
            continue
        if best_d is None or d < best_d:
            best, best_d = name, d
    return best


def idle_by_span(spans, lo: int, hi: int) -> dict[str, int]:
    """Nanoseconds of the idle gap [lo, hi) under each span name: the gap is
    cut where a span starts or ends, and each piece goes to the innermost
    span that covers it. The spaces between back-to-back ops are the
    device's own, not the host's: one bucket, no span lookup."""
    if hi - lo < SHORT_GAP_NS:
        return {"between_ops": hi - lo}
    cuts = {lo, hi}
    for _n, s, d in spans:
        cuts.update(t for t in (s, s + d) if lo < t < hi)
    cuts = sorted(cuts)
    out: dict[str, int] = {}
    for a, b in zip(cuts, cuts[1:]):
        name = covering_span(spans, (a + b) // 2)
        out[name] = out.get(name, 0) + b - a
    return out


def short_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def is_container(name: str) -> bool:
    """Control flow that only wraps other ops of the same line: counting
    it beside its body would count the body twice."""
    body = name.split(" = ", 1)[-1]
    return any(f" {op}(" in f" {body}" for op in ("while", "conditional", "call"))


def top_ops(events, lo: int, hi: int, k: int = 10) -> list[list]:
    total: dict[str, int] = {}
    for name, s, d in events:
        if s < hi and s + d > lo and not is_container(name):
            name = short_name(name)
            total[name] = total.get(name, 0) + min(s + d, hi) - max(s, lo)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce(data: dict, chips: int = 1, require_device: bool = True) -> dict:
    """The numbers a traced run reports (seconds), from ``load``'s dict.
    Without a device plane (the CPU rehearsal) the device numbers are
    None."""
    spans = data["spans"]
    windows = [(s, s + d) for n, s, d in spans if n == "window"]
    if not windows:
        raise ValueError("the trace holds no bench:window span")
    lo, hi = windows[0]
    planes = sorted(data["devices"])[:chips]
    if not planes:
        if require_device:
            raise ValueError("the trace holds no device plane with XLA ops")
        return {
            "busy_s": None, "window_s": (hi - lo) / 1e9, "breakdown": None,
            "idle_by_span_s": {}, "step_prepare_s": [],
            "steps": sum(1 for n, _s, _d in spans if n == "sweep"),
        }
    busy_by = {p: busy_union(data["devices"][p], lo, hi) for p in planes}
    busy_ns = sum(e - s for iv in busy_by.values() for s, e in iv) / len(planes)
    first = planes[0]
    by_span: dict[str, int] = {}
    for s, e in idle_gaps(busy_by[first], lo, hi):
        for name, ns in idle_by_span(spans, s, e).items():
            by_span[name] = by_span.get(name, 0) + ns
    ranked = sorted(by_span.items(), key=lambda kv: -kv[1])
    # per step: from the step span's start to the first device op inside it
    starts = [s for s, _e in busy_by[first]]
    prepare = []
    for n, s, d in spans:
        if n == "sweep":
            inside = [t for t in starts if s <= t < s + d]
            if inside:
                prepare.append((inside[0] - s) / 1e9)
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "idle_by_span_s": {k: v / 1e9 for k, v in ranked},
        "step_prepare_s": prepare,
        "steps": sum(1 for n, _s, _d in spans if n == "sweep"),
        "breakdown": {
            "device_ops": top_ops(data["devices"][first], lo, hi, 10),
            "idle_gaps": [[k, v / 1e9] for k, v in ranked[:10]],
        },
    }


def reduce_dir(trace_dir: str, chips: int = 1, require_device: bool = True) -> dict:
    """Reduce the newest ``.xplane.pb`` under a profiler output directory."""
    found = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(load(found[-1]), chips=chips, require_device=require_device)
